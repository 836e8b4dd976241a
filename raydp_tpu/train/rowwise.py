"""The row-wise embedding-table update: a train step that differentiates and
updates the rows a batch looked up, never the table (doc/training.md,
"Embedding tables: the row-wise update").

A model *declares* its lookups (``lookups(inputs) -> {parameter path: ids}``
and a forward that takes pre-gathered ``rows``; :class:`raydp_tpu.models.DLRM`
is the one that does). For each declared table with ids ``[B]`` the step

1. de-duplicates the ids at the static size ``B``: all the tables' ids in
   one stacked pass ``[T, B]`` of sorts (:func:`unique_rows_of`);
2. builds a *row view* of the parameters and of the optimizer state: every
   leaf that mirrors the table replaced by its ``uniq`` rows, everything else
   whole (:func:`index_trees`, :func:`take_rows`);
3. differentiates the loss w.r.t. that view — the forward reads
   ``view[inv]``, so the float32 gradient of a row is the sum over its
   duplicates — and calls the user's ``tx.update`` once on the view;
4. writes the ``uniq`` rows back into the donated table (:func:`put_rows`).

Whether that equals ``tx``'s dense result is a property of the optimizer, so
it is tried on a tiny tree before the step engages (:func:`same_as_dense`).

Shapes are static (nothing is lowered for a new batch), but the work is not:
a row read from or written to a table in HBM costs the chip 50 and 120 ns
(PERF.md, PR 25), and a Zipf batch of 4096 ids has some 1400 distinct ones.
So the lookup and the write-back walk ``uniq`` in passes of :data:`CHUNK`
rows and stop after the last real one; the fill ids past it are never
touched.

On a mesh the step is handed the shardings the state was placed with (a
traced leaf carries none). A table whose rows are split over mesh axes
(:func:`row_axes`) is then read and written per shard under ``shard_map``:
``uniq`` is sorted and a shard holds one contiguous range of rows, so its ids
are one slice of ``uniq``, and it walks that slice only (:func:`shard_rows`).
No collective runs inside a pass of the walk, since the shards make different
numbers of them. A sum over those axes after the walk puts the looked-up rows
together, and it carries the real rows only (:func:`sum_real_rows`): ``uniq``
holds the real ids first, the view's rows past them are zero on every shard,
and the table's global count is the same number on every shard, so the sum
runs in passes of :data:`SUM_PASS` rows, as many as hold real ids, a table's
parameter and accumulators in one all-reduce a pass. A table that is not
split by rows, or a step that was not told, keeps the one walk of all of
``uniq``, which the partitioner then runs on every chip that holds a part of
the table.
"""

from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

from raydp_tpu import metrics
from raydp_tpu.log import get_logger

logger = get_logger("train.rowwise")

#: in an index tree: this leaf is no row-wise table and stays whole
WHOLE = object()
#: rows a pass of the lookup and of the write-back handles
CHUNK = 256
#: a table this small (bytes of its rows padded to the chip's 128 lanes) is
#: not walked in passes. The rule reads the table the walk is handed: the
#: shard a chip holds where the walk runs per shard, the whole table where it
#: does not (one chip; on a mesh the partitioner then splits what the rule
#: judged whole). The TPU compiler stages such a table through fast memory
#: (128 MiB on a v5e) in a lane-padded row-major layout, and a scatter into
#: it then costs a sweep of it however few rows it writes: six passes ran
#: 1.28 ms against 0.23 ms for one pass over all B ids (143,091 x 32
#: float32, PERF.md PR 25).
STAGED_BYTES = 256 << 20
#: rows a pass of the sum over a table's row axes carries (a parameter's and
#: its accumulators' in one all-reduce). On four v5e chips, 8192 ids a table
#: of which ~2300 distinct, ten tables: passes of 512 ran the step in 10.96
#: ms, of 1024 in 10.98, one sum of all 8192 rows in 12.04 (CHANGES.md PR 34)
SUM_PASS = 512
#: how far the probe's two sides may lie apart, relative to the dense one:
#: eight units in a float32's last place
PROBE_RTOL = 1e-6

Path = Tuple[str, ...]


class Rows:
    """The rows a batch looked up in one table: ``uniq`` ``[B]`` (sorted, the
    real ids first) and how many of them are real. A leaf of an index tree
    (deliberately no pytree). Inside a shard of the table (:func:`shard_rows`)
    ``count`` ids from position ``first`` on are the shard's own."""

    __slots__ = ("uniq", "count", "first")

    def __init__(self, uniq, count, first=None):
        self.uniq, self.count, self.first = uniq, count, first

    def passes(self, table, visit, carry):
        """``carry`` after ``visit(start, ids, carry)`` over ``uniq`` in
        chunks, as many as hold the ``count`` ids from ``first`` on (one chunk
        of all of ``uniq`` for a ``table`` under :data:`STAGED_BYTES`). The
        last chunk is pulled back inside ``uniq``; rows it visits twice get
        the same values twice."""
        import jax.numpy as jnp
        from jax import lax

        b = self.uniq.shape[0]
        lanes = -(-int(np.prod(table.shape[1:], dtype=np.int64)) // 128) * 128
        if b <= CHUNK or (table.shape[0] * lanes * table.dtype.itemsize
                          <= STAGED_BYTES):
            return visit(0, self.uniq, carry)

        def body(i, carry):
            start = i * CHUNK if self.first is None \
                else self.first + i * CHUNK
            start = jnp.minimum(start, b - CHUNK)
            return visit(start, lax.dynamic_slice(self.uniq, (start,),
                                                  (CHUNK,)), carry)

        return lax.fori_loop(0, (self.count + CHUNK - 1) // CHUNK, body,
                             carry)


def row_axes(sharding, shape):
    """The mesh axes over which a leaf's rows are split, where each shard can
    walk its own: dim 0 sharded (over one axis or several) in equal parts and
    every other dim whole. ``None`` for anything else: no sharding told (the
    step was not handed the state's), a mesh of one, dim 0 whole, a
    column-sharded table."""
    spec = getattr(sharding, "spec", None)
    if not spec:
        return None
    extent = sharding.mesh.shape

    def split(entry):
        names = () if entry is None else \
            entry if isinstance(entry, tuple) else (entry,)
        return tuple(n for n in names if extent[n] > 1)

    axes = split(spec[0])
    shards = int(np.prod([extent[n] for n in axes]))
    if not axes or shape[0] % shards or any(split(e) for e in spec[1:]):
        return None
    return axes


def shard_rows(uniq, num_local: int, axes) -> Rows:
    """Inside a ``shard_map`` over ``axes``: the looked-up rows as the shard
    that holds ``num_local`` rows of the table sees them. Ids count from the
    shard's first row (negative, or ``num_local`` and more: another shard's);
    ``uniq`` is sorted and the table split in contiguous ranges, so the
    shard's own ids are one slice of it, and the walk covers that slice."""
    import jax.numpy as jnp
    from jax import lax

    first_id = lax.axis_index(axes) * num_local
    lo = jnp.sum(uniq < first_id, dtype=jnp.int32)
    hi = jnp.sum(uniq < first_id + num_local, dtype=jnp.int32)
    return Rows(uniq - first_id, hi - lo, lo)


def _names(path) -> Path:
    return tuple(str(getattr(k, "key", getattr(k, "name", k))) for k in path)


def leaf_at(tree, path: Path):
    """The leaf of a nested dict at a path of keys."""
    for key in path:
        tree = tree[key]
    return tree


def gains(table_shape, ids_shape) -> bool:
    """From shapes: does a table gain from the row-wise update? Only one that
    has more rows than the step looks up (one id a batch row)."""
    return len(ids_shape) == 1 and table_shape[0] > ids_shape[0]


def tables_to_update(apply_fn, state, batch, accum: int, seen: list,
                     placed=None):
    """``{parameter path: ids}`` of the declared tables THIS step updates by
    row, decided from what the step can observe: the model's declaration
    (``apply_fn.lookups``, set by the estimator's ``_make_apply``), no
    accumulation or pipeline, the shapes, and the probe of ``state.tx``.
    Counts every declared table once a built step (``seen``), the row-wise
    ones also by who walks them (``placed``: the parameters' shardings, where
    the step was told), and logs why the dense ones stayed dense."""
    lookups = getattr(apply_fn, "lookups", None)
    if lookups is None:
        return {}
    ids = {tuple(path): i for path, i in lookups(batch).items()}
    blocked = getattr(apply_fn, "rowwise_dense_because", None) or (
        "accum" if accum > 1 else None)
    because = {}
    for path, i in ids.items():
        if blocked:
            because[path] = blocked
        elif not gains(leaf_at(state.params, path).shape, i.shape):
            because[path] = "shape"
    wide = set(ids) - set(because)
    if wide and not same_as_dense(state.tx, state.params, wide):
        because.update(dict.fromkeys(wide, "probe"))
    if not seen:
        seen.append(True)
        for path, i in ids.items():
            metrics.inc("train_table_updates_total",
                        label="dense" if path in because else "rowwise")
            if path not in because:
                local = placed is not None and row_axes(
                    leaf_at(placed, path), leaf_at(state.params, path).shape)
                metrics.inc("train_table_walk_total",
                            label="shard_local" if local else "global")
                if local:
                    metrics.inc("train_table_sum_total",
                                label="real_rows" if i.shape[0] > SUM_PASS
                                else "all_rows")
        if because:
            why: Dict[str, list] = {}
            for path, reason in because.items():
                why.setdefault(reason, []).append("/".join(path))
            logger.info(
                "embedding tables: %d of %d declared update row-wise; dense "
                "because of %s", len(ids) - len(because), len(ids),
                "; ".join(f"{r}: {', '.join(t)}" for r, t in why.items()))
    return {p: i for p, i in ids.items() if p not in because}


def _dedup(ids, num_rows):
    """``(uniq, count, inv)`` ``[T, B]``, ``[T]``, ``[T, B]`` of the stacked
    ids ``[T, B]`` of tables of ``num_rows`` ``[T]`` rows: every table's ids
    de-duplicated at the static size ``B`` in one batched computation. A
    table's ``uniq`` is sorted, its tail filled with *distinct* ids past the
    table's end, ``num_rows[t] + arange(B)``; ``uniq[inv] == ids``. The
    arrays are ``jnp.unique(ids[t], size=B, return_inverse=True)``'s, bit for
    bit, but made of three sorts over the last axis and a running sum, with
    no gather and no scatter: the chip sorts 8192 ids with a payload in 7 us
    and moves 8192 scalars one index at a time in 60 to 70 (PERF.md, PR 63).

    The ids sorted with their positions; an id that differs from the one
    before it is the first of its run, and the running count of firsts is
    every sorted id's place in ``uniq``; sorted back by position that is
    ``inv``; the firsts sorted alone (the rest pushed to the end) are
    ``uniq``."""
    import jax.numpy as jnp
    from jax import lax

    t, b = ids.shape
    place = lax.broadcasted_iota(jnp.int32, (t, b), 1)
    ordered, came_from = lax.sort((ids, place), dimension=1, num_keys=1)
    first = jnp.concatenate([jnp.ones((t, 1), bool),
                             ordered[:, 1:] != ordered[:, :-1]], axis=1)
    rank = jnp.cumsum(first, axis=1, dtype=jnp.int32) - 1
    _, inv = lax.sort((came_from, rank), dimension=1, num_keys=1)
    uniq = lax.sort(jnp.where(first, ordered, jnp.iinfo(ids.dtype).max),
                    dimension=1)
    real = uniq < num_rows[:, None]
    uniq = jnp.where(real, uniq, num_rows[:, None] + place.astype(ids.dtype))
    return uniq, jnp.sum(real, axis=1, dtype=jnp.int32), inv


def unique_rows_of(tables: Dict[Path, object], num_rows: Dict[Path, int]):
    """``({path: rows}, {path: inv})`` of the ids ``[B]`` a step looked up in
    each of its row-wise ``tables`` (:func:`unique_rows` says what they
    hold), all in ONE pass over the stacked ids ``[T, B]`` (:func:`_dedup`).
    On a mesh the pass is written whole and the partitioner's to shard."""
    import jax
    import jax.numpy as jnp

    paths = list(tables)
    with jax.named_scope("table_dedup"):
        ids = jnp.stack([tables[path] for path in paths])
        sizes = jnp.asarray([num_rows[path] for path in paths], ids.dtype)
        uniq, count, inv = _dedup(ids, sizes)
        return ({path: Rows(uniq[i], count[i])
                 for i, path in enumerate(paths)},
                {path: inv[i] for i, path in enumerate(paths)})


def unique_rows(ids, num_rows: int):
    """``(rows, inv)`` of the ids ``[B]`` at the static size ``B``:
    ``rows.uniq`` sorted, its tail filled with *distinct* ids past the table's
    end (so a scatter may be told its indices are sorted and unique, and
    ``mode="drop"`` drops them); ``rows.uniq[inv] == ids``. The one-table
    case of :func:`unique_rows_of`."""
    rows, inv = unique_rows_of({(): ids}, {(): num_rows})
    return rows[()], inv[()]


def index_trees(tx, params, opt_state, uniq: Dict[Path, Rows]):
    """``(params_idx, state_idx)``: trees shaped like ``params`` and
    ``opt_state`` whose leaves are the table's :class:`Rows` where the leaf
    mirrors a row-wise table and :data:`WHOLE` elsewhere. A state leaf is
    matched to its parameter by where ``tx.init`` puts parameter trees, not
    by shape."""
    import jax
    import optax

    params_idx = jax.tree_util.tree_map_with_path(
        lambda path, _: uniq.get(_names(path), WHOLE), params)
    state_idx = optax.tree_utils.tree_map_params(
        tx, lambda _, u: u, opt_state, params_idx,
        transform_non_params=lambda _: WHOLE)
    return params_idx, state_idx


def take_rows(tree, idx, placed=None):
    """The row view of ``tree``: indexed leaves replaced by their ``uniq``
    rows ``[B, ...]`` (zeros where ``uniq`` holds a fill id; what is computed
    on those is never written). ``placed`` is ``tree``'s shardings where the
    caller knows them: a leaf whose rows are split over mesh axes
    (:func:`row_axes`) is read shard by shard, each shard its own slice of
    ``uniq``, and a sum over those axes of the real rows puts the view
    together (:func:`sum_real_rows`; one shard's rows and the others' zeros:
    exact). The leaves that mirror one table, its parameter and its
    accumulators, are read and summed together."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    leaves, treedef = jax.tree.flatten(tree)
    rows = treedef.flatten_up_to(idx)
    shardings = [None] * len(leaves) if placed is None \
        else treedef.flatten_up_to(placed)
    split: Dict[tuple, list] = {}   # (a table's Rows, mesh, axes): its leaves
    for i, (a, r, sharding) in enumerate(zip(leaves, rows, shardings)):
        if r is WHOLE:
            continue
        axes = row_axes(sharding, a.shape)
        if axes is not None:
            split.setdefault((id(r), sharding.mesh, axes), []).append(i)
            continue
        leaves[i] = r.passes(
            a, lambda start, ids, out, a=a: lax.dynamic_update_slice_in_dim(
                out, jnp.take(a, ids, axis=0, mode="clip",
                              indices_are_sorted=True, unique_indices=True),
                start, axis=0),
            jnp.zeros(r.uniq.shape + a.shape[1:], a.dtype))
    for (_, mesh, axes), members in split.items():
        views = _take_split([leaves[i] for i in members], rows[members[0]],
                            mesh, axes)
        for i, view in zip(members, views):
            leaves[i] = view
    return treedef.unflatten(leaves)


def _take_split(tables, rows: Rows, mesh, axes):
    """The views of ``tables``, leaves that looked up the same ``rows`` and
    whose rows are split over the mesh ``axes``: each shard reads its own
    slice of ``uniq``, then one sum puts the real rows of all of them
    together."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    def shard(tables, uniq, count):
        def walk(table):
            def visit(start, ids, out):
                own = (ids >= 0) & (ids < table.shape[0])
                got = jnp.take(table, ids, axis=0, mode="clip",
                               indices_are_sorted=True, unique_indices=True)
                return lax.dynamic_update_slice_in_dim(
                    out, jnp.where(lax.expand_dims(own, range(1, got.ndim)),
                                   got, 0), start, axis=0)

            # no collective inside the walk: shards differ in their passes
            return shard_rows(uniq, table.shape[0], axes).passes(
                table, visit, lax.pcast(
                    jnp.zeros(uniq.shape + table.shape[1:], table.dtype),
                    axes, to="varying"))

        return sum_real_rows([walk(table) for table in tables], count, axes)

    return jax.shard_map(shard, mesh=mesh, in_specs=(P(axes), P(), P()),
                         out_specs=P())(tables, rows.uniq, rows.count)


def sum_real_rows(views, count, axes):
    """Inside a ``shard_map`` over ``axes``: ``views`` ``[B, ...]``, each
    shard's own rows and zeros elsewhere, summed over the axes in their rows
    ``[0, count)``, in passes of :data:`SUM_PASS` rows, one ``psum`` of all
    the views a pass (the last pass pulled back inside ``B``). The rows past
    the last pass are zero on every shard and stay so, unsummed; a row below
    it is the sum one ``psum`` of all ``B`` rows would give.

    A collective in a loop, where the walk may hold none: ``count`` is the
    table's global count, computed from the replicated ``uniq``, so every
    shard makes the same number of passes."""
    import jax.numpy as jnp
    from jax import lax

    b = views[0].shape[0]
    if b <= SUM_PASS:
        return lax.psum(views, axes)

    def body(i, out):
        start = jnp.minimum(i * SUM_PASS, b - SUM_PASS)
        part = lax.psum([lax.dynamic_slice_in_dim(v, start, SUM_PASS)
                         for v in views], axes)
        return [lax.dynamic_update_slice_in_dim(o, p, start, axis=0)
                for o, p in zip(out, part)]

    return lax.fori_loop(0, (count + SUM_PASS - 1) // SUM_PASS, body,
                         [jnp.zeros(v.shape, v.dtype) for v in views])


def put_rows(tree, view, idx, placed=None):
    """``tree`` with the view written back: whole leaves replaced, indexed
    leaves updated in their real ``uniq`` rows and nowhere else. With the
    shardings ``placed``, a leaf whose rows are split over mesh axes is
    written shard by shard, each shard its own slice of ``uniq``."""
    import jax
    from jax import lax
    from jax.sharding import PartitionSpec as P

    def put(a, v, rows, sharding=None):
        if rows is WHOLE:
            return v
        axes = row_axes(sharding, a.shape)
        if axes is None:
            return rows.passes(
                a, lambda start, ids, table: table.at[ids].set(
                    lax.dynamic_slice_in_dim(v, start, ids.shape[0], axis=0)
                    .astype(a.dtype), mode="drop", indices_are_sorted=True,
                    unique_indices=True), a)

        # lax.scatter, not .at[]: an id below the shard's first row is
        # negative and dropped as it is (.at[] would count it from the end)
        into_rows = lax.ScatterDimensionNumbers(
            update_window_dims=tuple(range(1, a.ndim)),
            inserted_window_dims=(0,), scatter_dims_to_operand_dims=(0,))

        def shard(table, v, uniq):
            return shard_rows(uniq, table.shape[0], axes).passes(
                table, lambda start, ids, table: lax.scatter(
                    table, ids[:, None], lax.dynamic_slice_in_dim(
                        v, start, ids.shape[0], axis=0).astype(table.dtype),
                    into_rows, indices_are_sorted=True, unique_indices=True,
                    mode="drop"), table)

        return jax.shard_map(shard, mesh=sharding.mesh,
                             in_specs=(P(axes), P(), P()),
                             out_specs=P(axes))(a, v, rows.uniq)

    return jax.tree.map(put, tree, view, idx, *(() if placed is None
                                               else (placed,)))


def same_as_dense(tx, params, tables) -> bool:
    """The probe: does updating a row view give ``tx``'s own dense result?

    Tried on the host, as ONE compiled program that is dropped once it has
    answered, on a tiny tree of ``params``' structure (so a transformation
    that treats parameters by name sees the names): after one ordinary
    update, a gradient whose table rows 2 and 3 are zero goes through
    ``tx.update`` dense and through the row view of rows 0 and 1. They must
    agree to rounding (:data:`PROBE_RTOL`, relative alone: a zero on the rows
    the view skipped is a zero) in the updates and in every state leaf.
    ``adagrad`` and plain ``sgd`` do; ``adam`` (its moments decay on a
    skipped row), momentum and weight decay do not — for them a skipped row
    is a different result, and the step stays dense. Anything that raises on
    the way stays dense too.

    One dropped program, not eager ops: a program of the host's CPU client
    that is live while ``jax.profiler`` traces takes the place of every TPU
    program in the trace's ``/host:metadata``, and eager ops' ~70 small
    programs live as long as the process, so a trace of a row-wise fit named
    no op's scope (PERF.md, PR 63). The tiny tree is the program's argument,
    so that the compiler folds neither side into constants; what is left
    between the two sides of a passing optimizer is the order of a sum (a
    global norm over ``[4, 2]`` and over ``[3, 2]``), one unit in the last
    place."""
    import jax
    import jax.numpy as jnp
    import optax

    def tiny(path, p):
        shape = (4,) + (2,) * (p.ndim - 1) if _names(path) in tables \
            else (2,) * p.ndim
        n = int(np.prod(shape))
        return np.asarray(np.linspace(0.25, 1.0, n).reshape(shape), p.dtype)

    def touched(path, g):
        # the table rows a batch of ids {0, 1} looked up; 2 and 3 get nothing
        if _names(path) not in tables:
            return g
        return g * (jnp.arange(4) < 2).astype(g.dtype).reshape(
            (4,) + (1,) * (g.ndim - 1))

    def both(p0):
        g1 = jax.tree.map(lambda p: p * 0.5 - 0.4, p0)
        g2 = jax.tree_util.tree_map_with_path(
            touched, jax.tree.map(lambda p: p * -0.75 + 0.3, p0))
        u1, s1 = tx.update(g1, tx.init(p0), p0)
        p1 = optax.apply_updates(p0, u1)
        u_dense, s_dense = tx.update(g2, s1, p1)

        rows = Rows(jnp.asarray([0, 1, 6], jnp.int32), 2)  # 6: a fill id
        p_idx, s_idx = index_trees(tx, p1, s1, {t: rows for t in tables})
        u_view, s_view = tx.update(take_rows(g2, p_idx),
                                   take_rows(s1, s_idx),
                                   take_rows(p1, p_idx))
        u_rows = put_rows(jax.tree.map(jnp.zeros_like, u_dense), u_view,
                          p_idx)
        return (u_dense, s_dense), (u_rows, put_rows(s1, s_view, s_idx))

    try:
        cpu = jax.local_devices(backend="cpu")[0]
    except RuntimeError:
        cpu = None
    try:
        p0 = jax.tree_util.tree_map_with_path(tiny, params)
        with jax.default_device(cpu):
            dense, rowwise = jax.jit(both).lower(p0).compile()(p0)
        dense, dense_def = jax.tree.flatten(dense)
        rowwise, rowwise_def = jax.tree.flatten(rowwise)
        return dense_def == rowwise_def and all(
            np.allclose(a, b, rtol=PROBE_RTOL, atol=0.0)
            for a, b in zip(dense, rowwise))
    except Exception:  # noqa: BLE001 - an optimizer the view cannot serve
        return False
