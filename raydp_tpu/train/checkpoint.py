"""Checkpoint save/restore (parity: Ray Train Checkpoint usage,
torch/estimator.py:259-270, 392-396 — rank-0 writes, ``get_model`` rehydrates).

Two on-disk formats, selected by the process topology:

- **single process** — orbax ``PyTreeCheckpointer`` (chief-only,
  tf/estimator.py:202-210).
- **multi-process gang** — a *sharded* format: every process writes exactly the
  array shards it owns (``replica_id == 0`` filtering makes each unique index
  land once across the gang) as ``shard_<p>.npz`` + ``manifest_<p>.json``,
  with cross-process ``sync_global_devices`` barriers around the write and a
  chief-written ``COMPLETE`` marker for atomicity. This is what lets a gang
  train with parameters sharded *across* processes (fsdp/expert axes spanning
  hosts): no process ever needs to materialize the full state.

Checkpoints are ``step_<n>`` subdirectories; ``restore``/``restore_placed``
pick the latest complete one. Either format can be read back by either
topology (a driver process can reassemble a gang's sharded checkpoint).
Unlike the reference (no mid-training resume, SURVEY.md §5), a restored state
resumes the epoch loop where it left off.
"""

from __future__ import annotations

import glob
import json
import os
import shutil
from typing import Any, Optional, Tuple

import numpy as np

from raydp_tpu import profiler
from raydp_tpu.log import get_logger

logger = get_logger("train.checkpoint")

_KEEP = 2


def _is_complete(path: str) -> bool:
    """Sharded-format dirs need the chief's COMPLETE marker; orbax dirs count
    when orbax's own metadata landed. Anything else (e.g. a directory a gang
    created moments before a rank died, never written) is torn — restore must
    skip it and fall back to the previous step."""
    if os.path.exists(os.path.join(path, "COMPLETE")):
        return True
    if glob.glob(os.path.join(path, "manifest_*.json")):
        return False  # sharded write without the chief marker = torn
    # _METADATA / _CHECKPOINT_METADATA: current orbax; bare "checkpoint"
    # msgpack: older orbax aggregate format (pre-existing checkpoints must
    # not read as torn, or resume silently restarts from scratch)
    return os.path.exists(os.path.join(path, "_METADATA")) \
        or os.path.exists(os.path.join(path, "_CHECKPOINT_METADATA")) \
        or os.path.exists(os.path.join(path, "checkpoint"))


def _step_dirs(ckpt_dir: str, complete_only: bool = True):
    if not os.path.isdir(ckpt_dir):
        return []
    out = []
    for name in os.listdir(ckpt_dir):
        if name.startswith("step_"):
            try:
                path = os.path.join(ckpt_dir, name)
                if not complete_only or _is_complete(path):
                    out.append((int(name.split("_", 1)[1]), path))
            except ValueError:
                pass
    return sorted(out)


def _latest_agreed(ckpt_dir: str, max_step: Optional[int] = None
                   ) -> Optional[Tuple[int, str]]:
    """The ``(step, path)`` every rank will restore.

    Single process: the locally-latest complete step. Multi-process gang:
    ranks can disagree on which step is complete (lagging COMPLETE/manifest
    visibility on networked storage), and ranks resuming different epochs
    deadlock the first collective — so every rank takes the CHIEF's choice
    (broadcast), and a rank that cannot see that step fails fast with a
    shared-storage message instead of silently training from elsewhere.

    ``max_step`` bounds the choice: a fresh fit's retry passes the highest
    step it wrote itself, so stale higher-step dirs left in a reused
    checkpoint_dir by an earlier run are never adopted."""
    steps = _step_dirs(ckpt_dir)
    if max_step is not None:
        steps = [s for s in steps if s[0] <= max_step]
    import jax
    if jax.process_count() <= 1:
        return steps[-1] if steps else None
    from jax.experimental import multihost_utils
    local = steps[-1][0] if steps else -1
    chief = int(multihost_utils.broadcast_one_to_all(np.int32(local)))
    if chief < 0:
        return None
    for step, path in steps:
        if step == chief:
            return step, path
    raise FileNotFoundError(
        f"chief rank restores checkpoint step {chief} but rank "
        f"{jax.process_index()} only sees steps {[s for s, _ in steps]} in "
        f"{ckpt_dir!r}; multi-process gangs require checkpoint_dir on "
        "shared storage visible to every rank")


def warn_if_reused_dir(ckpt_dir: str) -> None:
    """A fresh (non-resume) fit pointed at a dir that already holds ``step_*``
    checkpoints: retention and retry-restore are scoped to THIS run's steps
    (``_latest_agreed(max_step=...)``), but a later explicit resume or
    ``restore()`` without ``max_step`` would silently prefer the foreign
    higher-numbered steps — tell the user the dir is reused up front."""
    steps = _step_dirs(ckpt_dir, complete_only=False)
    if steps:
        logger.warning(
            "checkpoint_dir %r already contains %d step_* checkpoint dir(s) "
            "(latest: step_%d) from an earlier run; this fit will not adopt "
            "them, but a later resume/restore() on this dir would — use a "
            "fresh checkpoint_dir per run to keep runs separate",
            ckpt_dir, len(steps), steps[-1][0])


def ensure_shared_dir(ckpt_dir: str, tag: str) -> None:
    """Gang-startup probe: the chief creates ``ckpt_dir``; every other rank
    must see it after a barrier, else the gang runs on per-host paths and a
    later save/resume deadlocks collectives. Fail fast with a shared-storage
    message instead. No-op single-process."""
    import jax
    if jax.process_count() <= 1:
        os.makedirs(ckpt_dir, exist_ok=True)
        return
    from jax.experimental import multihost_utils
    if jax.process_index() == 0:
        os.makedirs(ckpt_dir, exist_ok=True)
    multihost_utils.sync_global_devices(tag)
    if not os.path.isdir(ckpt_dir):
        raise RuntimeError(
            f"checkpoint_dir {ckpt_dir!r} is not visible on rank "
            f"{jax.process_index()}'s machine: multi-process gangs need "
            "shared storage for checkpoints — pass a checkpoint_dir on a "
            "filesystem mounted on every rank's host")


def _checkpointer():
    """An orbax checkpointer whose barriers never leave this process.

    Under a multi-process gang only the chief saves (and every rank restores
    independently from shared storage); stock orbax would run a
    ``sync_global_devices`` barrier across ALL processes inside save() —
    called from one rank, that deadlocks the gang (observed as a Gloo clique
    of one device per process timing out). ``active_processes={self}`` scopes
    every barrier to the calling process.
    """
    import jax
    # lazy on purpose: a process that never saves never pays it. The first
    # import of a process walks every installed distribution
    # (google.api_core) — seconds on a cold machine, hence its own span
    with profiler.trace("ckpt:import", "training"):
        import orbax.checkpoint as ocp

    if jax.process_count() > 1:
        from orbax.checkpoint.options import MultiprocessingOptions
        me = jax.process_index()
        return ocp.Checkpointer(
            ocp.PyTreeCheckpointHandler(),
            multiprocessing_options=MultiprocessingOptions(
                primary_host=me, active_processes={me},
                barrier_sync_key_prefix=f"proc{me}"))
    return ocp.PyTreeCheckpointer()


def _write_extra(path: str, ckpt_dir: str, step: int, extra: dict) -> None:
    tmp = os.path.join(ckpt_dir, f".extra_{step}.tmp")
    with open(tmp, "w") as f:
        json.dump(extra, f)
    os.replace(tmp, os.path.join(path, "extra.json"))


def _index_to_json(index, shape):
    out = []
    for i, sl in enumerate(index):
        start = 0 if sl.start is None else int(sl.start)
        stop = int(shape[i]) if sl.stop is None else int(sl.stop)
        out.append([start, stop])
    return out


def _flatten_with_keys(tree):
    import jax

    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    return [(jax.tree_util.keystr(kp), leaf) for kp, leaf in flat], treedef


def _raw(arr: np.ndarray) -> np.ndarray:
    """Flat uint8 view of an array's bytes. ``np.savez`` silently stores
    extension dtypes (ml_dtypes bfloat16 etc.) as raw void and cannot load
    them back, so every entry is stored as bytes and re-viewed through the
    manifest's dtype on load."""
    return np.frombuffer(np.ascontiguousarray(arr).tobytes(), np.uint8)


def _entry_array(npz, e: dict) -> np.ndarray:
    data = npz[e["arr"]]
    return data.view(np.dtype(e["dtype"])).reshape(
        [t - s for s, t in e["index"]])


def _save_sharded(ckpt_dir: str, state: Any, step: int,
                  extra: Optional[dict]) -> Tuple[str, int]:
    """Every gang process writes its owned shards; barriers make the write a
    gang-wide atomic step (COMPLETE marker last, chief-only). Returns the
    path and the bytes this process wrote."""
    import jax
    from jax.experimental import multihost_utils

    me = jax.process_index()
    path = os.path.join(os.path.abspath(ckpt_dir), f"step_{step}")
    if me == 0:
        os.makedirs(ckpt_dir, exist_ok=True)
        if os.path.exists(path):
            shutil.rmtree(path)
        os.makedirs(path)
    multihost_utils.sync_global_devices(f"rdt_ckpt_mk_{step}")

    with profiler.trace("ckpt:d2h", "training"):
        arrays, manifest = _owned_shards(state, me)
    with profiler.trace("ckpt:write", "training"):
        np.savez(os.path.join(path, f"shard_{me}.npz"), **arrays)
        with open(os.path.join(path, f"manifest_{me}.json"), "w") as f:
            json.dump(manifest, f)
        multihost_utils.sync_global_devices(f"rdt_ckpt_done_{step}")
        if me == 0:
            if extra is not None:
                _write_extra(path, ckpt_dir, step, extra)
            open(os.path.join(path, "COMPLETE"), "w").close()
            _prune(ckpt_dir, step)
    return path, sum(a.nbytes for a in arrays.values())


def _owned_shards(state: Any, me: int):
    """``(arrays, manifest)`` of the shards process ``me`` writes, copied to
    the host."""
    import jax

    flat, _ = _flatten_with_keys(state)
    arrays, manifest = {}, []
    n = 0
    for key, leaf in flat:
        is_global = (isinstance(leaf, jax.Array)
                     and hasattr(leaf, "addressable_shards")
                     and not leaf.is_fully_addressable)
        if is_global:
            # replica_id == 0 appears on exactly one device GANG-WIDE for a
            # global array, so each unique index lands once across processes
            for shard in leaf.addressable_shards:
                if shard.replica_id != 0:
                    continue
                name = f"a{n}"
                n += 1
                arrays[name] = _raw(np.asarray(shard.data))
                manifest.append({
                    "key": key, "arr": name,
                    "index": _index_to_json(shard.index, leaf.shape),
                    "shape": list(leaf.shape), "dtype": str(leaf.dtype)})
        elif me == 0:
            # process-local leaf (host scalar / numpy / fully-addressable
            # array): every process holds its own full copy with replica_id 0,
            # so the shard filter would dedup NOTHING — chief's value wins,
            # written once (orbax chief-only semantics for local state)
            arr = np.asarray(leaf)
            name = f"a{n}"
            n += 1
            arrays[name] = _raw(arr)
            manifest.append({"key": key, "arr": name,
                             "index": [[0, s] for s in arr.shape],
                             "shape": list(arr.shape),
                             "dtype": str(arr.dtype)})
    return arrays, manifest


def _prune(ckpt_dir: str, written_step: int) -> None:
    """Retention: keep the newest ``_KEEP`` steps AT OR BELOW the one just
    written. Bounding at ``written_step`` means stale higher-step dirs in a
    reused directory are left alone (they are foreign data, and pruning
    lower steps in their favor would delete the checkpoint written
    milliseconds earlier while keeping another run's)."""
    steps = [s for s in _step_dirs(ckpt_dir, complete_only=False)
             if s[0] <= written_step]
    for _, old in steps[:-_KEEP]:
        shutil.rmtree(old, ignore_errors=True)


def save(ckpt_dir: str, state: Any, step: int,
         extra: Optional[dict] = None) -> Optional[str]:
    """Checkpoint write. Single-process: chief-only orbax. Gang: every process
    writes its shards (call from ALL ranks — it contains barriers). ``extra``
    is a JSON-serializable sidecar (e.g. the accumulated epoch history, so a
    restarted gang's result is not truncated to post-restart epochs)."""
    import jax

    with profiler.trace("ckpt:save", "training", step=step) as span:
        if jax.process_count() > 1:
            path, nbytes = _save_sharded(ckpt_dir, state, step, extra)
        else:
            os.makedirs(ckpt_dir, exist_ok=True)
            path = os.path.join(os.path.abspath(ckpt_dir), f"step_{step}")
            if os.path.exists(path):
                shutil.rmtree(path)
            ckptr = _checkpointer()
            with profiler.trace("ckpt:d2h", "training"):
                host_state = jax.device_get(state)
            with profiler.trace("ckpt:write", "training"):
                with ckptr:
                    ckptr.save(path, host_state)
                if extra is not None:
                    _write_extra(path, ckpt_dir, step, extra)
                _prune(ckpt_dir, step)
            nbytes = sum(getattr(leaf, "nbytes", 0)
                         for leaf in jax.tree.leaves(host_state))
        profiler.add_args(span, bytes=nbytes)
    return path


def _load_manifests(path: str) -> dict:
    """key → list of (entry, shard_file) across every process's manifest."""
    entries: dict = {}
    for mf in sorted(glob.glob(os.path.join(path, "manifest_*.json"))):
        shard_file = mf.replace("manifest_", "shard_")[:-len(".json")] + ".npz"
        with open(mf) as f:
            for e in json.load(f):
                entries.setdefault(e["key"], []).append((e, shard_file))
    return entries


class _NpzCache:
    """Open-once NpzFile cache; close() after assembly (retry loops restore
    repeatedly — leaked zip handles would accumulate fds for the process
    lifetime)."""

    def __init__(self):
        self._files: dict = {}

    def __call__(self, fpath: str):
        npz = self._files.get(fpath)
        if npz is None:
            npz = self._files[fpath] = np.load(fpath)
        return npz

    def close(self) -> None:
        for npz in self._files.values():
            try:
                npz.close()
            except Exception:
                pass
        self._files.clear()


def _assemble_full(recs, files: "_NpzCache") -> np.ndarray:
    e0 = recs[0][0]
    full = np.empty(tuple(e0["shape"]), dtype=np.dtype(e0["dtype"]))
    for e, fpath in recs:
        full[tuple(slice(s, t) for s, t in e["index"])] = \
            _entry_array(files(fpath), e)
    return full


def _restore_sharded_host(path: str, template: Any) -> Any:
    """Reassemble full host arrays (any process count) from a sharded-format
    checkpoint into the structure of ``template``."""
    import jax

    entries = _load_manifests(path)
    flat, treedef = jax.tree_util.tree_flatten_with_path(template)
    files = _NpzCache()
    try:
        out = []
        for kp, _ in flat:
            key = jax.tree_util.keystr(kp)
            recs = entries.get(key)
            if not recs:
                raise KeyError(f"checkpoint at {path} is missing leaf {key}")
            out.append(_assemble_full(recs, files))
    finally:
        files.close()
    return jax.tree_util.tree_unflatten(treedef, out)


def _restore_sharded_placed(path: str, template: Any, shardings: Any) -> Any:
    """Place a sharded-format checkpoint directly under ``shardings`` reading
    only the shards THIS process addresses (exact index match — the
    unchanged-topology resume case). A leaf whose saved indices do not line up
    with the requested sharding falls back to full assembly for that leaf, so
    resharded restores still work; the common gang restart never materializes
    the full state."""
    import jax

    entries = _load_manifests(path)
    flat_t, treedef = jax.tree_util.tree_flatten_with_path(template)
    flat_s = treedef.flatten_up_to(shardings)
    files = _NpzCache()
    try:
        out = []
        for (kp, _), sharding in zip(flat_t, flat_s):
            key = jax.tree_util.keystr(kp)
            recs = entries.get(key)
            if not recs:
                raise KeyError(f"checkpoint at {path} is missing leaf {key}")
            e0 = recs[0][0]
            shape = tuple(e0["shape"])
            by_index = {tuple(tuple(se) for se in e["index"]): (e, f)
                        for e, f in recs}
            fallback: list = []  # assembled lazily, shared by the callbacks

            def cb(idx, by_index=by_index, recs=recs, shape=shape,
                   fallback=fallback):
                norm = tuple(
                    (0 if sl.start is None else int(sl.start),
                     shape[i] if sl.stop is None else int(sl.stop))
                    for i, sl in enumerate(idx))
                hit = by_index.get(norm)
                if hit is not None:
                    return _entry_array(files(hit[1]), hit[0])
                if not fallback:
                    fallback.append(_assemble_full(recs, files))
                return fallback[0][tuple(slice(s, t) for s, t in norm)]

            # make_array_from_callback runs the callbacks eagerly, so the
            # npz handles are drained before the finally closes them
            out.append(jax.make_array_from_callback(shape, sharding, cb))
    finally:
        files.close()
    return jax.tree_util.tree_unflatten(treedef, out)


def _host_template(template: Any) -> Any:
    """A host-side zeros tree with the template's shapes/dtypes — safe to build
    even when the template's leaves are cross-process global arrays (which
    ``device_get`` would reject)."""
    import jax

    return jax.tree.map(
        lambda x: np.zeros(getattr(x, "shape", ()),
                           getattr(x, "dtype", np.float32))
        if hasattr(x, "shape") else x, template)


def restore(ckpt_dir: str, template: Any) -> Optional[Tuple[Any, int]]:
    """Restore the latest checkpoint as HOST arrays into the structure of
    ``template``. Reads either format. Returns ``(state, step)`` or None.
    """
    latest = _latest_agreed(ckpt_dir)
    if latest is None:
        return None
    step, path = latest
    if glob.glob(os.path.join(path, "manifest_*.json")):
        return _restore_sharded_host(path, template), step
    with _checkpointer() as ckptr:
        restored = ckptr.restore(path, item=_host_template(template))
    return restored, step


def place_tree(tree: Any, shardings: Any) -> Any:
    """Place a host pytree under global shardings.

    Single-process: plain sharded ``device_put``. Multi-process gang:
    ``make_array_from_callback`` — every process holds the full host value
    (same rng / same restored checkpoint), each device reads its shard.
    """
    import jax

    if jax.process_count() > 1:
        def _put(x, s):
            if x is None:
                return None
            host = np.asarray(x)
            return jax.make_array_from_callback(
                host.shape, s, lambda idx: host[idx])
    else:
        def _put(x, s):
            return None if x is None else jax.device_put(x, s)
    return jax.tree.map(_put, tree, shardings, is_leaf=lambda x: x is None)


def restore_placed(ckpt_dir: str, template: Any, shardings: Any,
                   max_step: Optional[int] = None
                   ) -> Optional[Tuple[Any, int]]:
    """Restore the latest checkpoint and place it under ``shardings`` —
    correct in both single-process and gang topologies, for both formats.
    Sharded-format checkpoints restore shard-locally (each process reads only
    what its devices address). Returns ``(placed_state, step)`` or None.
    ``max_step`` restricts to steps the caller knows are its own (see
    :func:`_latest_agreed`)."""
    latest = _latest_agreed(ckpt_dir, max_step=max_step)
    if latest is None:
        return None
    step, path = latest
    if glob.glob(os.path.join(path, "manifest_*.json")):
        return _restore_sharded_placed(path, template, shardings), step
    with _checkpointer() as ckptr:
        host_state = ckptr.restore(path, item=_host_template(template))
    return place_tree(host_state, shardings), step


def restore_extra(ckpt_dir: str, max_step: Optional[int] = None
                  ) -> Optional[dict]:
    """The JSON sidecar of the latest checkpoint, or None. Gang-agreed like
    the state restore: divergent epoch bookkeeping would desynchronize the
    ranks' collective counts."""
    import json

    latest = _latest_agreed(ckpt_dir, max_step=max_step)
    if latest is None:
        return None
    path = os.path.join(latest[1], "extra.json")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)
