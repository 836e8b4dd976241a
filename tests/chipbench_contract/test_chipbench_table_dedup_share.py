"""``table_dedup_share``: the reader on a hand-made trace and on nothing (as
on a program without the scope), the entry where the manifest lists it, and
the scope it reads in the program's own lowered step."""

import os

import pytest

from chipbench import manifest

M = manifest.load_manifest()
NAME = "table_dedup_share"
#: the cells whose model declares tables: the only ones that de-duplicate ids
CELLS = ["dlrm_criteo_stream", "dlrm_criteo_dp2ep2"]


def _reader():
    return manifest.load_module(manifest.ROOT, "layer_metrics", f"{NAME}.py")


def _proto(fields):
    """Serialize ``[(number, value)]``: bytes length-delimited, ints varint."""
    def varint(n):
        out = bytearray()
        while True:
            out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
            n >>= 7
            if not n:
                return bytes(out)
    out = b""
    for number, value in fields:
        if isinstance(value, int):
            out += varint(number << 3) + varint(value)
        else:
            value = value.encode() if isinstance(value, str) else value
            out += varint(number << 3 | 2) + varint(len(value)) + value
    return out


def _xplane(path, instructions, events, stored=True):
    """An ``.xplane.pb`` whose one device plane holds the given ``XLA Ops``
    events ``(name, start_us, duration_us)``. ``stored``: the
    ``/host:metadata`` plane stores one program with the given ``{instruction
    name: op_name}`` (else a small program of the host's alone, as a trace
    beside live programs of the CPU client does: PERF.md, PR 63)."""
    computation = _proto([(1, "main")] + [
        (2, _proto([(1, name), (2, "fusion"), (7, _proto([(2, op_name)]))]))
        for name, op_name in (instructions if stored else
                              {"add.1": "jit(add)/add"}).items()])
    hlo = _proto([(1, _proto([(1, "jit_train_step" if stored else "jit_add"),
                              (3, computation)]))])
    metadata = _proto([
        (2, "/host:metadata"),
        (5, _proto([(1, 9), (2, _proto([(1, 9), (2, "Hlo Proto")]))])),
        (4, _proto([(1, 1), (2, _proto([
            (1, 1), (2, "jit_train_step(1)"),
            (5, _proto([(1, 9), (6, hlo)]))]))]))])
    ids = {name: i + 1 for i, name in enumerate(
        dict.fromkeys(e[0] for e in events))}
    device = _proto(
        [(1, 1), (2, "/device:TPU:0"),
         (5, _proto([(1, 8), (2, _proto([(1, 8), (2, "hlo_category")]))])),
         (3, _proto(
            [(1, 1), (2, "XLA Ops"), (3, 1000)] + [
                (4, _proto([(1, ids[name]), (2, int(start * 1e6)),
                            (3, int(dur * 1e6))]))
                for name, start, dur in events]))] + [
            (4, _proto([(1, i), (2, _proto(
                [(1, i), (2, f"%{name} = x"),
                 (5, _proto([(1, 8), (5, "fusion")]))]))]))
            for name, i in ids.items()])
    path.write_bytes(_proto([(1, device), (1, metadata)]))
    return str(path)


STEP = "jit(train_step)/"
DEDUP = STEP + "table_dedup/"
#: a four-chip DLRM step as the chip's compiler names it: the pass under its
#: scope (the gather of the batch's ids too), the walks and the model outside
#: it
PROGRAM = {
    "all-gather.1": DEDUP + "concatenate",
    "sort.2": DEDUP + "sort",
    "fusion.3": DEDUP + "jit(cumsum)/cumsum",
    "sort.4": DEDUP + "sort",
    "sort.5": DEDUP + "sort",
    "fusion.6": STEP + "shard_map/while/body/gather",
    "fusion.7": STEP + "jvp(DLRM)/bottom_0/dot_general",
    "all-reduce.8": STEP + "shard_map/while/body/psum",
    "fusion.9": STEP + "table_dedup_not/its/scope",
}
#: (name, start µs within the step, duration µs): 0.7 ms under the scope of
#: 5.0 ms busy
STEP_EVENTS = [("all-gather.1", 0, 50), ("sort.2", 100, 300),
               ("fusion.3", 500, 100), ("sort.4", 700, 150),
               ("sort.5", 900, 100),
               ("fusion.6", 1100, 2000), ("fusion.7", 3200, 1000),
               ("all-reduce.8", 4300, 1000), ("fusion.9", 5400, 300)]
UNDER, BUSY = 0.0007, 0.005


def _run(tmp_path, steps=2, program=PROGRAM, **how):
    from chipbench.trace import reduce as reducer

    events = [(name, 10000 * i + start, dur) for i in range(steps)
              for name, start, dur in STEP_EVENTS]
    xplane = _xplane(tmp_path / f"t{steps}.xplane.pb", program, events,
                     **how)
    return {"cell": CELLS[1], "trace": reducer.reduce(xplane),
            "xplane": xplane, "chips": 1, "counters": {}}


@pytest.mark.parametrize("steps", [1, 2, 5])
def test_reader_on_a_hand_made_trace(tmp_path, steps):
    """Busy seconds under the scope over all busy seconds, whatever the
    number of traced steps; an op whose scope only starts like it is not
    under it."""
    run = _run(tmp_path, steps)
    assert run["trace"]["busy_s"] == pytest.approx(steps * BUSY)
    assert _reader().read(run) == pytest.approx(100 * UNDER / BUSY)


@pytest.mark.parametrize("what", [
    "parent_of_the_scope", "no_program_of_the_steps", "no_xplane",
    "no_trace", "nothing_busy"])
def test_reader_that_finds_nothing_says_nothing(tmp_path, what):
    """A program compiled without the scope (the parent's: a ``jnp.unique`` a
    table, its sorts under no name of the step's) reads None and does not
    raise; so do a trace that stores no program of the step's and a run that
    kept no trace."""
    parent = {name: op.replace("table_dedup/", "")
              for name, op in PROGRAM.items()}
    run = _run(tmp_path, program=parent if what == "parent_of_the_scope"
               else PROGRAM, stored=what != "no_program_of_the_steps")
    if what == "no_xplane":
        run = dict(run, xplane=None)
    elif what == "no_trace":
        run = dict(run, trace=None)
    elif what == "nothing_busy":
        run = dict(run, trace=dict(run["trace"], busy_s=0.0))
    assert _reader().read(run) is None


def test_the_entry_is_present_once_and_lists_the_cells_with_tables():
    """Where in ``per_layer`` it stands is a later PR's to change."""
    assert manifest.validate(M) == []
    (entry,) = [m for m in M["per_layer"] if m["name"] == NAME]
    assert entry == {"name": NAME, "unit": "%", "better": "lower",
                     "source": "device_trace", "layer": "model",
                     "moves": "train_throughput", "workloads": CELLS}
    assert os.path.isfile(os.path.join(
        manifest.ROOT, manifest.BENCH_DIR, "layer_metrics", f"{NAME}.py"))


@pytest.mark.parametrize("cell", [w["name"] for w in M["workloads"]])
def test_it_resolves_in_the_cells_it_lists_and_in_no_other(cell):
    resolved = manifest.resolve(M, cell)
    listed = cell in CELLS
    assert (NAME in resolved.readers) == listed
    assert (NAME in [m["name"] for m in resolved.per_layer]) == listed
    if listed:
        assert "train_throughput" in {m["name"] for m in resolved.end_to_end}


def test_scope_the_reader_reads_is_the_programs():
    """A program that de-duplicates its tables' ids under the scope keeps it
    in the op names of the sort; one that does not (a parent of the scope)
    keeps nothing of the name, and the reader stays silent there."""
    import jax
    import jax.numpy as jnp

    from raydp_tpu.train import rowwise

    def dedup(a, b):
        of = getattr(rowwise, "unique_rows_of", None)
        if of is None:      # the parent: a pass a table, no scope
            return [rowwise.unique_rows(i, 100)[1] for i in (a, b)]
        return list(of({("a",): a, ("b",): b},
                       {("a",): 100, ("b",): 50})[1].values())

    ids = jnp.zeros((8,), jnp.int32)
    text = jax.jit(dedup).lower(ids, ids).as_text(debug_info=True)
    named = [line for line in text.splitlines() if "table_dedup/" in line]
    if hasattr(rowwise, "unique_rows_of"):
        assert any("sort" in line for line in named)
    else:
        assert not named
