"""The looped cell's controls, alone on the chip: what set the tolerance of
``ouro_2p6b_8k_train`` and where its step's time and memory go. Run by no
cell; every reading of ``PERF.md`` section 6, PR 57, that no cell's result
line holds names the mode that gave it.

``--control`` fits the cell for three epochs through the normal path
(``harness.fit_once``, the cell's own pipeline, ``--seed``'s rows) and then
runs check (a) as the harness does (``program_outputs`` against
``reference_outputs`` on the reference's ``SAMPLE``, ``relative_rms_error``
beside the reference's ``TOLERANCE``), followed by the same comparison with
one thing wrong at a time. Each prints ``compared <name>: <error> limit
<TOLERANCE> correct <true|false>``:

- ``program``: check (a) itself, which has to read correct;
- ``reference_at_<dtype>``: the reference with every product's operands
  rounded to bfloat16, float8_e5m2 and float8_e4m3fn, against the float32
  reference. The 8-bit ones are the nearest precision below the
  configuration's and have to read NOT correct;
- planted faults, the program's outputs against a reference with one piece
  changed (what check (a) would read if the program differed from the
  reference in that piece): ``a_pass_dropped`` (the last pass's logits are
  the pass before's), ``no_norm_between_passes`` (the final norm ends the last
  pass alone), ``gate_reads_complement`` (``1 - lambda`` for ``lambda``) and
  ``gate_without_survival`` (``p_t = lambda_t`` for ``lambda_t S_{t-1}``).

It ends with the device's ``memory_stats()`` after the fit, whole.

``--by-scope <trace dir>`` lists a traced run's busiest ops with the
``op_name`` the compiler kept for each, and the busy seconds under the loop's
scopes (same checkout, same call as the ``--trace 1`` run: the machine is
thrown away).

Needs a TPU: everything runs at the cell's size. Tier-1 holds the same
reference to the program at a tiny size (``tests/test_looped_lm.py``).

Run: python benchmarks/looped_control.py --control [--seed N]
     python benchmarks/looped_control.py --by-scope chipbench/out/ouro_2p6b_8k_train/trace
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CELL = "ouro_2p6b_8k_train"
FIT_EPOCHS = 3
LOW_PRECISIONS = ("bfloat16", "float8_e5m2", "float8_e4m3fn")


@contextlib.contextmanager
def patched(module, patch: dict):
    """``module`` with the attributes of ``patch`` set, and then put back."""
    kept = {k: getattr(module, k) for k in patch}
    for k, v in patch.items():
        setattr(module, k, v)
    try:
        yield
    finally:
        for k, v in kept.items():
            setattr(module, k, v)


def _faults(ref):
    """name -> what to set on the reference's module: one piece of the
    reference changed at a time."""
    passes, probabilities = ref.passes, ref.exit_probabilities

    def a_pass_dropped(params, tokens, cfg, untied=None):
        hidden = passes(params, tokens, cfg, untied)
        return hidden[:-1] + [hidden[-2]]

    def no_norm_between_passes(params, tokens, cfg, untied=None):
        h = ref._f32(params["embed"]["embedding"])[tokens]
        out = []
        for _ in range(cfg["total_ut_steps"]):
            for i in range(cfg["layers"]):
                h = ref._layer(params[f"block_{i}"], h, cfg)
            out.append(ref._rms_norm(h, params["ln_f"]["scale"],
                                     cfg["rms_norm_eps"]))
        return out

    def gate_reads_complement(params, hidden):
        flipped = {"exit_gate": {
            "kernel": -ref._f32(params["exit_gate"]["kernel"]),
            "bias": -ref._f32(params["exit_gate"]["bias"])}}
        return probabilities(flipped, hidden)

    def gate_without_survival(params, hidden):
        import jax
        gate = params["exit_gate"]
        return [jax.nn.sigmoid(h @ ref._f32(gate["kernel"])[:, 0]
                               + ref._f32(gate["bias"])[0]) for h in hidden]

    return {"a_pass_dropped": {"passes": a_pass_dropped},
            "no_norm_between_passes": {"passes": no_norm_between_passes},
            "gate_reads_complement": {
                "exit_probabilities": gate_reads_complement},
            "gate_without_survival": {
                "exit_probabilities": gate_without_survival}}


def _exit_probabilities(cell, got, want) -> None:
    """The mean exit distribution the program and the reference compared."""
    import numpy as np

    scale = cell.pipeline.exit_scale(cell.cfg)
    passes = cell.cfg["total_ut_steps"]
    print("EXIT_PROBABILITIES program", (
        got[..., -passes:].mean(axis=(0, 1)) / scale).tolist(),
        "reference", (want[..., -passes:].mean(axis=(0, 1))
                      / scale).tolist(), "largest difference",
        float(np.abs(got[..., -passes:] - want[..., -passes:]).max()
              / scale), flush=True)


def control(cell, seed: int, faults=_faults, report=_exit_probabilities,
            name: str = "looped_control") -> dict:
    """Fit, then check (a) and its controls; returns name -> error.
    ``faults(reference module)``: the pieces to plant; ``report(cell, got,
    want)``: what else to print of the compared outputs (None: nothing);
    ``name``: the session's and the output directory's (another cell's
    control, ``benchmarks/conv_control.py``, hands its own)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import raydp_tpu
    from chipbench import harness
    from raydp_tpu.data import from_frame_recoverable
    from raydp_tpu.parallel import make_mesh

    ref, pipeline, cfg = cell.reference, cell.pipeline, cell.cfg
    out_dir = os.path.join(ROOT, "chipbench", "out", name)
    os.makedirs(out_dir, exist_ok=True)
    rows = int(cell.wl["rows"])
    path = harness.write_input(cell, rows, seed, out_dir)
    os.environ.update(harness.residency_env(cell, rows))
    session = raydp_tpu.init(name, num_executors=2,
                             executor_cores=2, executor_memory="2GB")
    errors = {}

    def said(name, err):
        errors[name] = err
        print(f"compared {name}: {err} limit {ref.TOLERANCE} correct "
              f"{str(err <= ref.TOLERANCE).lower()}", flush=True)

    try:
        df, info = pipeline.etl(session.read.parquet(path), cfg, cell.wl)
        mesh = make_mesh(None, devices=jax.devices()[:1])
        est, result, _, _ = harness.fit_once(cell, df.persist(), info, mesh,
                                             FIT_EPOCHS, [])
        print("LOSSES", [e["train_loss"] for e in result.history], flush=True)
        print("MEMORY " + json.dumps(jax.devices()[0].memory_stats()),
              flush=True)
        sample = ref.SAMPLE
        df, _ = pipeline.etl(session.read.parquet(harness.write_input(
            cell, sample["rows"], seed + 1, out_dir, parts=1)), cfg, cell.wl)
        df = df.persist()
        table = df.to_arrow()
        got = harness.program_outputs(
            est, from_frame_recoverable(df), sample["batch"],
            lambda out: pipeline.compared(out, cfg))
        variables = jax.device_get(est.get_model())

        def reference():
            return harness.reference_outputs(cell, variables, table, info,
                                             sample["batch"])

        want = reference()
        said("program", harness.relative_rms_error(got, want))
        if report is not None:
            report(cell, got, want)
        for dtype in LOW_PRECISIONS:
            said("reference_at_" + dtype, harness.relative_rms_error(
                ref.at_precision(jnp.dtype(dtype), reference), want))
        for fault, patch in faults(ref).items():
            with patched(ref, patch):
                said(fault, harness.relative_rms_error(got, reference()))
    finally:
        raydp_tpu.stop()
        harness.reap_children()
    print("CONTROL " + json.dumps({
        "seed": seed, "tolerance": ref.TOLERANCE,
        "shape": list(np.shape(got)), **errors}), flush=True)
    return errors


LOOP_SCOPES = {
    "loop, under a block": lambda s: "/loop/" in s and bool(
        re.search(r"/block_\d+/", s)),
    "attn": lambda s: "/attn/" in s,
    "mlp": lambda s: "/mlp/" in s,
    "lm_head_loss": lambda s: "lm_head_loss" in s,
    "exit_gate": lambda s: "/exit_gate/" in s,
    "final norm (ln_f)": lambda s: "/ln_f/" in s,
    "no scope of the model's": lambda s: "TransformerLM" not in s,
}


def _loop_labels() -> dict:
    from chipbench import manifest

    # the reader's own rule for what lies under the loop and under no block
    inside = manifest.load_module(ROOT, "layer_metrics",
                                  "loop_carry_share.py").INSIDE
    return {"loop, under no block (loop_carry_share)":
            lambda s: "/loop/" in s and not inside.search(s), **LOOP_SCOPES}


def by_scope(trace_dir: str, labels=None, top: int = 40) -> None:
    """``labels``: name -> predicate on an ``op_name`` (None: the looped
    cell's)."""
    from chipbench.trace import reduce as reducer, scopes

    labels = _loop_labels() if labels is None else labels
    xplane = reducer.find_xplane(trace_dir)
    reduced = reducer.reduce(xplane)
    names = scopes.op_names(xplane)
    busy = reduced["busy_s"]
    print(f"BUSY {busy:.6f} s of {reduced['window_s']:.6f}")
    ops = sorted(reduced["op_seconds"].items(), key=lambda kv: -kv[1])
    for op, sec in ops[:top]:
        print(f"OP {sec:9.6f} s {100 * sec / busy:6.2f}%  {op}  "
              f"{names.get(op, '(no op_name)')[-200:]}")
    for label, under in labels.items():
        sec = sum(s for op, s in ops if under(names.get(op, "")))
        print(f"SCOPE {sec:9.6f} s {100 * sec / busy:6.2f}%  {label}")


def main(argv=None) -> int:
    from chipbench import manifest

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--by-scope", metavar="TRACE_DIR")
    ap.add_argument("--seed", type=int, default=57)
    args = ap.parse_args(argv)
    if args.by_scope:
        by_scope(args.by_scope)
    if args.control:
        from raydp_tpu.utils import compile_cache_dir
        compile_cache_dir()
        control(manifest.resolve(manifest.load_manifest(), CELL), args.seed)
    return 0


if __name__ == "__main__":
    sys.exit(main())
