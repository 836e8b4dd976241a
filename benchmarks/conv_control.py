"""The convolution cell's controls, alone on the chip: what set the tolerance
of ``lfm2_8ba1b_8k_train``. Run by no cell; every reading of ``PERF.md``
section 6, PR 61, that no cell's result line holds names the mode that gave
it.

``--control`` (``benchmarks/looped_control.py``'s, handed this cell and its
faults) fits the cell for three epochs through the normal path
(``harness.fit_once``, the cell's own pipeline, ``--seed``'s rows) and then
runs check (a) as the harness does (``program_outputs`` against
``reference_outputs`` on the reference's ``SAMPLE``, ``relative_rms_error``
beside the reference's ``TOLERANCE``), followed by the same comparison with
one thing wrong at a time. Each prints ``compared <name>: <error> limit
<TOLERANCE> correct <true|false>``:

- ``program``: check (a) itself, which has to read correct;
- ``reference_at_<dtype>``: the reference with every product's operands (the
  convolution's gates and taps among them) rounded to bfloat16, float8_e5m2
  and float8_e4m3fn, against the float32 reference. The 8-bit ones are the
  nearest precision below the configuration's and have to read NOT correct;
- planted faults, the program's outputs against a reference with one piece
  changed (what check (a) would read if the program differed from the
  reference in that piece): ``a_tap_missing`` (the convolution's oldest tap
  is zero), ``gate_before_missing`` (``conv(B)`` for ``conv(B * z)``),
  ``split_order_swapped`` (``C`` and ``z`` change places),
  ``no_head_norm`` (q and k go to RoPE as projected), ``picked_by_bare_score``
  (the bias left out of the top-4) and ``epsilon_1`` (1.0 beside the chosen
  scores' sum).

It ends with the device's ``memory_stats()`` after the fit, whole.

``--by-scope <trace dir>`` lists a traced run's busiest ops with the
``op_name`` the compiler kept for each, and the busy seconds under the
model's scopes (same checkout, same call as the ``--trace 1`` run: the
machine is thrown away).

Needs a TPU: everything runs at the cell's size. Tier-1 holds the same
reference to the program at a tiny size (``tests/test_conv_moe_lm.py``).

Run: python benchmarks/conv_control.py --control [--seed N]
     python benchmarks/conv_control.py --by-scope chipbench/out/lfm2_8ba1b_8k_train/trace
"""

from __future__ import annotations

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks.looped_control import by_scope, control  # noqa: E402

CELL = "lfm2_8ba1b_8k_train"


def _faults(ref):
    """name -> what to set on the reference's module: one piece of the
    reference changed at a time."""
    import jax.numpy as jnp

    short_conv, rms_norm, experts = ref._short_conv, ref._rms_norm, \
        ref._experts

    def a_tap_missing(p, u, cfg):
        return short_conv(dict(p, conv=jnp.asarray(p["conv"]).at[0].set(0)),
                          u, cfg)

    def reordered(order):
        def operator(p, u, cfg):
            d = u.shape[-1]
            kernel = jnp.asarray(p["in_proj"]["kernel"])
            parts = [kernel[:, i * d:(i + 1) * d] for i in order]
            return short_conv(dict(p, in_proj={
                "kernel": jnp.concatenate(parts, axis=1)}), u, cfg)
        return operator

    def gate_before_missing(p, u, cfg):
        # z's columns give ones: B * 1
        d = u.shape[-1]
        proj = ref._mm(u, ref._f32(p["in_proj"]["kernel"]))
        b_in, c_in = proj[..., :d], proj[..., d:2 * d]
        k, t = cfg["conv_L_cache"], u.shape[1]
        padded = jnp.pad(b_in, ((0, 0), (k - 1, 0), (0, 0)))
        conv = sum(ref._f32(p["conv"])[j] * padded[:, j:j + t]
                   for j in range(k))
        return ref._mm(c_in * conv, ref._f32(p["out_proj"]["kernel"]))

    def no_head_norm(x, scale, eps):
        # the layers' norms are 2048 wide, a head's 64
        return x if x.shape[-1] == 64 else rms_norm(x, scale, eps)

    def picked_by_bare_score(p, m, bias, cfg):
        return experts(p, m, jnp.zeros_like(ref._f32(bias)), cfg)

    return {"a_tap_missing": {"_short_conv": a_tap_missing},
            "gate_before_missing": {"_short_conv": gate_before_missing},
            "split_order_swapped": {"_short_conv": reordered((0, 2, 1))},
            "no_head_norm": {"_rms_norm": no_head_norm},
            "picked_by_bare_score": {"_experts": picked_by_bare_score},
            "epsilon_1": {"ROUTE_EPS": 1.0}}


SCOPES = {
    "short_conv": lambda s: "/short_conv/" in s,
    "short_conv/in_proj": lambda s: "/short_conv/in_proj/" in s,
    "short_conv/conv": lambda s: "/short_conv/conv/" in s,
    "short_conv/out_proj": lambda s: "/short_conv/out_proj/" in s,
    "attn": lambda s: "/attn/" in s,
    "mlp": lambda s: "/mlp/" in s,
    "moe": lambda s: "/moe/" in s,
    "lm_head_loss": lambda s: "lm_head_loss" in s,
    "embed": lambda s: "/embed/" in s,
    "no scope of the model's": lambda s: "TransformerLM" not in s,
}


def main(argv=None) -> int:
    from chipbench import manifest

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--by-scope", metavar="TRACE_DIR")
    ap.add_argument("--seed", type=int, default=61)
    args = ap.parse_args(argv)
    if args.by_scope:
        by_scope(args.by_scope, SCOPES)
    if args.control:
        from raydp_tpu.utils import compile_cache_dir
        compile_cache_dir()
        control(manifest.resolve(manifest.load_manifest(), CELL), args.seed,
                _faults, None, "conv_control")
    return 0


if __name__ == "__main__":
    sys.exit(main())
