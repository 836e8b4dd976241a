"""The Kimi Delta Attention cell's controls, alone on the chip: what set the
tolerance of ``kimi_linear_48ba3b_16k_train``. Run by no cell; every reading
of ``PERF.md`` section 6, PR 64, that no cell's result line holds names the
mode that gave it.

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
  convolution's taps and the scan's q, k, v among them) rounded to bfloat16,
  float8_e5m2 and float8_e4m3fn, against the float32 reference. The 8-bit
  ones are the nearest precision below the configuration's and have to read
  NOT correct;
- planted faults, the program's outputs against a reference with one piece
  changed (what check (a) would read if the program differed from the
  reference in that piece): ``beta_is_one`` (the delta rule writes with
  ``b = 1``), ``decay_in_bfloat16`` (``g`` rounded to bfloat16 before its
  ``exp``), ``decay_after_update`` (``S = diag(e^g)(S + b k (v - S^T k)^T)``),
  ``a_tap_missing`` (the convolution's oldest tap is zero), ``rotated`` (the shared key and the queries' last 64 rotated, RoPE theta
  1e4) and ``picked_by_bare_score`` (the bias left out of the top-8).

It ends with the device's ``memory_stats()`` after the fit, whole.

``--by-scope <trace dir>`` lists a traced run's busiest ops with the
``op_name`` the compiler kept for each, and the busy seconds under the
model's scopes (same checkout, same call as the ``--trace 1`` run: the
machine is thrown away).

Needs a TPU: everything runs at the cell's size. Tier-1 holds the same
reference to the program at a tiny size (``tests/test_kda_moe_lm.py``).

Run: python benchmarks/kda_control.py --control [--seed N]
     python benchmarks/kda_control.py --by-scope chipbench/out/kimi_linear_48ba3b_16k_train/trace
"""

from __future__ import annotations

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks.looped_control import by_scope, control  # noqa: E402

CELL = "kimi_linear_48ba3b_16k_train"


def _faults(ref):
    """name -> what to set on the reference's module: one piece of the
    reference changed at a time."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    beta, decay, conv_silu, experts, attention = (
        ref._beta, ref._decay, ref._conv_silu, ref._experts, ref._attention)

    def decay_in_bfloat16(p, u, heads, width):
        return decay(p, u, heads, width).astype(jnp.bfloat16).astype(
            jnp.float32)

    def decay_after_update(q, k, v, g, b):
        def step(state, at):
            qt, kt, vt, gt, bt = at
            seen = jnp.einsum("bhkv,bhk->bhv", state, kt)
            state = jnp.exp(gt)[..., None] * (
                state + (bt[..., None] * kt)[..., :, None]
                * (vt - seen)[..., None, :])
            return state, jnp.einsum("bhkv,bhk->bhv", state, qt)

        first = jnp.zeros(k.shape[:1] + k.shape[2:] + v.shape[3:],
                          jnp.float32)
        _, out = jax.lax.scan(step, first, tuple(
            jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, b)))
        return jnp.moveaxis(out, 0, 1)

    def rotated(p, u, cfg):
        # RoPE (interleaved pairs, theta 1e4) on k_r's columns of kv_a and
        # nothing else cannot be planted in the weights: rotate inside
        rank, nope = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"]
        half = cfg["qk_rope_head_dim"] // 2
        freqs = cfg["rope_theta"] ** (-np.arange(half) / half)
        angle = np.arange(u.shape[1])[:, None] * freqs[None, :]
        cos, sin = jnp.cos(angle), jnp.sin(angle)

        def turn(x):        # [..., T, (H,) 64], pairs (2i, 2i + 1)
            x1, x2 = x[..., 0::2], x[..., 1::2]
            c, s = (cos, sin) if x.ndim == 3 else (cos[:, None], sin[:, None])
            return jnp.stack([x1 * c - x2 * s, x1 * s + x2 * c],
                             axis=-1).reshape(x.shape)

        original = ref._mm

        def mm(a, b):
            out = original(a, b)
            if b.shape[-1] == rank + 2 * half:          # kv_a
                return jnp.concatenate(
                    [out[..., :rank], turn(out[..., rank:])], axis=-1)
            if b.shape[-1] == cfg["num_attention_heads"] * (nope + 2 * half) \
                    and a.shape[-1] == cfg["hidden_size"]:   # q
                heads = out.reshape(out.shape[:2] + (-1, nope + 2 * half))
                return jnp.concatenate(
                    [heads[..., :nope], turn(heads[..., nope:])],
                    axis=-1).reshape(out.shape)
            return out

        ref._mm = mm
        try:
            return attention(p, u, cfg)
        finally:
            ref._mm = original

    def picked_by_bare_score(p, m, bias, cfg, shared=True):
        return experts(p, m, jnp.zeros_like(ref._f32(bias)), cfg, shared)

    return {"beta_is_one": {"_beta": lambda p, u: jnp.ones_like(beta(p, u))},
            "decay_in_bfloat16": {"_decay": decay_in_bfloat16},
            "decay_after_update": {"_delta_rule": decay_after_update},
            "a_tap_missing": {"_conv_silu": lambda x, taps: conv_silu(
                x, taps.at[0].set(0))},
            "rotated": {"_attention": rotated},
            "picked_by_bare_score": {"_experts": picked_by_bare_score}}


SCOPES = {
    "kda": lambda s: "/kda/" in s,
    "kda/in_proj": lambda s: "/kda/in_proj/" in s,
    "kda/conv": lambda s: "/kda/conv/" in s,
    "kda/gate": lambda s: "/kda/gate/" in s,
    "kda/scan": lambda s: "/kda/scan/" in s,
    "kda/norm": lambda s: "/kda/norm/" in s,
    "kda/out_proj": lambda s: "/kda/out_proj/" in s,
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
    ap.add_argument("--seed", type=int, default=64)
    args = ap.parse_args(argv)
    if args.by_scope:
        by_scope(args.by_scope, SCOPES)
    if args.control:
        from raydp_tpu.utils import compile_cache_dir
        compile_cache_dir()
        control(manifest.resolve(manifest.load_manifest(), CELL), args.seed,
                _faults, None, "kda_control")
    return 0


if __name__ == "__main__":
    sys.exit(main())
