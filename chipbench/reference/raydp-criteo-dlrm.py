"""Plain reference of the DLRM of pang-wu/raydp ``examples/pytorch_dlrm.ipynb``
(facebookresearch/dlrm's architecture): bottom MLP on the 13 dense features,
26 embedding lookups, pairwise dot interaction over the 27 vectors (strict
lower triangle, row-major), concatenated after the bottom output with one zero
pad, top MLP to one logit. ReLU after every layer but the last. float32
``jax.numpy`` under ``highest`` matmul precision; no flax, no sharding: table
rows are looked up on the host from host copies of the tables.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# bfloat16 compute (one ulp = 2**-8 relative) through eight matmul layers and
# a 27x27 interaction, against float32. The error is the RMS difference over
# the RMS of the reference's logits (``harness.relative_rms_error``). 4 ulps: bfloat16 measures about 1 ulp
# (rehearsal on the CPU), a float32 program 0.00000x, and an 8-bit float (one
# ulp = 2**-4) could not pass.
TOLERANCE = 4 * 2.0 ** -8

def _dense(p, x):
    return x @ jnp.asarray(p["kernel"], jnp.float32) + jnp.asarray(
        p["bias"], jnp.float32)


def forward(variables: dict, inputs, cfg: dict) -> jnp.ndarray:
    dense, sparse = inputs
    params = variables["params"]
    tables = sorted((k for k in params if k.startswith("embedding_")),
                    key=lambda k: int(k.split("_")[1]))
    layers = sorted((k for k in params if k.startswith("Dense_")),
                    key=lambda k: int(k.split("_")[1]))
    n_bottom = len(cfg["model"]["bottom_mlp"])
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(dense, jnp.float32)
        for name in layers[:n_bottom]:
            x = jnp.maximum(_dense(params[name], x), 0)
        vectors = [x] + [
            jnp.asarray(np.asarray(params[t]["embedding"])[sparse[:, j]],
                        jnp.float32)
            for j, t in enumerate(tables)]
        v = jnp.stack(vectors, axis=1)                   # [B, 27, D]
        inter = jnp.einsum("bnd,bmd->bnm", v, v)
        rows, cols = np.tril_indices(v.shape[1], k=-1)
        z = jnp.concatenate([x, inter[:, rows, cols],
                             jnp.zeros((x.shape[0], 1), jnp.float32)], axis=1)
        for name in layers[n_bottom:-1]:
            z = jnp.maximum(_dense(params[name], z), 0)
        out = _dense(params[layers[-1]], z)
    return out[:, 0]

