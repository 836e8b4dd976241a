"""DLRM (Criteo click-through) with shardable embedding tables.

Architecture parity with the reference's notebook model
(examples/pytorch_dlrm.ipynb: 13 dense features → bottom MLP [512,128,32],
26 categorical embeddings of dim 32, pairwise dot interaction with the padded
tril flattening, top MLP [1024,1024,512,256,1], BCEWithLogits loss).

TPU-first design: the interaction is a batched matmul that tiles onto the MXU;
embedding tables are the memory hog, so each ``Embed`` kernel can be sharded
row-wise over the mesh's ``expert`` axis via
:func:`raydp_tpu.models.dlrm.dlrm_param_rules` — XLA turns the lookups into
gathers with the appropriate collectives, which is the reference's
"sparse embeddings want a model axis even for DP" hard part (SURVEY.md §7
step 5) solved by sharding annotation instead of a parameter server.

**The lookups are declared**, so the train step can update a table by the rows
a batch looked up instead of sweeping all of it (doc/training.md, "Embedding
tables: the row-wise update"). The protocol is two things on the module:

- ``lookups(inputs) -> {parameter path: ids [B]}``: which parameter (a tuple
  of keys into ``params``) each looked-up id tensor indexes along dim 0;
- ``__call__(inputs, rows=None)``: ``rows`` maps some of those paths to the
  float32 rows ``take(table, ids)`` already gathered by the caller; the
  forward then uses them (cast to the compute dtype) and never touches that
  table. Paths it is not given are looked up in the module's own parameters.

Both forms gather float32 rows first and cast the gathered rows: ``astype`` is
element-wise, so the values are those of casting the table and gathering, and
no table-sized cast is left in the step.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import flax.linen as nn
import jax.numpy as jnp
import numpy as np


def _tril_indices(n: int) -> Tuple[np.ndarray, np.ndarray]:
    rows = np.array([i for i in range(n) for _ in range(i)], dtype=np.int32)
    cols = np.array([j for i in range(n) for j in range(i)], dtype=np.int32)
    return rows, cols


class DotInteraction(nn.Module):
    """Pairwise dot products among the (1 + num_tables) feature vectors,
    concatenated with the bottom-MLP output and one zero pad (multiple-of-8
    width — also the MXU-friendly choice)."""

    @nn.compact
    def __call__(self, vectors: jnp.ndarray, bottom_out: jnp.ndarray):
        # vectors: [B, 1 + T, D]; bottom_out: [B, D]
        b, n, _ = vectors.shape
        inter = jnp.einsum("bnd,bmd->bnm", vectors, vectors)
        rows, cols = _tril_indices(n)
        flat = inter[:, rows, cols]                       # [B, n(n-1)/2]
        pad = jnp.zeros((b, 1), dtype=flat.dtype)
        return jnp.concatenate([bottom_out, flat, pad], axis=1)


class _Table(nn.Embed):
    """``nn.Embed`` (same parameter: ``<name>/embedding``) that gathers first
    and casts the gathered rows, where ``nn.Embed`` casts the whole table to
    ``dtype`` on every call."""

    def __call__(self, ids):
        return jnp.take(self.embedding, ids, axis=0).astype(
            self.dtype or self.embedding.dtype)


class DLRM(nn.Module):
    categorical_sizes: Sequence[int]
    num_dense: int = 13
    embedding_dim: int = 32
    bottom_mlp: Sequence[int] = (512, 128, 32)
    top_mlp: Sequence[int] = (1024, 1024, 512, 256, 1)
    dtype: Optional[jnp.dtype] = None

    @nn.nowrap
    def lookups(self, inputs: Dict[str, jnp.ndarray]):
        """The lookup declaration: parameter path -> the ids ``[B]`` that
        index it (one categorical column a table)."""
        return {(f"embedding_{i}", "embedding"): inputs["sparse"][:, i]
                for i in range(len(self.categorical_sizes))}

    @nn.compact
    def __call__(self, inputs: Dict[str, jnp.ndarray], rows=None):
        dense = inputs["dense"]          # [B, num_dense] float
        sparse = inputs["sparse"]        # [B, num_tables] int
        dtype = self.dtype or dense.dtype
        x = dense.astype(dtype)
        for w in self.bottom_mlp:
            x = nn.relu(nn.Dense(w, dtype=dtype)(x))
        bottom_out = x                   # [B, D] where D == embedding_dim

        embs = []
        for i, vocab in enumerate(self.categorical_sizes):
            given = (rows or {}).get((f"embedding_{i}", "embedding"))
            if given is not None:
                embs.append(given.astype(dtype))
                continue
            table = _Table(vocab, self.embedding_dim, dtype=dtype,
                           name=f"embedding_{i}")
            embs.append(table(sparse[:, i]))
        vectors = jnp.stack([bottom_out] + embs, axis=1)  # [B, 1+T, D]

        z = DotInteraction()(vectors, bottom_out)
        for w in self.top_mlp[:-1]:
            z = nn.relu(nn.Dense(w, dtype=dtype)(z))
        logit = nn.Dense(self.top_mlp[-1], dtype=dtype)(z)
        return logit.astype(jnp.float32)  # [B, 1] logits (BCE-with-logits loss)


def dlrm_param_rules(axis: str = "expert"):
    """Sharding rules: embedding tables row-sharded over ``axis``; MLPs
    replicated (pass to FlaxEstimator(param_rules=...))."""
    return [("embedding", (axis, None))]


def criteo_batch_preprocessor(num_dense: int = 13):
    """Split the estimator's flat batch into DLRM's dense/sparse dict.

    Matches the reference's column layout (_c1.._c13 dense float,
    _c14.._c39 categorical int, label _c0)."""

    def prep(batch):
        feats = batch["features"]
        dense = feats[:, :num_dense].astype(jnp.float32)
        sparse = feats[:, num_dense:].astype(jnp.int32)
        return {"dense": dense, "sparse": sparse}, batch["label"]

    return prep
