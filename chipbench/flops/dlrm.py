"""Operations one DLRM training sample needs, from the configuration's shapes.

Matrix multiplications only, 2 operations per multiply-add: the bottom MLP, the
pairwise interaction (one [1+T, D] x [D, 1+T] product) and the top MLP. The
backward pass costs twice the forward (a product for the input's gradient and
one for the weight's), so a training sample is 3x the forward. Embedding
lookups, the table update and elementwise work count zero: they move bytes.
"""

from __future__ import annotations


def mlp_forward_flops(widths) -> int:
    return sum(2 * a * b for a, b in zip(widths[:-1], widths[1:]))


def train_flops_per_item(cfg: dict, wl: dict, info: dict) -> float:
    m = cfg["model"]
    n_vec = 1 + len(m["table_rows"])
    dim = m["embedding_dim"]
    bottom = mlp_forward_flops([cfg["input"]["num_dense"], *m["bottom_mlp"]])
    interaction = 2 * n_vec * n_vec * dim
    top_in = dim + n_vec * (n_vec - 1) // 2 + 1
    top = mlp_forward_flops([top_in, *m["top_mlp"]])
    return 3.0 * (bottom + interaction + top)
