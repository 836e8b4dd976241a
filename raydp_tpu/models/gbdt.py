"""Histogram gradient-boosted decision trees, XLA-native.

The reference's third estimator wraps distributed XGBoost (Rabit collectives)
over Ray Train (reference: xgboost/estimator.py:54-81,
examples/xgboost_ray_nyctaxi.py:60-75). A TPU-native build cannot ride a CPU
tree library, so this module implements the algorithm the way the hardware
wants it — as dense, static-shape array programs:

- features are **quantile-binned once** on the host (the standard histogram
  trick); training sees only an ``int32 [n, f]`` bin matrix;
- trees grow **level-wise with a fixed max_depth**, so every per-level buffer
  (histograms ``[nodes, features, bins]``, split tables, leaf tables) has a
  static shape — no data-dependent control flow, one XLA compilation;
- per-level split finding is two ``segment_sum`` scatter-adds (gradient and
  hessian histograms) + a cumulative-sum gain scan + an argmax — all fusable,
  all data-parallel over rows, so sharding the row dimension over a mesh makes
  XLA insert ``psum``s for the histograms exactly where XGBoost's Rabit
  allreduce sits;
- the boosting loop is a ``lax.scan`` over rounds, carrying predictions and
  stacking per-tree tables; with eval sets / early stopping the scan runs in
  host-stepped chunks so per-round metrics come out without recompiling;
- multiclass (``multi:softmax`` / ``multi:softprob``) builds K one-vs-rest
  trees per round by ``vmap``-ing tree construction over the class axis of the
  softmax gradients — K trees for the price of one compilation;
- instance weights scale (g, h) before the histograms, xgboost-style.

A "no split" is represented as threshold ``num_bins - 1`` (every row routes
left), which lets gain-negative nodes degrade gracefully without ragged trees.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass
class GBDTModel:
    """A fitted forest: per-tree split/leaf tables + binning for inference.

    Table shapes: ``[T, nodes]`` for single-output objectives;
    ``[T, K, nodes]`` for multiclass (K trees per boosting round).
    """

    split_feature: np.ndarray   # [T, 2**depth - 1] or [T, K, 2**depth - 1]
    split_bin: np.ndarray       # same leading shape
    leaf_value: np.ndarray      # [T, 2**depth] or [T, K, 2**depth]
    bin_edges: np.ndarray       # [f, num_bins - 1] float32
    base_score: np.ndarray      # scalar, or [K] for multiclass
    max_depth: int
    objective: str
    best_iteration: Optional[int] = None   # set when early stopping fired

    @property
    def num_trees(self) -> int:
        return self.split_feature.shape[0]

    @property
    def num_class(self) -> int:
        return self.leaf_value.shape[1] if self.leaf_value.ndim == 3 else 1

    def predict(self, X: np.ndarray, output_margin: bool = False) -> np.ndarray:
        Xb = apply_bins(np.asarray(X, dtype=np.float32), self.bin_edges)
        margin = predict_binned(Xb, self.split_feature, self.split_bin,
                                self.leaf_value, self.max_depth)
        margin = margin + self.base_score
        if output_margin:
            return margin
        if self.objective == "binary:logistic":
            return 1.0 / (1.0 + np.exp(-margin))
        if self.objective == "multi:softprob":
            e = np.exp(margin - margin.max(axis=1, keepdims=True))
            return e / e.sum(axis=1, keepdims=True)
        if self.objective == "multi:softmax":
            return margin.argmax(axis=1).astype(np.float32)
        return margin


def make_bins(X: np.ndarray, num_bins: int = 256) -> np.ndarray:
    """Per-feature quantile bin edges ``[f, num_bins - 1]`` (host side, once)."""
    qs = np.linspace(0, 1, num_bins + 1)[1:-1]
    return np.quantile(X, qs, axis=0).T.astype(np.float32)


def apply_bins(X: np.ndarray, edges: np.ndarray) -> np.ndarray:
    """float features → int32 bin indices in ``[0, num_bins)``."""
    out = np.empty(X.shape, dtype=np.int32)
    for j in range(X.shape[1]):
        out[:, j] = np.searchsorted(edges[j], X[:, j], side="left")
    return out


def _grad_hess(pred, y, objective: str):
    """(g, h) per row — shape [n] (single-output) or [n, K] (multiclass)."""
    if objective == "binary:logistic":
        p = jax.nn.sigmoid(pred)
        return p - y, p * (1.0 - p)
    if objective.startswith("multi:"):
        K = pred.shape[1]
        p = jax.nn.softmax(pred, axis=-1)
        onehot = jax.nn.one_hot(y.astype(jnp.int32), K, dtype=pred.dtype)
        return p - onehot, p * (1.0 - p)
    # reg:squarederror — ½(pred − y)²
    return pred - y, jnp.ones_like(pred)


def _build_tree(Xb, g, h, *, max_depth: int, num_bins: int,
                learning_rate: float, reg_lambda: float,
                min_child_weight: float):
    """One tree for one (g, h) target; returns (split tables, leaf values,
    per-row update)."""
    n, f = Xb.shape
    num_internal = 2 ** max_depth - 1
    num_leaves = 2 ** max_depth
    rows = jnp.arange(n)
    feat_ids = jnp.arange(f)

    node = jnp.zeros(n, dtype=jnp.int32)  # level-local node index
    split_feature = jnp.zeros(num_internal, dtype=jnp.int32)
    split_bin = jnp.full(num_internal, num_bins - 1, dtype=jnp.int32)

    for depth in range(max_depth):  # static unroll: buffers double per level
        level_nodes = 2 ** depth
        offset = level_nodes - 1
        # histograms over (node, feature, bin) via one scatter-add each
        seg = (node[:, None] * f + feat_ids[None, :]) * num_bins + Xb
        num_segments = level_nodes * f * num_bins
        hist_g = jax.ops.segment_sum(
            jnp.broadcast_to(g[:, None], (n, f)).ravel(), seg.ravel(),
            num_segments=num_segments).reshape(level_nodes, f, num_bins)
        hist_h = jax.ops.segment_sum(
            jnp.broadcast_to(h[:, None], (n, f)).ravel(), seg.ravel(),
            num_segments=num_segments).reshape(level_nodes, f, num_bins)

        GL = jnp.cumsum(hist_g, axis=-1)
        HL = jnp.cumsum(hist_h, axis=-1)
        Gt = GL[..., -1:]
        Ht = HL[..., -1:]
        GR = Gt - GL
        HR = Ht - HL
        gain = (GL * GL / (HL + reg_lambda)
                + GR * GR / (HR + reg_lambda)
                - Gt * Gt / (Ht + reg_lambda))
        ok = (HL >= min_child_weight) & (HR >= min_child_weight)
        gain = jnp.where(ok, gain, -jnp.inf)
        # bin B-1 keeps everything left — the canonical "no split"
        gain = gain.at[..., num_bins - 1].set(0.0)

        flat = gain.reshape(level_nodes, f * num_bins)
        best = jnp.argmax(flat, axis=1)
        best_gain = jnp.take_along_axis(flat, best[:, None], axis=1)[:, 0]
        bf = (best // num_bins).astype(jnp.int32)
        bb = (best % num_bins).astype(jnp.int32)
        no_split = best_gain <= 0.0
        bf = jnp.where(no_split, 0, bf)
        bb = jnp.where(no_split, num_bins - 1, bb)

        idx = offset + jnp.arange(level_nodes)
        split_feature = split_feature.at[idx].set(bf)
        split_bin = split_bin.at[idx].set(bb)

        go_right = Xb[rows, bf[node]] > bb[node]
        node = node * 2 + go_right.astype(jnp.int32)

    leaf_g = jax.ops.segment_sum(g, node, num_segments=num_leaves)
    leaf_h = jax.ops.segment_sum(h, node, num_segments=num_leaves)
    leaf_value = (-leaf_g / (leaf_h + reg_lambda)
                  * learning_rate).astype(jnp.float32)
    return split_feature, split_bin, leaf_value, leaf_value[node]


def _boost_round(Xb, y, w, pred, build, objective: str):
    """ONE boosting round — the single copy of the per-round tree math every
    scan body shares (g/h weighting, the multiclass vmap, the margin
    update): returns ``(new_pred, (sf, sb, lv))``."""
    g, h = _grad_hess(pred, y, objective)
    if g.ndim == 2:  # multiclass: K trees via vmap over the class axis
        g = g * w[:, None]
        h = h * w[:, None]
        sf, sb, lv, upd = jax.vmap(
            lambda gk, hk: build(Xb, gk, hk),
            in_axes=1, out_axes=0)(g, h)     # tables [K, ...], upd [K, n]
        return pred + upd.T, (sf, sb, lv)
    sf, sb, lv, upd = build(Xb, g * w, h * w)
    return pred + upd, (sf, sb, lv)


def _route(Xb, sf, sb, leaves, max_depth: int):
    """Route every row of a binned matrix through one tree — the single
    routing walk (also the in-scan eval predictor)."""
    n = Xb.shape[0]
    rows = jnp.arange(n)
    node = jnp.zeros(n, dtype=jnp.int32)
    for depth in range(max_depth):
        offset = 2 ** depth - 1
        feat = sf[offset + node]
        thr = sb[offset + node]
        node = node * 2 + (Xb[rows, feat] > thr).astype(jnp.int32)
    return leaves[node]


@partial(jax.jit, static_argnames=(
    "chunk", "max_depth", "num_bins", "objective"))
def _boost_chunk(Xb, y, w, pred, *, chunk: int, max_depth: int, num_bins: int,
                 learning_rate: float, reg_lambda: float,
                 min_child_weight: float, objective: str):
    """``chunk`` boosting rounds from ``pred``; returns (stacked trees, pred).

    Compiled once per (shape, chunk); the host loop re-invokes it between
    eval/early-stop checks without recompiling.
    """
    build = partial(_build_tree, max_depth=max_depth, num_bins=num_bins,
                    learning_rate=learning_rate, reg_lambda=reg_lambda,
                    min_child_weight=min_child_weight)

    def boost(pred, _):
        return _boost_round(Xb, y, w, pred, build, objective)

    pred, trees = jax.lax.scan(boost, pred, None, length=chunk)
    return trees, pred


def _eval_metric_value(margin, y, objective: str):
    """In-jit twin of :func:`eval_metric`'s value (same formulas, jnp ops) —
    what the fused train+eval scan accumulates per round.

    KEEP IN SYNC with :func:`eval_metric` (host numpy/float64): the
    early-stopping path consumes that host version, and the two histories
    are pinned together by tests/test_gbdt.py's fused-eval parity test
    (rtol 1e-5) — edit both or that test fails."""
    if objective == "binary:logistic":
        p = 1.0 / (1.0 + jnp.exp(-margin))
        eps = 1e-7
        return -jnp.mean(y * jnp.log(p + eps) + (1 - y) * jnp.log(1 - p + eps))
    if objective.startswith("multi:"):
        e = jnp.exp(margin - margin.max(axis=1, keepdims=True))
        p = e / e.sum(axis=1, keepdims=True)
        rows = jnp.arange(y.shape[0])
        return -jnp.mean(jnp.log(p[rows, y.astype(jnp.int32)] + 1e-7))
    return jnp.sqrt(jnp.mean((margin - y) ** 2))


@partial(jax.jit, static_argnames=(
    "chunk", "max_depth", "num_bins", "objective"))
def _boost_chunk_eval(Xb, y, w, pred, eXb, ey, eval_margin, *, chunk: int,
                      max_depth: int, num_bins: int, learning_rate: float,
                      reg_lambda: float, min_child_weight: float,
                      objective: str):
    """``chunk`` rounds with the per-round eval-set metric computed ON
    DEVICE: one dispatch covers the whole train+eval history. The host
    per-round loop this replaces (still used for early stopping, whose
    keep/stop decision is host semantics) paid a tree-table fetch plus an
    eval dispatch every round."""
    build = partial(_build_tree, max_depth=max_depth, num_bins=num_bins,
                    learning_rate=learning_rate, reg_lambda=reg_lambda,
                    min_child_weight=min_child_weight)

    def boost(carry, _):
        pred, emargin = carry
        pred, (sf, sb, lv) = _boost_round(Xb, y, w, pred, build, objective)
        if sf.ndim == 2:  # multiclass: [K, nodes] tables → [en, K] margins
            emargin = emargin + jax.vmap(
                lambda s, b, l: _route(eXb, s, b, l, max_depth))(
                    sf, sb, lv).T
        else:
            emargin = emargin + _route(eXb, sf, sb, lv, max_depth)
        value = _eval_metric_value(emargin, ey, objective)
        return (pred, emargin), (sf, sb, lv, value)

    (pred, _), (sf, sb, lv, values) = jax.lax.scan(
        boost, (pred, eval_margin), None, length=chunk)
    return (sf, sb, lv), pred, values


@partial(jax.jit, static_argnames=("max_depth",))
def _predict_binned_jit(Xb, split_feature, split_bin, leaf_value,
                        max_depth: int):
    n = Xb.shape[0]

    def route(sf, sb, leaves):
        return _route(Xb, sf, sb, leaves, max_depth)

    def one_tree(pred, tree):
        sf, sb, leaves = tree
        if sf.ndim == 2:  # multiclass: [K, nodes] tables → [n, K] margins
            return pred + jax.vmap(route)(sf, sb, leaves).T, None
        return pred + route(sf, sb, leaves), None

    if split_feature.ndim == 3:
        pred0 = jnp.zeros((n, split_feature.shape[1]), dtype=jnp.float32)
    else:
        pred0 = jnp.zeros(n, dtype=jnp.float32)
    pred, _ = jax.lax.scan(one_tree, pred0,
                           (split_feature, split_bin, leaf_value))
    return pred


def predict_binned(Xb, split_feature, split_bin, leaf_value,
                   max_depth: int) -> np.ndarray:
    return np.asarray(_predict_binned_jit(
        jnp.asarray(Xb), jnp.asarray(split_feature), jnp.asarray(split_bin),
        jnp.asarray(leaf_value), max_depth))


def eval_metric(margin: np.ndarray, y: np.ndarray,
                objective: str) -> Tuple[str, float]:
    """The objective's default metric (xgboost naming).

    KEEP IN SYNC with :func:`_eval_metric_value` (the in-jit jnp/float32
    twin the fused boosting scan accumulates); the parity test in
    tests/test_gbdt.py pins the pair at rtol 1e-5."""
    if objective == "binary:logistic":
        p = 1.0 / (1.0 + np.exp(-margin))
        eps = 1e-7
        return "logloss", float(-np.mean(
            y * np.log(p + eps) + (1 - y) * np.log(1 - p + eps)))
    if objective.startswith("multi:"):
        e = np.exp(margin - margin.max(axis=1, keepdims=True))
        p = e / e.sum(axis=1, keepdims=True)
        rows = np.arange(len(y))
        return "mlogloss", float(-np.mean(
            np.log(p[rows, y.astype(np.int64)] + 1e-7)))
    return "rmse", float(np.sqrt(np.mean((margin - y) ** 2)))


def fit_gbdt(
    X: np.ndarray,
    y: np.ndarray,
    *,
    num_trees: int = 100,
    max_depth: int = 6,
    num_bins: int = 256,
    learning_rate: float = 0.3,
    reg_lambda: float = 1.0,
    min_child_weight: float = 1.0,
    objective: str = "reg:squarederror",
    num_class: Optional[int] = None,
    sample_weight: Optional[np.ndarray] = None,
    evals: Optional[Tuple[np.ndarray, np.ndarray]] = None,
    early_stopping_rounds: Optional[int] = None,
    bin_edges: Optional[np.ndarray] = None,
    mesh=None,
) -> Tuple[GBDTModel, np.ndarray, Dict[str, List[float]]]:
    """Fit a forest; returns (model, final train margins, evals_result).

    ``evals_result`` holds per-round eval metrics (reference behavior: the
    wrapped xgboost reports eval sets every boosting round,
    xgboost/estimator.py:54-81); empty when no ``evals`` given. With
    ``early_stopping_rounds`` the loop stops once the eval metric has not
    improved for that many rounds and the forest is truncated to the best
    iteration (recorded on ``model.best_iteration``).

    ``mesh`` shards the ROW dimension over the mesh's data axes: the
    per-level histograms become partial scatter-adds on each device with XLA
    inserting the cross-device reduction — the exact spot XGBoost's Rabit
    allreduce sits in the reference's distributed trainer. Split finding and
    tree tables stay replicated.
    """
    known = ("reg:squarederror", "binary:logistic", "multi:softmax",
             "multi:softprob")
    if objective not in known:
        raise ValueError(f"unsupported objective {objective!r}; have {known}")
    multi = objective.startswith("multi:")
    X = np.asarray(X, dtype=np.float32)
    y = np.asarray(y, dtype=np.float32)
    if bin_edges is None:
        bin_edges = make_bins(X, num_bins)
    Xb = apply_bins(X, bin_edges)
    w = (np.ones(len(y), np.float32) if sample_weight is None
         else np.asarray(sample_weight, np.float32))

    if multi:
        K = int(num_class or int(y.max()) + 1)
        counts = np.bincount(y.astype(np.int64), minlength=K) + 1.0
        base_score = np.log(counts / counts.sum()).astype(np.float32)
        pred = jnp.broadcast_to(jnp.asarray(base_score),
                                (len(y), K)).astype(jnp.float32)
    elif objective == "binary:logistic":
        p = float(np.clip(np.average(y, weights=w), 1e-6, 1 - 1e-6))
        base_score = np.float32(np.log(p / (1 - p)))
        pred = jnp.full(len(y), base_score, dtype=jnp.float32)
    else:
        base_score = np.float32(np.average(y, weights=w))
        pred = jnp.full(len(y), base_score, dtype=jnp.float32)

    kwargs = dict(max_depth=max_depth, num_bins=num_bins,
                  learning_rate=learning_rate, reg_lambda=reg_lambda,
                  min_child_weight=min_child_weight, objective=objective)
    n_orig = len(y)
    if mesh is not None:
        from raydp_tpu.parallel import batch_sharding
        from raydp_tpu.parallel.mesh import data_axes

        rows = batch_sharding(mesh)
        # static shapes: pad rows to the sharding divisor with zero-weight
        # rows (they contribute nothing to any histogram or leaf)
        total = int(np.prod([mesh.shape[a] for a in data_axes(mesh)]))
        pad = (-len(y)) % total
        if pad:
            Xb = np.concatenate([Xb, np.zeros((pad, Xb.shape[1]), Xb.dtype)])
            y = np.concatenate([y, np.zeros(pad, y.dtype)])
            w = np.concatenate([w, np.zeros(pad, w.dtype)])
            if multi:
                pred = jnp.concatenate(
                    [pred, jnp.broadcast_to(pred[0], (pad, pred.shape[1]))])
            else:
                pred = jnp.concatenate(
                    [pred, jnp.full(pad, pred[0], pred.dtype)])
        Xb_j = jax.device_put(jnp.asarray(Xb), rows)
        y_j = jax.device_put(jnp.asarray(y), rows)
        w_j = jax.device_put(jnp.asarray(w), rows)
        pred = jax.device_put(pred, rows)
    else:
        Xb_j, y_j, w_j = jnp.asarray(Xb), jnp.asarray(y), jnp.asarray(w)

    evals_result: Dict[str, List[float]] = {}
    if evals is None:
        # fast path: one scan over all rounds, no host round-trips
        trees, pred = _boost_chunk(Xb_j, y_j, w_j, pred, chunk=num_trees,
                                   **kwargs)
        tables = [np.asarray(t) for t in trees]
        best_iteration = None
    else:
        eX, ey = evals
        eXb = apply_bins(np.asarray(eX, np.float32), bin_edges)
        ey = np.asarray(ey, np.float32)
        if multi:
            eval_margin = np.broadcast_to(base_score,
                                          (len(ey), len(base_score))).copy()
        else:
            eval_margin = np.full(len(ey), base_score, np.float32)
        metric_name = eval_metric(eval_margin, ey, objective)[0]
        if early_stopping_rounds is None:
            # no host decisions between rounds: fuse training AND the
            # per-round eval into one device scan — one dispatch total
            trees, pred, values = _boost_chunk_eval(
                Xb_j, y_j, w_j, pred, jnp.asarray(eXb), jnp.asarray(ey),
                jnp.asarray(eval_margin), chunk=num_trees, **kwargs)
            tables = [np.asarray(t) for t in trees]
            history = [float(v) for v in np.asarray(values)]
            evals_result = {f"eval_{metric_name}": history}
            best_iteration = None
        else:
            # early stopping: the keep/stop decision is host semantics —
            # round-at-a-time with host metric checks
            parts: List[Tuple[np.ndarray, ...]] = []
            history: List[float] = []
            best, best_round = np.inf, -1
            for rnd in range(num_trees):
                trees, pred = _boost_chunk(Xb_j, y_j, w_j, pred, chunk=1,
                                           **kwargs)
                chunk_tables = tuple(np.asarray(t) for t in trees)
                parts.append(chunk_tables)
                eval_margin = eval_margin + predict_binned(
                    eXb, *chunk_tables, max_depth)
                _, value = eval_metric(eval_margin, ey, objective)
                history.append(value)
                if value < best - 1e-12:
                    best, best_round = value, rnd
                if rnd - best_round >= early_stopping_rounds:
                    break
            evals_result = {f"eval_{metric_name}": history}
            # a metric that never improves (NaN/inf) leaves best_round at -1:
            # keep at least the first round rather than an empty forest
            best_round = max(best_round, 0)
            keep = best_round + 1
            tables = [np.concatenate([p[i] for p in parts[:keep]], axis=0)
                      for i in range(3)]
            best_iteration = best_round
            if keep < len(parts):  # truncated: train margins must match
                pred = base_score + predict_binned(Xb, *tables, max_depth)

    model = GBDTModel(split_feature=tables[0], split_bin=tables[1],
                      leaf_value=tables[2], bin_edges=bin_edges,
                      base_score=np.asarray(base_score),
                      max_depth=max_depth, objective=objective,
                      best_iteration=best_iteration)
    return model, np.asarray(pred)[:n_orig], evals_result
