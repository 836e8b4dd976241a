"""Training metrics (parity: the torchmetrics wrapper, torch/torch_metrics.py).

The reference wraps torchmetrics objects with per-epoch update/compute/reset
(torch_metrics.py:21-55). Here each metric is a pair of pure functions so the
update runs *inside* the jitted step (no host sync per batch): ``update`` maps a
batch's (predictions, labels) to summable statistics, ``compute`` turns the
accumulated statistics into the final value on the host at epoch end.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Sequence, Union

import jax.numpy as jnp
import numpy as np


def _row_weights(labels, mask):
    """Per-ELEMENT weights from a per-row 0/1 mask (pad-and-mask feeds):
    broadcast the mask over the label's trailing dims so a padded row's
    elements weigh 0 in both the statistic sum and the count. ``mask=None``
    weighs every element 1 — the pre-mask semantics exactly."""
    if mask is None:
        return jnp.ones_like(labels, dtype=jnp.float32)
    return jnp.broadcast_to(
        mask.reshape((-1,) + (1,) * (labels.ndim - 1)),
        labels.shape).astype(jnp.float32)


class Metric:
    name: str = "metric"

    def init(self) -> Dict[str, float]:
        return {"sum": 0.0, "count": 0.0}

    def update(self, stats, preds, labels, mask=None):
        raise NotImplementedError

    def compute(self, stats) -> float:
        return float(stats["sum"] / np.maximum(stats["count"], 1e-12))


class MSE(Metric):
    name = "mse"

    def update(self, stats, preds, labels, mask=None):
        w = _row_weights(labels, mask)
        err = jnp.sum(((preds - labels) ** 2) * w)
        return {"sum": stats["sum"] + err,
                "count": stats["count"] + jnp.sum(w)}


class RMSE(MSE):
    name = "rmse"

    def compute(self, stats) -> float:
        return float(np.sqrt(stats["sum"] / np.maximum(stats["count"], 1e-12)))


class MAE(Metric):
    name = "mae"

    def update(self, stats, preds, labels, mask=None):
        w = _row_weights(labels, mask)
        err = jnp.sum(jnp.abs(preds - labels) * w)
        return {"sum": stats["sum"] + err,
                "count": stats["count"] + jnp.sum(w)}


class Accuracy(Metric):
    name = "accuracy"

    def update(self, stats, preds, labels, mask=None):
        if preds.ndim > labels.ndim:
            pred_cls = jnp.argmax(preds, axis=-1)
        else:
            pred_cls = (preds > 0.5).astype(jnp.int32)
        hits = (pred_cls == labels.astype(pred_cls.dtype)).astype(jnp.float32)
        if mask is not None:
            hits = hits * mask
            rows = jnp.sum(mask)
        else:
            rows = labels.shape[0]
        return {"sum": stats["sum"] + jnp.sum(hits),
                "count": stats["count"] + rows}


class BinaryCrossEntropy(Metric):
    name = "bce"

    def update(self, stats, preds, labels, mask=None):
        w = _row_weights(labels, mask)
        p = jnp.clip(preds, 1e-7, 1 - 1e-7)
        ll = -jnp.sum((labels * jnp.log(p)
                       + (1 - labels) * jnp.log(1 - p)) * w)
        return {"sum": stats["sum"] + ll,
                "count": stats["count"] + jnp.sum(w)}


class ModelCounters(Metric):
    """What a model that brings its own loss counts beside it (the second
    output of its ``loss_rows``; ``model.loss_counters`` names each entry as
    a (registry metric, label) pair): a counter's entry is summed inside the
    jitted step like any metric's statistics and, at the epoch's end, added
    to the registry's counter; a gauge's keeps the last step's value and sets
    the gauge. It reports nothing into the epoch's history."""

    name = "model_counters"

    def __init__(self, names):
        from raydp_tpu import metrics as registry
        self.names = tuple(names)
        self.gauge = np.array([registry.METRICS[name].kind == registry.GAUGE
                               for name, _ in self.names])

    def init(self):
        return np.zeros(len(self.names), np.float32)

    def update(self, stats, preds, labels, mask=None):
        if not self.gauge.any():        # counters alone: the step as it was
            return stats + preds[1]
        return jnp.where(self.gauge, preds[1], stats + preds[1])

    def compute(self, stats) -> None:
        from raydp_tpu import metrics as registry
        for (name, label), gauge, value in zip(self.names, self.gauge, stats):
            if gauge:
                registry.set_gauge(name, float(value), label)
            else:
                registry.inc(name, float(value), label)


def model_counters(model) -> List[Metric]:
    names = getattr(model, "loss_counters", ())
    return [ModelCounters(names)] if names else []


_REGISTRY = {m.name: m for m in (MSE(), RMSE(), MAE(), Accuracy(),
                                 BinaryCrossEntropy())}
_REGISTRY["mean_squared_error"] = _REGISTRY["mse"]
_REGISTRY["mean_absolute_error"] = _REGISTRY["mae"]


def build_metrics(specs: Sequence[Union[str, Metric]]) -> List[Metric]:
    """Accept names or instances (parity: torch_metrics.py name-or-instance)."""
    out: List[Metric] = []
    for s in specs or []:
        if isinstance(s, Metric):
            out.append(s)
        elif isinstance(s, str):
            if s not in _REGISTRY:
                raise ValueError(f"unknown metric {s!r}; have {sorted(_REGISTRY)}")
            out.append(_REGISTRY[s])
        else:
            raise TypeError(f"metric spec must be str or Metric, got {type(s)}")
    return out
