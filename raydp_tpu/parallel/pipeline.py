"""Pipeline parallelism: collective GPipe over the mesh's ``stage`` axis.

The reference has no pipeline (or any non-data) parallelism (SURVEY.md §2.4);
this is part of the TPU build's complete strategy matrix (dp/fsdp/tp/sp/ep/pp).

TPU-idiomatic design — no per-stage processes, no send/recv runtime: ONE
compiled SPMD program under ``shard_map``. Per-stage parameters are stacked on
a leading axis and sharded over ``stage``; microbatches march through the
classic GPipe schedule inside a ``lax.scan``, activations hopping stage →
stage+1 with ``lax.ppermute`` each tick (on hardware these hops ride
neighboring ICI/DCN links — ``stage`` is the outermost mesh axis). The
backward pass needs no hand scheduling: AD of scan+ppermute IS the reverse
pipeline (ppermute transposes to the reverse permutation), so one
``jax.grad`` over :func:`pipeline_apply` trains the whole pipeline.

Total ticks = n_micro + n_stages - 1; the (n_stages - 1)-tick bubble is the
standard GPipe cost, amortized by more microbatches.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax


def stack_stage_params(param_trees) -> Any:
    """Stack per-stage parameter pytrees on a new leading 'stage' axis
    (stage-homogeneous layers: identical structure and shapes required)."""
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *param_trees)


def _pipeline_local(stage_params, x_micro, *, fn, stage_axis: str,
                    n_micro: int):
    """Per-stage body under shard_map. ``stage_params`` leaves arrive with
    leading axis ``layers_per_stage`` (this stage's contiguous slice of the
    layer stack); ``x_micro`` is [n_micro, ...] (batch dim possibly
    data-sharded)."""
    n_stages = lax.psum(1, stage_axis)
    s = lax.axis_index(stage_axis)
    # this stage's shard holds its CONTIGUOUS run of layers (leading dim =
    # layers_per_stage); apply them in order — one stage may own several
    layers_per_stage = jax.tree.leaves(stage_params)[0].shape[0]

    def apply_stage(x):
        for i in range(layers_per_stage):
            x = fn(jax.tree.map(lambda p: p[i], stage_params), x)
        return x

    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    # carry inits must vary over the union of the manual axes of everything
    # they mix with — the inputs' axes plus stage (state mixes with
    # params-derived activations from tick 1 on)
    from raydp_tpu.parallel.mesh import vary_manual
    in_vma = tuple(jax.typeof(x_micro).vma)
    vma = tuple(dict.fromkeys(in_vma + (stage_axis,)))
    state0 = vary_manual(jnp.zeros_like(x_micro[0]), vma)
    out0 = vary_manual(jnp.zeros_like(x_micro), vma)

    def tick(carry, t):
        state, outputs = carry
        # stage 0 injects microbatch t while t < n_micro; other stages
        # consume the activation that arrived from stage-1 on the last hop
        inject = x_micro[jnp.clip(t, 0, n_micro - 1)]
        cur = jnp.where(s == 0, inject, state)
        y = apply_stage(cur)
        # the last stage finished microbatch (t - (n_stages - 1))
        idx = t - (n_stages - 1)
        live = (s == n_stages - 1) & (idx >= 0)
        outputs = jnp.where(
            live, outputs.at[jnp.clip(idx, 0, n_micro - 1)].set(y), outputs)
        state = lax.ppermute(y, stage_axis, perm)
        return (state, outputs), None

    (_, outputs), _ = lax.scan(tick, (state0, out0),
                               jnp.arange(n_micro + n_stages - 1))
    # outputs live on the last stage only; replicate them across the axis
    # (masked psum — every other stage holds zeros)
    return lax.psum(jnp.where(s == n_stages - 1, outputs, 0.0), stage_axis)


def pipeline_apply(fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
                   stage_params: Any, x_micro: jnp.ndarray, mesh,
                   stage_axis: str = "stage"):
    """Run ``x_micro`` ([n_micro, mb, ...]) through ``n_stages`` pipeline
    stages; ``fn(params, x) -> y`` is one stage (y must have x's shape/dtype —
    stage-homogeneous pipelines, the transformer-block case).

    ``stage_params`` leaves are stacked [n_layers, ...]
    (:func:`stack_stage_params`; ``n_layers`` must be a multiple of
    ``n_stages`` — each stage applies its contiguous run of layers in order)
    and sharded over ``stage_axis``; returns
    [n_micro, mb, ...] outputs, replicated over the stage axis. The
    microbatch dim (axis 1) is sharded over the mesh's data axes inside the
    pipeline, so pp×dp does dp-partitioned work per stage rather than
    redundant replication; tp composes inside a stage as usual.
    Differentiable end-to-end: ``jax.grad`` of a loss over ``pipeline_apply``
    backpropagates through the scan + ppermute schedule (the reverse
    pipeline), with stage-sharded gradients landing on their stage.
    """
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    from raydp_tpu.parallel.mesh import data_axes

    n_stages = mesh.shape[stage_axis]
    n_micro = int(x_micro.shape[0])
    n_layers = stage_params_leading_dim(stage_params)
    if n_stages > 1 and n_layers % n_stages != 0:
        raise ValueError(
            f"{n_layers} stacked layers cannot split over {n_stages} pipeline "
            f"stages (must divide evenly; each stage applies its contiguous "
            f"run of layers in order)")
    if n_stages <= 1:
        # no stage axis: plain sequential application of every stage
        def seq_apply(x):
            for i in range(stage_params_leading_dim(stage_params)):
                x = fn(jax.tree.map(lambda p: p[i], stage_params), x)
            return x
        return jax.vmap(seq_apply)(x_micro)

    daxes = tuple(a for a in data_axes(mesh) if mesh.shape[a] > 1)
    dp = 1
    for a in daxes:
        dp *= mesh.shape[a]
    mb = int(x_micro.shape[1])
    pad = 0
    if daxes and mb % dp != 0:
        # microbatch not divisible by the data extent: pad zero rows up to
        # the next divisible count and slice them back off the outputs —
        # the pipeline stays dp-sharded instead of silently replicating
        # every microbatch (the pre-r17 fallback). Padded rows are zeros;
        # callers mask their loss rows the same way the feed's pad-and-mask
        # tail does, and the outputs sliced off here never reach a loss.
        pad = dp - mb % dp
        widths = [(0, 0)] * x_micro.ndim
        widths[1] = (0, pad)
        x_micro = jnp.pad(x_micro, widths)
        from raydp_tpu import metrics
        metrics.inc("train_padded_rows_total", pad * n_micro)
    if daxes:
        mspec = P(None, daxes if len(daxes) > 1 else daxes[0])
    else:  # single-device data extent: nothing to shard the rows over
        mspec = P()
    pspec = jax.tree.map(lambda _: P(stage_axis), stage_params)
    body = functools.partial(_pipeline_local, fn=fn, stage_axis=stage_axis,
                             n_micro=n_micro)
    out = shard_map(body, mesh=mesh,
                    in_specs=(pspec, mspec), out_specs=mspec)(
                        stage_params, x_micro)
    return out[:, :mb] if pad else out


def stage_params_leading_dim(stage_params) -> int:
    return int(jax.tree.leaves(stage_params)[0].shape[0])
