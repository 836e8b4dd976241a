"""Mesh construction and sharding helpers.

Axis convention (sizes multiply to the device count):

- ``stage``   — pipeline parallel: layer stages, activations ppermute forward
  (see :mod:`raydp_tpu.parallel.pipeline`).
- ``data``    — data parallel: batch dim sharded, params replicated, grad psum.
- ``fsdp``    — params+optimizer sharded over this axis, all-gathered per layer.
- ``tensor``  — tensor parallel (Megatron-style column/row splits).
- ``seq``     — sequence/context parallel (ring attention / all-to-all).
- ``expert``  — expert parallel (MoE experts and DLRM embedding shards).

On hardware, axis order maps inner axes to ICI neighbors — keep ``tensor``/
``seq`` innermost so their heavy collectives ride the fastest links, and
``stage`` outermost (its per-microbatch boundary hops are the rarest, and on
multi-slice deployments they are what crosses DCN). The scaling-book recipe:
pick a mesh, annotate shardings, let XLA insert collectives.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

AXES = ("stage", "data", "fsdp", "expert", "seq", "tensor")


@dataclass
class MeshSpec:
    """Sizes per axis; ``data=-1`` absorbs all remaining devices."""

    data: int = -1
    fsdp: int = 1
    expert: int = 1
    seq: int = 1
    tensor: int = 1
    stage: int = 1

    def sizes(self, num_devices: int) -> Dict[str, int]:
        fixed = {"fsdp": self.fsdp, "expert": self.expert, "seq": self.seq,
                 "tensor": self.tensor, "stage": self.stage}
        known = int(np.prod(list(fixed.values())))
        data = self.data
        if data == -1:
            if num_devices % known != 0:
                raise ValueError(
                    f"{num_devices} devices not divisible by "
                    f"stage*fsdp*expert*seq*tensor={known}")
            data = num_devices // known
        total = data * known
        if total != num_devices:
            raise ValueError(
                f"mesh {dict(data=data, **fixed)} needs {total} devices, "
                f"have {num_devices}")
        return {"data": data, **fixed}


def make_mesh(spec: Optional[Union[MeshSpec, Dict[str, int]]] = None,
              devices=None, axis_names: Sequence[str] = AXES):
    """Build a ``jax.sharding.Mesh`` over all (or given) devices.

    ``spec`` may be a :class:`MeshSpec` or a plain axis-size dict
    (``dict(fsdp=4, tensor=2)``) — the estimator's ``mesh_spec=`` argument
    accepts either, so callers need not import MeshSpec to go sharded."""
    import jax
    from jax.sharding import Mesh

    devices = list(devices if devices is not None else jax.devices())
    if isinstance(spec, dict):
        unknown = set(spec) - set(AXES)
        if unknown:
            raise ValueError(f"unknown mesh axes {sorted(unknown)}; "
                             f"have {AXES}")
        spec = MeshSpec(**spec)
    spec = spec or MeshSpec()
    sizes = spec.sizes(len(devices))
    shape = tuple(sizes[a] for a in axis_names)
    arr = np.array(devices).reshape(shape)
    return Mesh(arr, tuple(axis_names))


def vary_manual(x, axes: Sequence[str]):
    """Mark ``x`` varying over the manual mesh ``axes`` it is not already
    varying over (carry inits made with ``zeros_like`` are invariant and
    must be cast before mixing with varying values; ``pcast`` rejects axes
    already in the input's vma). Shared by ring attention and the pipeline."""
    import jax
    from jax import lax

    need = tuple(a for a in axes if a not in jax.typeof(x).vma)
    return lax.pcast(x, need, to="varying") if need else x


def data_axes(mesh) -> Tuple[str, ...]:
    """Axes the batch dimension is sharded over: data + fsdp (fsdp shards the
    batch too — params gather per layer, grads reduce-scatter)."""
    return tuple(a for a in ("data", "fsdp") if a in mesh.axis_names
                 and mesh.shape[a] > 1) or ("data",)


def batch_sharding(mesh, extra_batch_axes: Sequence[str] = (),
                   seq: bool = False):
    """Sharding of a batch-leading array: dim 0 over the data axes (plus any
    ``extra_batch_axes`` folded into the same dim). With ``seq=True`` and a
    >1 ``seq`` extent, dim 1 — the sequence dim — additionally shards over
    ``seq``, so long-context activations never materialize whole per device
    (callers must only apply the seq form to ndim >= 2 arrays)."""
    from jax.sharding import NamedSharding, PartitionSpec
    axes = tuple(data_axes(mesh)) + tuple(extra_batch_axes)
    entry = axes if len(axes) > 1 else axes[0]
    if seq and seq_extent(mesh) > 1:
        return NamedSharding(mesh, PartitionSpec(entry, "seq"))
    return NamedSharding(mesh, PartitionSpec(entry))


def seq_extent(mesh) -> int:
    """Size of the mesh's ``seq`` axis (1 when absent) — the gate every
    seq-sharding call site checks before extending specs past dim 0."""
    return int(mesh.shape.get("seq", 1)) if "seq" in mesh.axis_names else 1


def stage_extent(mesh) -> int:
    """Size of the mesh's ``stage`` axis (1 when absent) — the gate the
    estimator checks before routing training through the GPipe schedule.
    ``stage`` stays the OUTERMOST mesh axis (:data:`AXES`): its per-tick
    boundary hops are the rarest collective, so they ride the slowest links
    (cross-slice DCN on multi-slice deployments)."""
    return int(mesh.shape.get("stage", 1)) if "stage" in mesh.axis_names else 1


def replicated(mesh):
    from jax.sharding import NamedSharding, PartitionSpec
    return NamedSharding(mesh, PartitionSpec())


def param_sharding_rules(mesh, rules: Optional[List[Tuple[str, Tuple]]] = None):
    """Compile path-pattern → PartitionSpec rules into a tree-mapping function.

    ``rules`` is an ordered list of ``(substring, spec_tuple)``; the first
    matching substring of the parameter path wins. Leaves no rule matches go
    to the role policy (:mod:`raydp_tpu.parallel.roles` — embeddings over
    fsdp×tensor, kernels over fsdp/tensor by dimension, biases replicated),
    which is total: what it cannot shard it replicates (pure DP, the
    reference's only strategy).
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from raydp_tpu.parallel.roles import role_partition_spec

    def spec_for(path: str, leaf) -> NamedSharding:
        if rules:
            for pat, spec in rules:
                if pat in path:
                    return NamedSharding(mesh, PartitionSpec(*spec))
        return NamedSharding(mesh, role_partition_spec(
            mesh, path, tuple(getattr(leaf, "shape", ()))))

    def shardings_of(tree):
        flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
        out = []
        for path, leaf in flat:
            path_str = "/".join(
                str(getattr(p, "key", getattr(p, "name", p))) for p in path)
            out.append(spec_for(path_str, leaf))
        return jax.tree_util.tree_unflatten(treedef, out)

    return shardings_of


def shard_params(params, mesh, rules=None):
    """Place a parameter tree according to the rules (device_put per leaf)."""
    import jax
    shardings = param_sharding_rules(mesh, rules)(params)
    return jax.tree.map(lambda x, s: jax.device_put(x, s), params, shardings)
