"""Role-driven parameter sharding: pytree path → PartitionSpec.

The mesh (:mod:`raydp_tpu.parallel.mesh`) has carried ``fsdp``/``tensor``
axes since the seed, but choosing a PartitionSpec per parameter was left to
hand-written ``param_rules``. This module is the SpecLayout-style policy that
closes the gap: classify every parameter (and optimizer-state leaf) by its
*role* — read off the pytree path and the leaf's shape — and emit the spec
that role wants on this mesh:

- **embedding tables** (path names an embedding, 2-D): rows sharded over
  ``fsdp`` × ``tensor`` — the vocab dim is the big dim and gathers are
  per-lookup, so both axes pay off together;
- **projection / dense kernels** (≥ 2-D): Megatron-style ``tensor`` on the
  output (last) dim, ``fsdp`` on the largest remaining dim — FSDP all-gathers
  params per layer so its dim choice is a memory layout, not a math change;
- **stacked expert kernels** (path names an expert, 3-D ``[E, in, out]``):
  dim 0 over the mesh's ``expert`` axis, the inner dims as a kernel's;
- **biases / norm scales / scalars** (≤ 1-D): replicated — sharding a few
  hundred bytes buys nothing and costs a gather.

A dim is only ever sharded when the axis has size > 1 **and** divides it;
anything unshardable degrades axis by axis down to replicated, so the policy
is total (never raises on an odd shape). Optimizer state inherits its
parameter's spec for free: optax moment trees (adam ``mu``/``nu``) mirror the
parameter paths and shapes, so the same classification fires — the FSDP
memory win covers the Adam moments, not just the weights.

``param_sharding_rules`` consults this policy whenever no explicit rule
matches, so ``mesh_spec=dict(fsdp=..., tensor=...)`` alone yields a fully
sharded train state.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

#: path substrings that mark an embedding table (lowercased match). "embed"
#: catches flax ``nn.Embed`` scopes and the conventional ``embedding`` /
#: ``embed_tokens`` / ``token_embedder`` spellings in one token.
EMBEDDING_TOKENS = ("embed",)

#: path substrings that mark a stage-stacked leaf — per-layer parameter
#: pytrees stacked on a leading axis by
#: :func:`raydp_tpu.parallel.pipeline.stack_stage_params`. The leading dim is
#: the layer stack and shards over the mesh's ``stage`` axis; the REST of the
#: shape classifies through the ordinary role policy (the token is stripped
#: before inner classification so a stacked kernel still gets tensor/fsdp on
#: its inner dims).
STAGE_TOKENS = ("stage_stack",)

#: path substrings that mark the stacked kernels of a sparse expert layer
#: (``raydp_tpu/models/moe.py``: ``experts_gate`` / ``experts_up`` /
#: ``experts_down``, each ``[E, in, out]``). The router is 2-D: a kernel.
EXPERT_TOKENS = ("expert",)

REPLICATED = "replicated"
EMBEDDING = "embedding"
KERNEL = "kernel"
EXPERT = "expert"


def classify_param(path: str, shape: Tuple[int, ...]) -> str:
    """The role of one leaf: ``embedding`` | ``expert`` | ``kernel`` |
    ``replicated``.

    Works on parameter paths AND their optimizer-state mirrors (e.g.
    ``opt_state/0/mu/Dense_0/kernel`` classifies like the kernel itself);
    scalars (step counts) and 1-D leaves (biases, norm scales) replicate.
    """
    ndim = len(shape)
    if ndim <= 1:
        return REPLICATED
    low = path.lower()
    if ndim == 2 and any(tok in low for tok in EMBEDDING_TOKENS):
        return EMBEDDING
    if ndim == 3 and any(tok in low for tok in EXPERT_TOKENS):
        return EXPERT
    return KERNEL


def _divides(dim: int, size: int) -> bool:
    return size > 1 and dim > 1 and dim % size == 0


def role_partition_spec(mesh, path: str, shape: Tuple[int, ...]):
    """The PartitionSpec the leaf's role wants on ``mesh`` (total: degrades
    to replicated whenever an axis is absent, size 1, or does not divide).

    Stage-stacked leaves (path contains a :data:`STAGE_TOKENS` token) put the
    mesh's ``stage`` axis on their leading (layer-stack) dim when it divides,
    then classify the INNER shape through the ordinary role policy — a
    stacked kernel is still a kernel on dims 1..n. Optimizer-state mirrors
    (adam ``mu``/``nu``) inherit this for free: their paths carry the same
    token."""
    from jax.sharding import PartitionSpec

    low = path.lower()
    if any(tok in low for tok in STAGE_TOKENS) and len(shape) >= 1:
        stage = int(mesh.shape.get("stage", 1))
        lead = shape[0]
        head = "stage" if _divides(lead, stage) else None
        inner_path = low
        for tok in STAGE_TOKENS:
            inner_path = inner_path.replace(tok, "")
        inner = role_partition_spec(mesh, inner_path, tuple(shape[1:]))
        return PartitionSpec(head, *inner)

    fsdp = int(mesh.shape.get("fsdp", 1))
    tensor = int(mesh.shape.get("tensor", 1))
    role = classify_param(path, shape)
    if role == EXPERT:
        # the expert stack over ``expert`` where it divides; each expert's
        # [in, out] kernel then takes a kernel's spec on its own dims
        expert = int(mesh.shape.get("expert", 1))
        head = "expert" if _divides(shape[0], expert) else None
        inner_path = low
        for tok in EXPERT_TOKENS:
            inner_path = inner_path.replace(tok, "")
        spec = [head, *role_partition_spec(mesh, inner_path,
                                           tuple(shape[1:]))]
        while spec and spec[-1] is None:
            spec.pop()
        return PartitionSpec(*spec)
    if role == REPLICATED or (fsdp <= 1 and tensor <= 1):
        return PartitionSpec()

    spec: list = [None] * len(shape)
    if role == EMBEDDING:
        # rows (vocab) over the fsdp×tensor product when it divides; else
        # whichever single axis does; embedding dim stays replicated
        rows = shape[0]
        if _divides(rows, fsdp * tensor) and fsdp > 1 and tensor > 1:
            spec[0] = ("fsdp", "tensor")
        elif _divides(rows, fsdp):
            spec[0] = "fsdp"
        elif _divides(rows, tensor):
            spec[0] = "tensor"
        return PartitionSpec(*spec)

    # kernels: tensor on the output (last) dim, fsdp on the largest
    # remaining divisible dim (deterministic tie-break: lower index wins)
    if _divides(shape[-1], tensor):
        spec[-1] = "tensor"
    if fsdp > 1:
        order = sorted(range(len(shape)), key=lambda i: (-shape[i], i))
        for i in order:
            if spec[i] is None and _divides(shape[i], fsdp):
                spec[i] = "fsdp"
                break
    return PartitionSpec(*spec)


#: the remat policy vocabulary (RDT_TRAIN_REMAT / FlaxEstimator remat=)
REMAT_MODES = ("none", "dots", "full")


def remat_policy(mode: str):
    """The ``jax.checkpoint`` saveable policy for one remat mode — the
    activation-side mirror of the parameter role policy above. Roles split
    the forward's residuals the same way they split the weights:

    - ``dots`` keeps the MXU-bound products — the outputs of kernel and
      embedding contractions (:data:`KERNEL`/:data:`EMBEDDING` leaves are
      exactly the operands of those dots) — and recomputes the cheap
      elementwise glue (:data:`REPLICATED`-role bias adds, activations,
      norms) in the backward;
    - ``full`` saves nothing: every residual recomputes, trading the most
      FLOPs for the smallest live-activation footprint;
    - ``none`` returns None — the caller skips ``jax.checkpoint`` entirely
      and XLA keeps all residuals (the fastest, fattest default).
    """
    import jax

    if mode == "none":
        return None
    if mode == "dots":
        return jax.checkpoint_policies.checkpoint_dots
    if mode == "full":
        return jax.checkpoint_policies.nothing_saveable
    raise ValueError(
        f"unknown remat mode {mode!r}: expected one of {REMAT_MODES}")


def apply_remat(fn, mode: str):
    """``fn`` wrapped in ``jax.checkpoint`` under ``mode``'s policy
    (``none`` returns ``fn`` untouched). Applied to the train-step forward
    so the whole per-microbatch activation set obeys the policy."""
    import jax

    policy = remat_policy(mode)
    if policy is None:
        return fn
    return jax.checkpoint(fn, policy=policy)


#: the roles a remat policy may key on: the param-role vocabulary plus
#: ``default`` (the fallback mode — a bare mode string is sugar for
#: ``default=<mode>``, which keeps the pre-r20 global knob meaning).
REMAT_ROLES = (REPLICATED, EMBEDDING, KERNEL, EXPERT, "default")


def parse_remat_policy(spec: str) -> Dict[str, str]:
    """``RDT_TRAIN_REMAT`` / ``remat=`` grammar → a total role→mode map.

    Accepts either a bare mode (``"dots"`` — the pre-r20 global form, now
    meaning *default policy for every role*) or a comma-separated
    ``role=mode`` list (``"embedding=none,kernel=dots,expert=dots,default=full"``).
    Roles come from :data:`REMAT_ROLES`, modes from :data:`REMAT_MODES`;
    anything else raises ``ValueError`` — validated eagerly, long before any
    compile. The returned dict always carries a ``default`` entry
    (``none`` unless the spec set one)."""
    spec = (spec or "none").strip()
    policy: Dict[str, str] = {}
    if "=" not in spec:
        if spec not in REMAT_MODES:
            raise ValueError(
                f"unknown remat mode {spec!r}: expected one of {REMAT_MODES} "
                f"or a 'role=mode,...' policy over roles {REMAT_ROLES}")
        policy["default"] = spec
        return policy
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        if "=" not in part:
            raise ValueError(
                f"bad remat policy entry {part!r} in {spec!r}: expected "
                f"role=mode")
        role, _, mode = (p.strip() for p in part.partition("="))
        if role not in REMAT_ROLES:
            raise ValueError(
                f"unknown remat role {role!r} in {spec!r}: expected one of "
                f"{REMAT_ROLES}")
        if mode not in REMAT_MODES:
            raise ValueError(
                f"unknown remat mode {mode!r} for role {role!r} in {spec!r}: "
                f"expected one of {REMAT_MODES}")
        if role in policy:
            raise ValueError(f"duplicate remat role {role!r} in {spec!r}")
        policy[role] = mode
    policy.setdefault("default", "none")
    return policy


def remat_mode_for_role(policy: Dict[str, str], role: str) -> str:
    """The mode a parsed policy assigns to one param role (``default``
    fallback — the policy map is total by construction)."""
    return policy.get(role, policy["default"])


def segment_role(tree) -> str:
    """The dominant param role of a (sub)tree, weighted by leaf bytes — the
    role whose parameters own most of the segment's memory decides which
    remat mode the segment's forward runs under, exactly how the param specs
    are chosen leaf-by-leaf. Empty trees classify ``replicated``."""
    import jax

    weights: Dict[str, int] = {}
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    for path, leaf in flat:
        path_str = "/".join(
            str(getattr(p, "key", getattr(p, "name", p))) for p in path)
        shape = tuple(getattr(leaf, "shape", ()))
        nbytes = getattr(leaf, "nbytes", None)
        if nbytes is None:
            size = 1
            for d in shape:
                size *= int(d)
            nbytes = size * 4
        role = classify_param(path_str, shape)
        weights[role] = weights.get(role, 0) + int(nbytes)
    if not weights:
        return REPLICATED
    return max(weights.items(), key=lambda kv: (kv[1], kv[0]))[0]


def describe_roles(tree) -> dict:
    """Debug/bench helper: path → (role, shape) for every leaf of ``tree``."""
    import jax

    out = {}
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    for path, leaf in flat:
        path_str = "/".join(
            str(getattr(p, "key", getattr(p, "name", p))) for p in path)
        shape = tuple(getattr(leaf, "shape", ()))
        out[path_str] = (classify_param(path_str, shape), shape)
    return out


def addressable_nbytes(tree) -> int:
    """Bytes of ``tree`` actually resident on THIS process's devices —
    replicated leaves count one copy per addressable device (that IS the
    memory they occupy), sharded leaves only their local shards. The number
    the fsdp-vs-replicated HBM headroom claim is measured in."""
    import jax

    total = 0
    for leaf in jax.tree.leaves(tree):
        shards = getattr(leaf, "addressable_shards", None)
        if shards is not None:
            total += sum(s.data.nbytes for s in shards)
        elif hasattr(leaf, "nbytes"):
            total += int(leaf.nbytes)
    return total
