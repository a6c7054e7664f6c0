"""Logical-axis -> mesh-axis sharding rules.

Every tensor in the system (params, activations, KV caches, optimizer state)
carries a tuple of *logical axis names* (one per dim). The plan decides which
logical axes map onto which mesh axes; this module turns that decision into
concrete ``PartitionSpec``/``NamedSharding`` objects.

This is the pjit-era analogue of SystemML's "blocked matrix" physical layout
decision: the compiler, not the model author, owns the layout.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.config import MeshConfig
from repro.core.strategies import PlanConfig

# Logical axes eligible for the "model" (tensor-parallel) mesh axis, in
# priority order. Only one logical axis per tensor maps to "model".
MODEL_AXIS_PRIORITY = (
    "experts",
    "q_heads",
    "heads",
    "kv_heads",
    "ffn",
    "vocab",
    "ssm_heads",
    "ssm_inner",
    "lru",
    "embed_out",   # output-projection embed dim (row-parallel)
)

# Logical axes eligible for FSDP (data-axes) sharding, largest-first is
# resolved dynamically; these are merely *allowed*.
FSDP_AXES = (
    "embed",
    "embed_out",
    "ffn",
    "vocab",
    "q_heads",
    "heads",
    "kv_heads",
    "ssm_inner",
    "ssm_heads",
    "lru",
    "experts",
)

# Axes that must never shard (scan-stacked layer dim, small vectors).
NEVER_SHARD = ("layers", "head_dim", "ssm_state", "conv", "scalar", "window")


def make_mesh(shape: Sequence[int], axes: Sequence[str]) -> Mesh:
    """``jax.make_mesh`` with Auto axes. The models place tensors with
    sharding constraints and let GSPMD propagate the rest; JAX's default
    Explicit axes would instead demand an output sharding on every
    ambiguous gather and contraction."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def _axis_size(mesh: MeshConfig, names: Sequence[str]) -> int:
    n = 1
    for nm, sz in zip(mesh.axis_names, mesh.shape):
        if nm in names:
            n *= sz
    return n


def spec_for(
    shape: Tuple[int, ...],
    axes: Tuple[Optional[str], ...],
    plan: PlanConfig,
    mesh: MeshConfig,
    kind: str = "param",
) -> P:
    """Compute the PartitionSpec for one tensor.

    kind: "param" | "act" | "cache" | "opt"
    """
    if len(shape) != len(axes):
        raise ValueError(f"shape {shape} vs logical axes {axes}")
    assignment: list = [None] * len(shape)
    used_mesh_axes: set = set()


    def assign(i, mesh_axes):
        if isinstance(mesh_axes, str):
            mesh_axes = (mesh_axes,)
        mesh_axes = tuple(a for a in mesh_axes if a not in used_mesh_axes and a in mesh.axis_names)
        if not mesh_axes:
            return False
        div = _axis_size(mesh, mesh_axes)
        if div <= 1 or shape[i] % div != 0:
            return False
        assignment[i] = mesh_axes[0] if len(mesh_axes) == 1 else tuple(mesh_axes)
        used_mesh_axes.update(mesh_axes)
        return True

    # 1. batch axis
    for i, ax in enumerate(axes):
        if ax == "batch":
            baxes = plan.cache_batch_axes if kind == "cache" else plan.batch_axes
            if baxes and shape[i] % _axis_size(mesh, baxes) == 0:
                assign(i, baxes)

    # 1b. context parallelism: activation seq dim (prefill)
    if kind == "act":
        for i, ax in enumerate(axes):
            if ax == "seq" and plan.seq_axes:
                assign(i, plan.seq_axes)

    # 2. cache sequence sharding (decode long-context)
    if kind == "cache":
        for i, ax in enumerate(axes):
            if ax == "seq" and plan.cache_seq_axes:
                assign(i, plan.cache_seq_axes)
        for i, ax in enumerate(axes):
            if ax in ("kv_heads", "heads", "ssm_heads") and plan.cache_heads_over_model:
                assign(i, "model")

    # 3. tensor / expert parallel over "model"
    if kind in ("param", "opt") and (plan.tensor_parallel or plan.expert_parallel):
        allowed = MODEL_AXIS_PRIORITY if plan.tensor_parallel else ("experts",)
        for cand in allowed:
            done = False
            for i, ax in enumerate(axes):
                if ax == cand and assignment[i] is None and assign(i, "model"):
                    done = True
                    break
            if done:
                break

    # 4. FSDP over the data axes: largest remaining eligible dim
    if kind in ("param", "opt") and plan.params_over_data:
        daxes = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
        cands = [
            (shape[i], i)
            for i, ax in enumerate(axes)
            if ax in FSDP_AXES and assignment[i] is None
        ]
        for _, i in sorted(cands, reverse=True):
            if assign(i, daxes):
                break

    # 5. activations: shard the feature dims that TP shards (GSPMD would
    #    propagate this anyway; being explicit avoids resharding wobble)
    if kind == "act" and plan.tensor_parallel:
        for cand in MODEL_AXIS_PRIORITY:
            done = False
            for i, ax in enumerate(axes):
                if ax == cand and assignment[i] is None and assign(i, "model"):
                    done = True
                    break
            if done:
                break

    return P(*assignment)


def named_sharding(
    mesh: Mesh,
    shape: Tuple[int, ...],
    axes: Tuple[Optional[str], ...],
    plan: PlanConfig,
    mesh_cfg: MeshConfig,
    kind: str = "param",
) -> NamedSharding:
    return NamedSharding(mesh, spec_for(shape, axes, plan, mesh_cfg, kind))


def tree_specs(shapes_tree, axes_tree, plan: PlanConfig, mesh_cfg: MeshConfig, kind: str = "param"):
    """Map spec_for over a pytree of ShapeDtypeStructs + matching axes tree."""
    # shapes_tree's leaves (ShapeDtypeStruct/Array) define the structure;
    # axes_tree is flattened *up to* those leaf positions, so its tuple
    # leaves arrive intact.
    return jax.tree.map(
        lambda s, a: spec_for(tuple(s.shape), tuple(a), plan, mesh_cfg, kind),
        shapes_tree,
        axes_tree,
    )
