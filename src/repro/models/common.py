"""Shared model substrate: param-spec helpers, RMSNorm, RoPE, sharding ctx.

Parameters travel as nested dicts of arrays; every param dict has a
*parallel axes dict* whose leaves are tuples of logical axis names consumed
by ``repro.core.sharding`` — the planner owns physical layout, the model
owns logical structure (the SystemML separation of script from plan).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.config import MeshConfig
from repro.core.sharding import spec_for
from repro.core.strategies import PlanConfig


# ---------------------------------------------------------------------------
# ShardCtx: plan-driven sharding hints inside model code
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ShardCtx:
    plan: Optional[PlanConfig] = None
    mesh_cfg: Optional[MeshConfig] = None
    # the concrete mesh the step runs on: constraints then name it
    # (NamedSharding) and need no mesh context at trace time. Without it a
    # bare PartitionSpec resolves against the caller's ``with mesh:``.
    mesh: Optional[Mesh] = None

    def _pin(self, x: jnp.ndarray, spec: P) -> jnp.ndarray:
        if self.mesh is not None:
            spec = NamedSharding(self.mesh, spec)
        return lax.with_sharding_constraint(x, spec)

    def constrain(self, x: jnp.ndarray, axes: Tuple[Optional[str], ...],
                  kind: str = "act") -> jnp.ndarray:
        if self.plan is None or self.mesh_cfg is None:
            return x
        if self.mesh_cfg.num_devices == 1:
            return x  # LOCAL plan: nothing to constrain (no mesh in context)
        spec = spec_for(tuple(x.shape), axes, self.plan, self.mesh_cfg, kind)
        return self._pin(x, spec)

    def kernel_map(self, fn, in_axes, out_axes):
        """Place a Pallas kernel call on the step's mesh. Mosaic kernels
        cannot be partitioned by the compiler, so on a multi-device mesh the
        call runs under ``shard_map``: "batch" dims split over the plan's
        batch axes and "heads" dims over "model" under tensor parallelism,
        each only where every such dim divides; all else is replicated.
        ``in_axes``: one logical-axes tuple per positional argument."""
        if self.mesh is None or self.plan is None:
            return fn
        plan, mesh_cfg = self.plan, self.mesh_cfg

        def run(*args):
            dims = {"batch": set(), "heads": set()}
            for x, axes in zip(args, in_axes):
                for n, ax in zip(x.shape, axes):
                    if ax in dims:
                        dims[ax].add(n)
            batch = plan.batch_axes
            nb = math.prod(mesh_cfg.shape[mesh_cfg.axis_names.index(a)]
                           for a in batch)
            mp = mesh_cfg.model_parallelism
            place = {
                "batch": (batch if batch and nb > 1
                          and all(n % nb == 0 for n in dims["batch"])
                          else None),
                "heads": ("model" if plan.tensor_parallel and mp > 1
                          and all(n % mp == 0 for n in dims["heads"])
                          else None),
            }

            def spec(axes):
                return P(*(place.get(ax) for ax in axes))

            return jax.shard_map(
                fn, mesh=self.mesh, in_specs=tuple(spec(a) for a in in_axes),
                out_specs=spec(out_axes), check_vma=False)(*args)

        return run

    def ckpt_constrain(self, x: jnp.ndarray) -> jnp.ndarray:
        """Residual-checkpoint constraint: seq over 'model' when the plan
        chose sequence-parallel remat checkpoints (Megatron SP). GSPMD
        lowers the transition out of a TP region into a reduce-scatter."""
        if self.plan is None or not self.plan.seq_shard_checkpoints:
            return x
        batch = self.plan.batch_axes or None
        return self._pin(x, P(batch, "model", None))

    def constrain_seq_model(self, x: jnp.ndarray) -> jnp.ndarray:
        """Pin dim-1 (seq) to the model axis, rest replicated-by-batch —
        the SP-attention layout for archs whose heads don't divide the
        model axis."""
        if self.plan is None or self.mesh_cfg is None or self.mesh_cfg.num_devices == 1:
            return x
        batch = self.plan.batch_axes or None
        return self._pin(x, P(*([batch, "model"] + [None] * (x.ndim - 2))))

    def seq_gather(self, x: jnp.ndarray) -> jnp.ndarray:
        """Megatron-SP region boundary: all-gather the seq dim at layer
        entry so the TP dims (heads/ffn) are free to use the model axis —
        without this, GSPMD resolves the axis conflict by gathering the
        *weights* every layer (catastrophically worse)."""
        if self.plan is None or not self.plan.seq_shard_checkpoints:
            return x
        batch = self.plan.batch_axes or None
        return self._pin(x, P(*([batch] + [None] * (x.ndim - 1))))


NULL_CTX = ShardCtx()


# ---------------------------------------------------------------------------
# param spec plumbing
# ---------------------------------------------------------------------------


class SpecBuilder:
    """Collects (shape, axes, init) triples; materializes either
    ShapeDtypeStructs (dry-run) or real initialized arrays (smoke/train)."""

    def __init__(self, dtype=jnp.bfloat16):
        self.dtype = dtype
        self.entries: Dict[str, Any] = {}

    def add(self, name: str, shape: Tuple[int, ...],
            axes: Tuple[Optional[str], ...], init: str = "normal",
            scale: Optional[float] = None, dtype=None):
        assert len(shape) == len(axes), (name, shape, axes)
        self.entries[name] = (tuple(shape), tuple(axes), init, scale,
                              dtype or self.dtype)
        return self

    def specs(self):
        return {
            k: jax.ShapeDtypeStruct(sh, dt)
            for k, (sh, ax, ini, sc, dt) in self.entries.items()
        }

    def axes(self):
        return {k: ax for k, (sh, ax, ini, sc, dt) in self.entries.items()}

    def init(self, key, shardings=None):
        """Random initial values, each leaf made in its own dtype by one
        small jitted program, so no float32 copy of a large (layer-stacked)
        weight is ever resident. ``shardings``: optional {name: Sharding}
        the leaves are created under (a sharded model is never gathered
        onto one device)."""
        out = {}
        for k, (sh, ax, ini, sc, dt) in self.entries.items():
            key, sub = jax.random.split(key)
            if ini == "normal":
                fan_in = sh[-2] if len(sh) >= 2 else sh[-1]
                sc = sc if sc is not None else 1.0 / math.sqrt(max(1, fan_in))
            make = _init_leaf_fn(sh, jnp.dtype(dt).name, ini, sc,
                                 None if shardings is None else shardings[k])
            out[k] = make(sub)
        return out


@functools.lru_cache(maxsize=256)
def _init_leaf_fn(shape, dtype: str, init: str, scale, sharding):
    """One jitted initializer per distinct leaf signature (shared by every
    model built in the process)."""

    def make(key):
        if init == "zeros":
            return jnp.zeros(shape, dtype)
        if init == "ones":
            return jnp.ones(shape, dtype)
        if init == "ssm_a":
            # A_log init: log of uniform [1, 16] (mamba2 convention)
            return jnp.log(jax.random.uniform(key, shape, jnp.float32,
                                              1.0, 16.0)).astype(dtype)
        return (jax.random.normal(key, shape, jnp.float32) * scale
                ).astype(dtype)

    return jax.jit(make, out_shardings=sharding)


def merge_trees(**subtrees):
    return dict(subtrees)


# ---------------------------------------------------------------------------
# primitives
# ---------------------------------------------------------------------------


def rms_norm(x: jnp.ndarray, gamma: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * lax.rsqrt(ms + eps)).astype(x.dtype) * gamma


def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (..., S, H, D); positions broadcastable to (..., S)."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs      # (..., S, half)
    cos = jnp.cos(ang)[..., None, :]                            # (..., S, 1, half)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).astype(x.dtype)


def swiglu(x: jnp.ndarray, w_gate: jnp.ndarray, w_up: jnp.ndarray,
           w_down: jnp.ndarray) -> jnp.ndarray:
    g = jnp.einsum("...d,df->...f", x, w_gate)
    u = jnp.einsum("...d,df->...f", x, w_up)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    return jnp.einsum("...f,fd->...d", h, w_down)


def softmax_xent_logits(logits: jnp.ndarray, targets: jnp.ndarray,
                        mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """logits (..., V) bf16 -> fp32 mean xent over unmasked positions."""
    lf = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(lf, axis=-1)
    tgt = jnp.take_along_axis(lf, targets[..., None], axis=-1)[..., 0]
    nll = lse - tgt
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


def causal_conv1d(x: jnp.ndarray, w: jnp.ndarray,
                  state: Optional[jnp.ndarray] = None):
    """Depthwise causal conv. x: (B, S, C); w: (W, C).
    With ``state`` (B, W-1, C): single-step decode (S==1) path returning
    (y, new_state)."""
    wd = w.shape[0]
    if state is not None:
        full = jnp.concatenate([state, x], axis=1)       # (B, W, C)
        y = jnp.einsum("bwc,wc->bc", full[:, -wd:], w)[:, None, :]
        return y, full[:, 1:]
    pad = jnp.zeros(x.shape[:1] + (wd - 1,) + x.shape[2:], x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)
    # stack of shifted views -> einsum (BLAS-3 form, no explicit loop conv)
    views = jnp.stack([xp[:, i : i + x.shape[1]] for i in range(wd)], axis=0)
    return jnp.einsum("wbsc,wc->bsc", views, w)
