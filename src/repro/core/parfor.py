"""Task-parallel ``parfor`` (paper §3, "Distributed Operations").

SystemML: "for scoring using a compute-intensive deep network ... it is often
better to use the task-parallel loop construct — parfor — with a small
batch_size ... The parfor optimizer then automatically creates optimal
parallel execution plans that exploit multi-core, multi-GPU, and cluster
parallelism ... compiles a row-partitioned remote-parfor plan ... that avoids
shuffling and scales linearly."

TPU adaptation:

* *remote parfor*  -> ``shard_map`` over the data axes with a
  **collective-free body** (the "avoids shuffling" property — asserted in
  tests by grepping the lowered HLO for collectives).
* *local parfor*   -> ``jax.vmap`` / batched execution on one device.
* the *parfor optimizer* -> :func:`choose_parfor_plan`, which picks
  local vs remote from data size and mesh size, like SystemML's optimizer
  picks local vs remote workers.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

# Below this many rows per device, distributing is not worth it (SystemML's
# local-parfor decision for small task sets).
MIN_ROWS_PER_WORKER = 1


def choose_parfor_plan(num_rows: int, mesh: Optional[Mesh]) -> str:
    if mesh is None or len(mesh.devices.flatten()) == 1:
        return "local"
    workers = _data_size(mesh)
    if num_rows < workers * MIN_ROWS_PER_WORKER or num_rows % workers != 0:
        return "local"
    return "remote"


def _data_size(mesh: Mesh) -> int:
    n = 1
    for ax in mesh.axis_names:
        if ax in ("pod", "data"):
            n *= mesh.shape[ax]
    return n


def parfor(
    body: Callable,
    rows: jnp.ndarray,
    *,
    mesh: Optional[Mesh] = None,
    reduce: Optional[str] = None,
):
    """Row-partitioned task-parallel map: ``body`` maps a row batch -> output
    batch. ``reduce``: None (stack results) | "sum" | "mean" — the
    ``test_algo="allreduce"`` aggregation.
    """
    plan = choose_parfor_plan(rows.shape[0], mesh)
    if plan == "local":
        out = body(rows)
        return _reduce_local(out, reduce), plan

    daxes = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    in_spec = P(daxes)
    if reduce is None:
        out_spec = P(daxes)

        def shard_body(x):
            return body(x)

    else:
        out_spec = P()

        def shard_body(x):
            o = body(x)
            # one final all-reduce of the per-worker aggregate — the only
            # collective in the whole parfor plan (the paper's "allreduce")
            s = jnp.sum(o, axis=0)
            for ax in daxes:
                s = jax.lax.psum(s, ax)
            if reduce == "mean":
                s = s / rows.shape[0]
            return s

    fn = jax.shard_map(
        shard_body,
        mesh=mesh,
        in_specs=(in_spec,),
        out_specs=out_spec,
        check_vma=False,
    )
    return fn(rows), plan


def _reduce_local(out, reduce):
    if reduce == "sum":
        return jnp.sum(out, axis=0)
    if reduce == "mean":
        return jnp.mean(out, axis=0)
    return out


def count_collectives(hlo_text: str) -> int:
    """Number of collective ops in an HLO dump (test helper for the
    "avoids shuffling" claim)."""
    keys = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
            "collective-permute")
    return sum(hlo_text.count(k) for k in keys)
