"""Mamba-2 SSD (state-space duality) Pallas kernel.

TPU adaptation of the SSD algorithm (arXiv:2405.21060): the sequential
recurrence is re-expressed as *chunked matmuls* (BLAS-3) — exactly the kind
of rewrite SystemML's compiler performs when it lowers iterative DML to
matrix operators. Within a chunk everything is dense matmul on the MXU;
across chunks a (P x N) state tile is carried in VMEM scratch along the
sequential minor grid axis.

Grid: (B, H, S/chunk) with the chunk axis innermost (sequential on TPU).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.config import VMEM_LIMIT_BYTES


def _ssd_kernel(
    x_ref, dt_ref, dtr_ref, a_ref, b_ref, c_ref, d_ref, y_ref, state_ref,
    *, chunk: int, n_chunks: int,
):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    h = pl.program_id(1)
    x = x_ref[0, 0, 0].astype(jnp.float32)     # (chunk, P)
    dt = dt_ref[0, 0, 0].astype(jnp.float32)   # (chunk, 1)
    dt_row = dtr_ref[0, 0, 0].astype(jnp.float32)  # (1, chunk): same values
    a = a_ref[h]                               # scalar decay rate (negative)
    bm = b_ref[0, 0].astype(jnp.float32)       # (chunk, N)
    cm = c_ref[0, 0].astype(jnp.float32)       # (chunk, N)
    d = d_ref[h]                               # scalar skip weight

    # inclusive prefix sums of dt*a as masked reductions (Mosaic has no
    # cumsum): the column form reduces the row copy over lanes, the row
    # form reduces the column copy over sublanes, so nothing is transposed
    aseg = dt * a                              # (chunk, 1)
    aseg_row = dt_row * a                      # (1, chunk)
    r = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tri = r >= c
    cum = jnp.sum(jnp.where(tri, aseg_row, 0.0), axis=1, keepdims=True)  # (chunk, 1)
    cum_row = jnp.sum(jnp.where(r <= c, aseg, 0.0), axis=0, keepdims=True)
    total = jnp.sum(aseg, axis=0, keepdims=True)  # (1, 1)

    # intra-chunk: L[i,j] = exp(cum_i - cum_j) * [i >= j]
    li = cum - cum_row                         # (chunk, chunk)
    lmat = jnp.exp(jnp.where(tri, li, -1e30))  # mask before exp (overflow)
    scores = jnp.dot(cm, bm.T, preferred_element_type=jnp.float32)
    w = scores * lmat                          # (chunk, chunk)
    dx = dt * x                                # (chunk, P)
    y = jnp.dot(w, dx, preferred_element_type=jnp.float32)

    # inter-chunk: exp(cum_i) * C_i . state_prev^T   (state: (P, N))
    state = state_ref[...]
    y += jnp.exp(cum) * jnp.dot(cm, state.T, preferred_element_type=jnp.float32)

    # state update: exp(total) * state + sum_t exp(total - cum_t) dx_t b_t^T
    decay_to_end = jnp.exp(total - cum)        # (chunk, 1)
    contrib = jnp.dot((dx * decay_to_end).T, bm, preferred_element_type=jnp.float32)
    state_ref[...] = jnp.exp(total) * state + contrib

    y_ref[0, 0, 0] = (y + d * x).astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(
    x: jnp.ndarray,      # (B, S, H, P)
    dt: jnp.ndarray,     # (B, S, H)
    a: jnp.ndarray,      # (H,)
    b_mat: jnp.ndarray,  # (B, S, N)
    c_mat: jnp.ndarray,  # (B, S, N)
    d: jnp.ndarray,      # (H,)
    *,
    chunk: int = 64,
    interpret: bool = False,
) -> jnp.ndarray:
    B, S, H, P = x.shape
    N = b_mat.shape[-1]
    chunk = min(chunk, S)
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk
    # layouts: (B, H, nc, chunk, *)
    xr = x.transpose(0, 2, 1, 3).reshape(B, H, nc, chunk, P)
    dtr = dt.transpose(0, 2, 1).reshape(B, H, nc, chunk, 1)
    dtr_row = dtr.reshape(B, H, nc, 1, chunk)
    br = b_mat.reshape(B, nc, chunk, N)
    cr = c_mat.reshape(B, nc, chunk, N)

    out = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk, n_chunks=nc),
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, 1, chunk, P), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, chunk, 1), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, chunk), lambda b, h, c: (b, h, c, 0, 0)),
            # per-head scalars: the whole (H,) vectors sit in SMEM (a
            # size-1 rank-1 VMEM block is not tile-aligned)
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, chunk, N), lambda b, h, c: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, chunk, N), lambda b, h, c: (b, c, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, chunk, P), lambda b, h, c: (b, h, c, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, nc, chunk, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(xr, dtr, dtr_row, a.astype(jnp.float32), br, cr, d.astype(jnp.float32))
    return out.reshape(B, H, S, P).transpose(0, 2, 1, 3)
