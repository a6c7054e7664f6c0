"""Operator dispatch layer (paper §3, "GPU Backend" / "Native BLAS").

SystemML "compile[s] a GPU low-level operator if the input data, intermediate
data and output data for a given operation fits in the GPU device memory",
falling back to generic operators otherwise. The TPU analogue, one level
down the hierarchy: a Pallas kernel's *per-block working set* must fit the
scoped VMEM limit (``repro.config.VMEM_LIMIT_BYTES``) the kernels compile
with.

On a TPU backend the Pallas kernel is the only path: a working set that does
not fit raises :class:`VmemBudgetError` instead of quietly running the XLA
form, so a chip run never measures a fallback it did not ask for. Off TPU,
``auto`` runs the plain XLA (jnp) form and a forced ``pallas`` runs the
kernel in ``interpret=True`` mode (tests/benchmarks), still falling back to
XLA past the budget. Set ``ops.BACKEND`` to force a path.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.config import VMEM_LIMIT_BYTES
from repro.kernels import ref
from repro.kernels.conv2d_im2col import conv2d_im2col
from repro.kernels.flash_attention import flash_attention
from repro.kernels.matmul import matmul as matmul_kernel
from repro.kernels.paged_attention import (paged_attention_xla, paged_block_bytes,
                                           paged_decode_attention)
from repro.kernels.ssd_scan import ssd_scan

# "auto": pallas iff running on TPU; "pallas": force (interpret on CPU);
# "xla": force jnp fallback.
BACKEND = "auto"


class VmemBudgetError(RuntimeError):
    """A Pallas kernel's block working set exceeds the scoped VMEM limit."""


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    return not on_tpu()


def block_set_fits(*block_bytes: float) -> bool:
    """SystemML's device-memory-fit test, applied to a kernel's per-block
    VMEM set. Pipelined in/out blocks are double-buffered by Pallas, so the
    callers count those twice."""
    return sum(block_bytes) <= VMEM_LIMIT_BYTES


def _use_pallas(name: str, *block_bytes: float) -> bool:
    if BACKEND == "xla" or (BACKEND == "auto" and not on_tpu()):
        return False
    if block_set_fits(*block_bytes):
        return True
    if on_tpu():
        raise VmemBudgetError(
            f"{name}: block working set {sum(block_bytes):.0f} B exceeds the "
            f"scoped VMEM limit {VMEM_LIMIT_BYTES} B")
    return False


# ---------------------------------------------------------------------------


def matmul(a: jnp.ndarray, b: jnp.ndarray, bm: int = 128, bn: int = 128,
           bk: int = 128) -> jnp.ndarray:
    dt = a.dtype.itemsize
    if _use_pallas("matmul", 2 * bm * bk * dt, 2 * bk * bn * dt,
                   2 * bm * bn * dt, bm * bn * 4):
        return matmul_kernel(a, b, bm=bm, bn=bn, bk=bk, interpret=_interpret())
    return ref.matmul_ref(a, b)


def conv2d(x: jnp.ndarray, w: jnp.ndarray, stride: int = 1, pad: int = 0) -> jnp.ndarray:
    n, c, h, wd = x.shape
    f, _, k, _ = w.shape
    dt = x.dtype.itemsize
    hp, wp = h + 2 * pad, wd + 2 * pad
    ho, wo = (hp - k) // stride + 1, (wp - k) // stride + 1
    blk = c * hp * wp * dt + ho * wo * c * k * k * 4 + c * k * k * 128 * dt
    if _use_pallas("conv2d", blk):
        return conv2d_im2col(x, w, stride=stride, pad=pad, interpret=_interpret())
    return ref.conv2d_ref(x, w, stride=stride, pad=pad)


def _placed(fn, partition, in_axes, out_axes):
    """``partition`` (``ShardCtx.kernel_map``) puts a kernel call on a
    multi-device mesh; without one the call runs as is."""
    return fn if partition is None else partition(fn, in_axes, out_axes)


def attention(q, k, v, *, causal: bool = True, window: int = 0,
              q_offset: Optional[int] = None, bq: int = 128, bk: int = 128,
              partition=None):
    d = q.shape[-1]
    dt = q.dtype.itemsize
    if _use_pallas("flash_attention", 2 * bq * d * dt, 2 * 2 * bk * d * dt,
                   2 * bq * d * dt, bq * bk * 4, bq * (d + 2) * 4):
        fn = functools.partial(
            flash_attention, causal=causal, window=window,
            q_offset=-1 if q_offset is None else q_offset,
            bq=bq, bk=bk, interpret=_interpret())
        bhsd = ("batch", "heads", None, None)
        return _placed(fn, partition, (bhsd,) * 3, bhsd)(q, k, v)
    return ref.attention_ref(q, k, v, causal=causal, window=window,
                             q_offset=q_offset)


def paged_attention(q, k_cache, v_cache, tables, pos, *, page: int, sc: int,
                    partition=None):
    """Fused paged-decode attention; page tables resolved inside the op.

    Pallas path per-block working set: one K and one V physical page with
    every kv head, the row's (Hkv, g, D) query group in and out, and the
    f32 online-softmax scratch.
    """
    hkv, d = k_cache.shape[1], q.shape[-1]
    g = q.shape[2] // hkv
    dt = q.dtype.itemsize
    if _use_pallas("paged_attention", paged_block_bytes(page, hkv, g, d, dt)):
        fn = functools.partial(paged_decode_attention, page=page, sc=sc,
                               interpret=_interpret())
        # the slot stack is shared by every row: it never splits by batch
        q_axes = ("batch", None, "heads", None)
        kv_axes = (None, "heads", None)
        pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1),
                               (q.shape[0],))
        return _placed(fn, partition,
                       (q_axes, kv_axes, kv_axes, ("batch", None), ("batch",)),
                       q_axes)(q, k_cache, v_cache, tables, pos)
    return paged_attention_xla(q, k_cache, v_cache, tables, pos,
                               page=page, sc=sc)


def ssd(x, dt, a, b_mat, c_mat, d, *, chunk: int = 64, partition=None):
    P = x.shape[-1]
    N = b_mat.shape[-1]
    dtb = x.dtype.itemsize
    blk = 2 * chunk * (2 * P + 2 * N + 2) * dtb + chunk * chunk * 4 + P * N * 4
    if _use_pallas("ssd_scan", blk):
        fn = functools.partial(ssd_scan, chunk=chunk, interpret=_interpret())
        bshp = ("batch", None, "heads", None)
        bsn = ("batch", None, None)
        return _placed(fn, partition,
                       (bshp, bshp[:3], ("heads",), bsn, bsn, ("heads",)),
                       bshp)(x, dt, a, b_mat, c_mat, d)
    y, _ = ref.ssd_chunked_ref(x, dt, a, b_mat, c_mat, d,
                               chunk=min(chunk, x.shape[1]))
    return y
