"""Flash attention Pallas kernel (GQA + causal + sliding window).

TPU adaptation of the attention hot-spot: online-softmax tiling so the
(Sq x Sk) score matrix never leaves VMEM. Blocks are MXU-aligned; the
kv-block loop is the minor (sequential) grid axis, carrying the running
max / denominator / accumulator in VMEM scratch.

Used for: dense-arch training & prefill, the sliding-window serving variant
(``long_500k`` on full-attention archs, DESIGN §5), and recurrentgemma's
local-attention blocks.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.config import VMEM_LIMIT_BYTES

NEG_INF = -1e30

BQ, BK = 128, 128
MIN_BLOCK = 8


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
    *, n_kv: int, bq: int, bk: int, causal: bool, window: int, q_offset: int,
    scale: float,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale          # (bq, d)
    k = k_ref[0].astype(jnp.float32)                  # (bk, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq, bk)

    qpos = q_offset + qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                               # (bq, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    p = jnp.where(mask, p, 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
    # f32 accumulation: Mosaic refuses a bf16-accumulating MXU dot
    acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot(
        p.astype(v_ref.dtype), v_ref[0], preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _done():
        lsum = l_ref[...]
        safe = jnp.where(lsum == 0.0, 1.0, lsum)
        o_ref[0, ...] = (acc_ref[...] / safe).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "q_offset", "bq", "bk", "interpret"),
)
def flash_attention(
    q: jnp.ndarray,   # (B, Hq, Sq, D)
    k: jnp.ndarray,   # (B, Hkv, Sk, D)
    v: jnp.ndarray,   # (B, Hkv, Sk, D)
    *,
    causal: bool = True,
    window: int = 0,
    q_offset: int = -1,   # -1 -> Sk - Sq (standard causal alignment)
    bq: int = BQ,
    bk: int = BK,
    interpret: bool = False,
) -> jnp.ndarray:
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    g = hq // hkv
    if q_offset < 0:
        q_offset = sk - sq
    # blocks are at least MIN_BLOCK rows (the TPU sublane tile), so a short
    # prompt is padded up to one aligned block instead of shrinking below it
    bq = max(MIN_BLOCK, min(bq, _pow2_floor(sq)))
    bk = max(MIN_BLOCK, min(bk, _pow2_floor(sk)))
    sqp, skp = _pad(sq, bq), _pad(sk, bk)
    if sqp != sq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, sqp - sq), (0, 0)))
    if skp != sk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, skp - sk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, skp - sk), (0, 0)))
        # padded keys masked out via positions > any qpos under causal; for
        # non-causal we mask explicitly by window over positions; to be safe
        # the wrapper only allows padding with causal=True or window>0.
        if not causal and window == 0:
            raise ValueError("Sk must be tile-aligned for full bidirectional attention")
    # fold GQA groups into the batch*head grid axis: kv head = bh // g
    qr = q.reshape(b * hq, sqp, d)
    n_kv = skp // bk

    grid = (b * hq, sqp // bq, n_kv)
    kernel = functools.partial(
        _flash_kernel, n_kv=n_kv, bq=bq, bk=bk, causal=causal,
        window=window, q_offset=q_offset, scale=1.0 / (d ** 0.5),
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, qi, ki, g=g: (bh // g, ki, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, qi, ki, g=g: (bh // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, sqp, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(qr, k.reshape(b * hkv, skp, d), v.reshape(b * hkv, skp, d))
    return out.reshape(b, hq, sqp, d)[:, :, :sq, :]


def _pow2_floor(x: int) -> int:
    p = 1
    while p * 2 <= x:
        p *= 2
    return p


def _pad(x: int, b: int) -> int:
    return ((x + b - 1) // b) * b
