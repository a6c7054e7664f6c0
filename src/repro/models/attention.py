"""Attention forward paths for the big models.

Three physical operators for one logical op — the SystemML operator-
selection idea applied to attention:

* ``einsum``  — small sequences (smoke tests; cheapest to trace/compile)
* ``blocked`` — lax.scan over KV chunks with online softmax (flash
  semantics expressed in XLA; keeps peak HBM flat for the 32k dry-runs)
* Pallas flash kernel — on real TPU via ``repro.kernels.ops`` dispatch

plus the decode path (one query against a — possibly rotating — cache).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels import ops as kops

BLOCKED_THRESHOLD = 4096  # beyond this seq, use the blocked operator
KV_CHUNK = 1024


def attention(
    q: jnp.ndarray,     # (B, Sq, H, D)
    k: jnp.ndarray,     # (B, Sk, H, D) — GQA k/v pre-expanded to H (the
    v: jnp.ndarray,     #   repeat is sharded away under tensor parallelism)
    *,
    causal: bool = True,
    window: int = 0,
    q_offset: int = 0,  # absolute position of q[0] relative to k[0]
    partition=None,     # ShardCtx.kernel_map of a multi-device step
) -> jnp.ndarray:
    b, sq, hq, d = q.shape
    sk = k.shape[1]
    if kops.on_tpu():
        out = kops.attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), causal=causal, window=window,
            q_offset=q_offset, partition=partition,
        )
        return out.transpose(0, 2, 1, 3)
    big = max(sq, sk) >= BLOCKED_THRESHOLD
    # windowed attention beyond its window always prefers the blocked
    # operator: the einsum operator would materialize the full S^2 scores
    if window and max(sq, sk) > window:
        big = True
    if big and sq > 1:
        return _blocked(q, k, v, causal, window, q_offset)
    return _einsum(q, k, v, causal=causal, window=window, q_offset=q_offset)


def _mask(sq, sk, q_offset, causal, window):
    qpos = q_offset + jnp.arange(sq)[:, None]
    kpos = jnp.arange(sk)[None, :]
    m = jnp.ones((sq, sk), bool)
    if causal:
        m &= kpos <= qpos
    if window:
        m &= kpos > qpos - window
    return m


def _einsum(q, k, v, *, causal, window, q_offset):
    b, sq, h, d = q.shape
    sk = k.shape[1]
    qf = q.astype(jnp.float32) * (d ** -0.5)
    s = jnp.einsum("bqhd,bkhd->bhqk", qf, k.astype(jnp.float32))
    m = _mask(sq, sk, q_offset, causal, window)
    s = jnp.where(m[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return o.astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _blocked(q, k, v, causal, window, q_offset):
    """Online-softmax over KV chunks with a flash-style custom VJP: the
    backward pass *recomputes* per-chunk scores from (q, k, v, out, lse)
    instead of letting autodiff stack every chunk's probabilities — this is
    what keeps the S^2 term out of HBM for the training shapes."""
    out, _ = _blocked_fwd_impl(q, k, v, causal, window, q_offset)
    return out


def _blocked_fwd(q, k, v, causal, window, q_offset):
    out, lse = _blocked_fwd_impl(q, k, v, causal, window, q_offset)
    return out, (q, k, v, out, lse)


def _blocked_bwd(causal, window, q_offset, res, dout):
    q, k, v, out, lse = res
    b, sq, h, d = q.shape
    sk = k.shape[1]
    chunk = min(KV_CHUNK, sk)
    n_chunks = -(-sk // chunk)
    pad = n_chunks * chunk - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    scale = d ** -0.5
    qf = q.astype(jnp.float32)
    doutf = dout.astype(jnp.float32)
    outf = out.astype(jnp.float32)
    delta = jnp.sum(doutf * outf, axis=-1)                  # (b, sq, h)
    qpos = q_offset + jnp.arange(sq)
    kc = k.reshape(b, n_chunks, chunk, h, d).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, n_chunks, chunk, h, d).transpose(1, 0, 2, 3, 4)

    def step(dq, inp):
        ci, kb, vb = inp
        kbf, vbf = kb.astype(jnp.float32), vb.astype(jnp.float32)
        s = jnp.einsum("bqhd,bkhd->bqhk", qf, kbf) * scale
        kpos = ci * chunk + jnp.arange(chunk)
        msk = kpos[None, :] < sk
        if causal:
            msk = msk & (kpos[None, :] <= qpos[:, None])
        if window:
            msk = msk & (kpos[None, :] > (qpos[:, None] - window))
        p = jnp.exp(s - lse[..., None])
        p = jnp.where(msk[None, :, None, :], p, 0.0)        # (b,sq,h,ck)
        dv = jnp.einsum("bqhk,bqhd->bkhd", p, doutf)
        dp = jnp.einsum("bqhd,bkhd->bqhk", doutf, vbf)
        ds = p * (dp - delta[..., None]) * scale
        dq = dq + jnp.einsum("bqhk,bkhd->bqhd", ds, kbf)
        dk = jnp.einsum("bqhk,bqhd->bkhd", ds, qf)
        return dq, (dk, dv)

    dq0 = jnp.zeros((b, sq, h, d), jnp.float32)
    dq, (dks, dvs) = lax.scan(step, dq0, (jnp.arange(n_chunks), kc, vc))
    dk = dks.transpose(1, 0, 2, 3, 4).reshape(b, n_chunks * chunk, h, d)
    dv = dvs.transpose(1, 0, 2, 3, 4).reshape(b, n_chunks * chunk, h, d)
    if pad:
        dk, dv = dk[:, :sk], dv[:, :sk]
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_blocked.defvjp(_blocked_fwd, _blocked_bwd)


def _blocked_fwd_impl(q, k, v, causal, window, q_offset):
    """Online-softmax over KV chunks: flash semantics in pure XLA.
    Returns (out, lse)."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    chunk = min(KV_CHUNK, sk)
    n_chunks = -(-sk // chunk)
    pad = n_chunks * chunk - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qf = q.astype(jnp.float32) * (d ** -0.5)
    qpos = q_offset + jnp.arange(sq)

    kc = k.reshape(b, n_chunks, chunk, h, d).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, n_chunks, chunk, h, d).transpose(1, 0, 2, 3, 4)

    def step(carry, inp):
        m_prev, l_prev, acc = carry
        ci, kb, vb = inp
        s = jnp.einsum("bqhd,bkhd->bqhk", qf, kb.astype(jnp.float32))
        kpos = ci * chunk + jnp.arange(chunk)
        msk = jnp.ones((sq, chunk), bool)
        msk &= kpos[None, :] < sk  # padding
        if causal:
            msk &= kpos[None, :] <= qpos[:, None]
        if window:
            msk &= kpos[None, :] > (qpos[:, None] - window)
        s = jnp.where(msk[None, :, None, :], s, -1e30)
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[..., None])
        p = jnp.where(msk[None, :, None, :], p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1)
        acc = alpha[..., None] * acc + jnp.einsum(
            "bqhk,bkhd->bqhd", p, vb.astype(jnp.float32))
        return (m_new, l_new, acc), None

    m0 = jnp.full((b, sq, h), -1e30, jnp.float32)
    l0 = jnp.zeros((b, sq, h), jnp.float32)
    a0 = jnp.zeros((b, sq, h, d), jnp.float32)
    (m_f, l_f, acc), _ = lax.scan(
        step, (m0, l0, a0), (jnp.arange(n_chunks), kc, vc))
    l_safe = jnp.where(l_f == 0, 1.0, l_f)
    out = (acc / l_safe[..., None]).astype(q.dtype)
    # log-sum-exp of the *scaled* scores, for the recompute-backward
    lse = jnp.where(l_f == 0, -1e30, m_f + jnp.log(l_safe))
    return out, lse


# ---------------------------------------------------------------------------
# decode: one query against a (possibly rotating) cache
# ---------------------------------------------------------------------------


def decode_attention(
    q: jnp.ndarray,        # (B, 1, Hq, D)
    k_cache: jnp.ndarray,  # (B, Sc, Hkv, D)
    v_cache: jnp.ndarray,
    pos: jnp.ndarray,      # int32: absolute position of the new token —
    *,                     #   scalar (whole batch) or (B,) per-row vector
    window: int = 0,       # rotating cache iff window > 0 (Sc == window)
) -> jnp.ndarray:
    b, _, h, d = q.shape
    sc = k_cache.shape[1]
    qf = (q.astype(jnp.float32) * (d ** -0.5))[:, 0]
    s = jnp.einsum("bhd,bkhd->bhk", qf, k_cache.astype(jnp.float32))
    slots = jnp.arange(sc)[None, :]          # (1, Sc)
    pb = jnp.reshape(pos, (-1, 1))           # (B, 1) or (1, 1) — broadcasts
    if window:
        # rotating cache: slot i holds absolute position
        # p_i = pos - ((pos - i) mod Sc); valid iff 0 <= p_i <= pos
        p_i = pb - jnp.mod(pb - slots, sc)
        valid = (p_i >= 0) & (p_i <= pb)
    else:
        valid = slots <= pb
    s = jnp.where(valid[:, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhk,bkhd->bhd", p, v_cache.astype(jnp.float32))
    return o[:, None].astype(q.dtype)


def paged_slots(tables: jnp.ndarray, lslots: jnp.ndarray,
                page: int) -> jnp.ndarray:
    """Physical slot per logical slot through a page table:
    ``table[lslot // page] * page + lslot % page``.

    ``tables``: (B, max_pages) int32; unallocated entries hold the sentinel
    ``n_pages``, mapping to out-of-range physical slots (gathers through
    them are masked by the position validity mask, scatters drop).
    ``lslots``: (B,) or (B, S) logical slots. Returns same-shape physical
    slot indices into the arena's flat ``n_pages * page`` slot stack."""
    lp = jnp.clip(lslots // page, 0, tables.shape[1] - 1)
    entry = jnp.take_along_axis(
        tables, lp if lp.ndim > 1 else lp[:, None], axis=1)
    if lp.ndim == 1:
        entry = entry[:, 0]
    return entry * page + jnp.mod(lslots, page)


def paged_gather_kv(
    k_cache: jnp.ndarray,  # (n_slots, Hkv, D) — flat per-arena slot stack
    v_cache: jnp.ndarray,
    tables: jnp.ndarray,   # (B, max_pages) int32 page table per row
    page: int,
    sc: int,               # logical cache slots per row
    pos: Optional[jnp.ndarray] = None,  # per-row decode position
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Gather each row's logical cache view ``(B, sc, Hkv, D)`` out of the
    shared slot stack.

    With ``pos``, slots beyond each row's committed extent
    (``min(pos + 1, sc)`` — identical for dense and rotating rows, see
    kernels/paged_attention.py) are masked: their gather index is pinned to
    slot 0 and the gathered values zeroed, so uncommitted bucket slots are
    neither wandered through (sentinel table entries point at clamped
    arbitrary arena slots) nor carried as garbage into the attention op.
    The decode validity mask downstream already hides their scores; the
    masking here makes the memory access pattern and the gathered values
    deterministic. Without ``pos`` (legacy callers) slots on unallocated
    pages read clamped garbage, still hidden by the validity mask."""
    b = tables.shape[0]
    i = jnp.arange(sc, dtype=jnp.int32)
    phys = paged_slots(tables, jnp.broadcast_to(i, (b, sc)), page)
    phys = jnp.minimum(phys, k_cache.shape[0] - 1)
    if pos is not None:
        posb = jnp.broadcast_to(
            jnp.reshape(jnp.asarray(pos, jnp.int32), (-1,)), (b,))
        committed = i[None, :] < jnp.minimum(posb + 1, sc)[:, None]  # (B, sc)
        phys = jnp.where(committed, phys, 0)
        ke, ve = k_cache[phys], v_cache[phys]
        keep = committed[..., None, None]
        return jnp.where(keep, ke, 0), jnp.where(keep, ve, 0)
    return k_cache[phys], v_cache[phys]


def paged_cache_write(
    k_cache: jnp.ndarray, v_cache: jnp.ndarray,  # (n_slots, Hkv, D)
    k_new: jnp.ndarray, v_new: jnp.ndarray,      # (B, 1, Hkv, D)
    pos: jnp.ndarray, tables: jnp.ndarray, page: int, sc: int,
    *, window: int = 0,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Scatter each row's new K/V into its page-mapped physical slot.
    Rotating caches (window > 0) wrap within the row's own pages
    (``pos mod sc``); non-rotating writes beyond capacity — and writes from
    rows whose page table is unallocated (free rows) — are dropped."""
    b = k_new.shape[0]
    n_slots = k_cache.shape[0]
    posb = jnp.broadcast_to(
        jnp.reshape(jnp.asarray(pos, jnp.int32), (-1,)), (b,))
    lslot = jnp.mod(posb, sc) if window else posb
    phys = paged_slots(tables, lslot, page)
    if not window:
        phys = jnp.where(posb < sc, phys, n_slots)  # out of capacity: drop
    k_cache = k_cache.at[phys].set(k_new[:, 0].astype(k_cache.dtype),
                                   mode="drop")
    v_cache = v_cache.at[phys].set(v_new[:, 0].astype(v_cache.dtype),
                                   mode="drop")
    return k_cache, v_cache


def cache_write(
    k_cache: jnp.ndarray, v_cache: jnp.ndarray,
    k_new: jnp.ndarray, v_new: jnp.ndarray,  # (B, 1, Hkv, D)
    pos: jnp.ndarray, *, window: int = 0,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``pos`` scalar: one shared write slot (dynamic-update-slice). ``pos``
    (B,) vector: rows at different generation depths write their own slots
    (scatter; out-of-capacity rows drop their write — their decode mask
    never exposes those slots either)."""
    sc = k_cache.shape[1]
    if pos.ndim:
        slot = jnp.mod(pos, sc) if window else pos
        rows = jnp.arange(k_cache.shape[0])
        k_cache = k_cache.at[rows, slot].set(
            k_new[:, 0].astype(k_cache.dtype), mode="drop")
        v_cache = v_cache.at[rows, slot].set(
            v_new[:, 0].astype(v_cache.dtype), mode="drop")
        return k_cache, v_cache
    slot = jnp.mod(pos, sc) if window else pos
    k_cache = lax.dynamic_update_slice_in_dim(k_cache, k_new.astype(k_cache.dtype), slot, axis=1)
    v_cache = lax.dynamic_update_slice_in_dim(v_cache, v_new.astype(v_cache.dtype), slot, axis=1)
    return k_cache, v_cache
