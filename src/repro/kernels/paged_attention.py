"""Paged-attention decode Pallas kernel (page tables resolved in-kernel).

Decode attention against a *paged* KV cache: K/V live in a flat slot stack
``(n_slots, Hkv, D)`` shared by all rows of an arena, and each request row
owns a ``(max_pages,)`` int32 page table mapping its logical pages onto
physical ones. The serving hot path previously resolved that indirection
with jnp gathers *around* the flash kernel, materializing a gathered
``(B, Sc, Hkv, D)`` K/V copy plus a GQA-expanded ``(B, Sc, Hq, D)`` copy
before attending. This kernel fuses the indirection into the attention
itself:

- grid ``(B, n_pages)`` — one block row per request, the page axis minor
  (sequential) so online-softmax state lives in VMEM; each K/V block is one
  physical page with every kv head, ``(page, Hkv, D)``, the tile-aligned
  block of the ``(slots, Hkv, D)`` stack;
- the page table and per-row ``pos`` ride in as *scalar prefetch* operands
  (``PrefetchScalarGridSpec``), so the K/V BlockSpec index_maps read the
  table entry and DMA the physical page directly — no gathered copy exists;
- accumulation covers *committed pages only*: page ``j`` of a row is
  skipped (``pl.when``) unless ``j * page < min(pos + 1, Sc)``.

Mask equivalence (why one kernel serves both cache layouts): the decode
validity rule in ``models/attention.py::decode_attention`` is

    non-rotating:  valid(i) = i <= pos
    rotating:      valid(i) = 0 <= pos - mod(pos - i, Sc) <= pos

For a single query at position ``pos`` both reduce to the same set
``i < min(pos + 1, Sc)``: a rotating cache at depth ``pos >= Sc`` has every
slot live, and below that depth slots ``i <= pos`` are exactly the written
ones. The rotation only changes *which absolute position* a slot holds
(i.e. the cache contents), never the valid set, so the kernel needs ``pos``
and ``Sc`` but not the window.

``paged_attention_xla`` is the fallback form for non-TPU backends: same
committed-slot masking, grouped GQA einsums straight off the flat slot
stack (no ``jnp.repeat`` expansion), one gather instead of three
materialized intermediates. Dispatch between them lives in ``ops.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.config import VMEM_LIMIT_BYTES
from repro.kernels.ref import NEG_INF, phys_slots


def _paged_decode_kernel(
    tables_ref,  # (B, n_pages) int32, scalar prefetch
    pos_ref,     # (B,) int32, scalar prefetch
    q_ref,       # (1, Hkv, g, D)
    k_ref,       # (page, Hkv, D) — the physical page picked by the index_map
    v_ref,       # (page, Hkv, D)
    o_ref,       # (1, Hkv, g, D)
    m_ref,       # (Hkv, g, 1) f32 scratch
    l_ref,       # (Hkv, g, 1) f32 scratch
    acc_ref,     # (Hkv, g, D) f32 scratch
    *, page: int, n_pages: int, sc: int, hkv: int, g: int, scale: float,
):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    n_valid = jnp.minimum(pos_ref[b] + 1, sc)  # committed slots in this row

    @pl.when(j * page < n_valid)
    def _accumulate():
        islot = j * page + jax.lax.broadcasted_iota(jnp.int32, (g, page), 1)
        mask = islot < n_valid
        # the block carries every kv head of the page (a (page, 1, D) block
        # of the (slots, Hkv, D) stack is not tile-aligned); heads are a
        # short static loop
        for h in range(hkv):
            q = q_ref[0, h].astype(jnp.float32) * scale            # (g, d)
            k = k_ref[:, h, :].astype(jnp.float32)                 # (page, d)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(mask, s, NEG_INF)                        # (g, page)

            m_prev = m_ref[h]                                      # (g, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[h] = alpha * l_ref[h] + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[h] = alpha * acc_ref[h] + jax.lax.dot(
                p, v_ref[:, h, :].astype(jnp.float32),
                preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    @pl.when(j == n_pages - 1)
    def _done():
        lsum = l_ref[...]
        safe = jnp.where(lsum == 0.0, 1.0, lsum)
        o_ref[0] = (acc_ref[...] / safe).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("page", "sc", "interpret"))
def paged_decode_attention(
    q: jnp.ndarray,        # (B, 1, Hq, D) — one new token per row
    k_cache: jnp.ndarray,  # (n_slots, Hkv, D) flat slot stack
    v_cache: jnp.ndarray,  # (n_slots, Hkv, D)
    tables: jnp.ndarray,   # (B, n_pages) int32; unallocated entries >= n_phys
    pos: jnp.ndarray,      # (B,) int32 absolute position of the new token
    *,
    page: int,
    sc: int,               # logical cache length per row (bucket Sc)
    interpret: bool = False,
) -> jnp.ndarray:
    bsz, _, hq, d = q.shape
    hkv = k_cache.shape[1]
    g = hq // hkv
    n_phys = k_cache.shape[0] // page
    n_pages = tables.shape[1]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (bsz,))

    # Sentinel / out-of-range table entries are clamped to a real page at
    # DMA time; the committed-slot mask keeps their scores out of the sum.
    def kv_map(b, j, tables_ref, pos_ref):
        del pos_ref
        return (jnp.minimum(tables_ref[b, j], n_phys - 1), 0, 0)

    grid = (bsz, n_pages)
    kernel = functools.partial(
        _paged_decode_kernel, page=page, n_pages=n_pages, sc=sc, hkv=hkv,
        g=g, scale=1.0 / (d ** 0.5),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, hkv, g, d), lambda b, j, *_: (b, 0, 0, 0)),
                pl.BlockSpec((page, hkv, d), kv_map),
                pl.BlockSpec((page, hkv, d), kv_map),
            ],
            out_specs=pl.BlockSpec((1, hkv, g, d), lambda b, j, *_: (b, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((hkv, g, 1), jnp.float32),
                pltpu.VMEM((hkv, g, 1), jnp.float32),
                pltpu.VMEM((hkv, g, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((bsz, hkv, g, d), q.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(tables, pos, q.reshape(bsz, hkv, g, d), k_cache, v_cache)
    return out.reshape(bsz, hq, d)[:, None]


def paged_block_bytes(page: int, hkv: int, g: int, d: int, itemsize: int) -> int:
    """VMEM bytes of one grid step of :func:`paged_decode_attention`, the
    number ``ops.paged_attention`` and the plan compiler test against
    ``VMEM_LIMIT_BYTES``: double-buffered K and V pages and query/output
    blocks, plus the f32 online-softmax scratch."""
    return (2 * 2 * page * hkv * d * itemsize + 2 * 2 * hkv * g * d * itemsize
            + hkv * g * (d + 2) * 4)


def paged_attention_xla(
    q: jnp.ndarray,        # (B, 1, Hq, D)
    k_cache: jnp.ndarray,  # (n_slots, Hkv, D)
    v_cache: jnp.ndarray,  # (n_slots, Hkv, D)
    tables: jnp.ndarray,   # (B, n_pages) int32
    pos: jnp.ndarray,      # (B,) int32
    *,
    page: int,
    sc: int,
) -> jnp.ndarray:
    """XLA form of the fused operator (the non-TPU dispatch target).

    Algorithmically matches the kernel: committed-slot mask, scores taken
    in grouped (kv-head) form so the GQA expansion is never materialized,
    and uncommitted slots pinned to slot 0 so the single gather is the only
    cache-sized intermediate.
    """
    bsz, _, hq, d = q.shape
    hkv = k_cache.shape[1]
    g = hq // hkv
    n_slots = k_cache.shape[0]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (bsz,))

    n_valid = jnp.minimum(pos + 1, sc)[:, None]               # (B, 1)
    valid = jnp.arange(sc, dtype=jnp.int32)[None, :] < n_valid  # (B, Sc)
    phys = phys_slots(tables, sc, page)
    phys = jnp.where(valid, jnp.minimum(phys, n_slots - 1), 0)

    ke = k_cache[phys]                                        # (B, Sc, Hkv, D)
    ve = v_cache[phys]
    qf = q.astype(jnp.float32)[:, 0].reshape(bsz, hkv, g, d) * (d ** -0.5)
    s = jnp.einsum("bhgd,bkhd->bhgk", qf, ke.astype(jnp.float32))
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgk,bkhd->bhgd", p, ve.astype(jnp.float32))
    return o.reshape(bsz, hq, d)[:, None].astype(q.dtype)
