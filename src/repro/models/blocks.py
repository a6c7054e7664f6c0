"""Transformer / SSM / RG-LRU block definitions.

Each block kind provides:
  ``*_params(cfg)``        -> {name: (shape, axes, init)} per-layer specs
  ``*_apply(cfg, p, x, ...)``   full-sequence forward (train / prefill)
  ``*_decode(cfg, p, x, cache, pos)`` one-token forward + cache update

Param layout is logical-axis annotated (see core.sharding); the planner
decides the physical sharding.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.config import ModelConfig
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.models import attention as ATT
from repro.models.common import (NULL_CTX, ShardCtx, causal_conv1d, rms_norm,
                                 rope, swiglu)


# ===========================================================================
# dense / MoE attention block
# ===========================================================================


def attn_block_params(cfg: ModelConfig, cross: bool = False) -> Dict:
    d, hq, kv, hd, f = (cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
                        cfg.head_dim, cfg.d_ff)
    p = {
        "ln1": ((d,), (None,), "ones"),
        "wq": ((d, hq, hd), ("embed", "q_heads", "head_dim"), "normal"),
        "wk": ((d, kv, hd), ("embed", "kv_heads", "head_dim"), "normal"),
        "wv": ((d, kv, hd), ("embed", "kv_heads", "head_dim"), "normal"),
        "wo": ((hq, hd, d), ("q_heads", "head_dim", "embed_out"), "normal"),
        "ln2": ((d,), (None,), "ones"),
    }
    if cross:
        p.update({
            "xln": ((d,), (None,), "ones"),
            "xwq": ((d, hq, hd), ("embed", "q_heads", "head_dim"), "normal"),
            "xwk": ((d, kv, hd), ("embed", "kv_heads", "head_dim"), "normal"),
            "xwv": ((d, kv, hd), ("embed", "kv_heads", "head_dim"), "normal"),
            "xwo": ((hq, hd, d), ("q_heads", "head_dim", "embed_out"), "normal"),
        })
    if cfg.num_experts:
        e = cfg.num_experts
        p.update({
            "router": ((d, e), ("embed", None), "normal"),
            "e_wg": ((e, d, f), ("experts", "embed", "ffn"), "normal"),
            "e_wu": ((e, d, f), ("experts", "embed", "ffn"), "normal"),
            "e_wd": ((e, f, d), ("experts", "ffn", "embed_out"), "normal"),
        })
    elif cfg.family == "audio":
        # whisper-style GELU MLP
        p.update({
            "wi": ((d, f), ("embed", "ffn"), "normal"),
            "wo_mlp": ((f, d), ("ffn", "embed_out"), "normal"),
        })
    else:
        p.update({
            "wg": ((d, f), ("embed", "ffn"), "normal"),
            "wu": ((d, f), ("embed", "ffn"), "normal"),
            "wd": ((f, d), ("ffn", "embed_out"), "normal"),
        })
    return p


def _qkv(cfg, p, x, positions, prefix="", ctx: ShardCtx = NULL_CTX,
         expand: bool = True):
    """Returns ``(q, k, v, (k_kv, v_kv))`` — the last pair is the rope'd
    K/V in kv-head form (pre-GQA-expansion, pre-constraint): exactly what a
    decode cache row stores, so the prefill path can hand its K/V off."""
    q = jnp.einsum("bsd,dhk->bshk", x, p[prefix + "wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p[prefix + "wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p[prefix + "wv"])
    if positions is not None:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    kv_form = (k, v)
    if expand and cfg.q_per_kv > 1:
        # GQA: expand K/V to the full head count. Under tensor parallelism
        # the expanded heads shard over "model", so each chip materializes
        # only its slice — no memory cost, and it keeps attention einsums
        # reshape-free (GSPMD shards merged/reshaped dims poorly).
        k = jnp.repeat(k, cfg.q_per_kv, axis=2)
        v = jnp.repeat(v, cfg.q_per_kv, axis=2)
    # pin layouts. Three regimes:
    #  * context-parallel (plan.seq_axes): Q seq-sharded, K/V gathered
    #  * heads divisible by the model axis: head-sharded attention (TP)
    #  * heads NOT divisible (phi3's 40, recurrentgemma's 10): keep the
    #    attention region *sequence*-sharded (SP attention) and gather K/V
    #    — otherwise every chip replicates the full attention working set
    qspec = ("batch", "seq", "q_heads", "head_dim")
    cp = ctx.plan is not None and bool(ctx.plan.seq_axes)
    sp = _sp_attention(cfg, ctx)
    if sp and not cp:
        # SP attention: Q seq-sharded over "model", K/V gathered
        q = ctx.constrain_seq_model(q)
        k = ctx.constrain(k, ("batch", None, None, None))
        v = ctx.constrain(v, ("batch", None, None, None))
        return q, k, v, kv_form
    kvspec = ("batch", None, None, None) if cp else qspec
    q = ctx.constrain(q, qspec)
    k = ctx.constrain(k, kvspec)
    v = ctx.constrain(v, kvspec)
    return q, k, v, kv_form


def _heads_shardable(cfg, ctx: ShardCtx) -> bool:
    if ctx.plan is None or ctx.mesh_cfg is None or not ctx.plan.tensor_parallel:
        return False
    return cfg.num_heads % ctx.mesh_cfg.model_parallelism == 0


def _sp_attention(cfg, ctx: ShardCtx) -> bool:
    """Sequence-parallel attention region: TP is on but heads don't divide
    the model axis, and residuals are seq-sharded."""
    return (ctx.plan is not None and ctx.plan.seq_shard_checkpoints
            and not _heads_shardable(cfg, ctx))


def _ffn(cfg, p, x, ctx: ShardCtx):
    if cfg.num_experts:
        return moe_ffn(cfg, p, x, ctx)
    if cfg.family == "audio":
        h = jax.nn.gelu(jnp.einsum("bsd,df->bsf", x, p["wi"]).astype(jnp.float32))
        return jnp.einsum("bsf,fd->bsd", h.astype(x.dtype), p["wo_mlp"]), 0.0
    return swiglu(x, p["wg"], p["wu"], p["wd"]), 0.0


def attn_block_apply(
    cfg: ModelConfig, p: Dict, x: jnp.ndarray, positions: jnp.ndarray,
    *, causal: bool = True, window: int = 0, ctx: ShardCtx = NULL_CTX,
    enc_out: Optional[jnp.ndarray] = None, want_kv: bool = False,
) -> Tuple:
    """Returns (x_out, aux_loss), or with ``want_kv`` the 3-tuple
    (x_out, aux_loss, {"k", "v"}) — K/V in kv-head cache-row form
    ``(B, S, Kv, Dh)`` for the prefill→decode handoff."""
    h = rms_norm(x, p["ln1"])
    if not _sp_attention(cfg, ctx):
        h = ctx.seq_gather(h)
    q, k, v, (kr, vr) = _qkv(cfg, p, h, positions, ctx=ctx)
    o = ATT.attention(q, k, v, causal=causal, window=window,
                      partition=ctx.kernel_map)
    if _sp_attention(cfg, ctx) and not (ctx.plan and ctx.plan.seq_axes):
        o = ctx.constrain_seq_model(o)
    else:
        o = ctx.constrain(o, ("batch", "seq", "q_heads", "head_dim"))
    x = x + ctx.ckpt_constrain(jnp.einsum("bshk,hkd->bsd", o, p["wo"]))
    if enc_out is not None:  # cross attention (enc-dec decoder)
        h = rms_norm(x, p["xln"])
        qx = jnp.einsum("bsd,dhk->bshk", h, p["xwq"])
        kx = jnp.einsum("bsd,dhk->bshk", enc_out, p["xwk"])
        vx = jnp.einsum("bsd,dhk->bshk", enc_out, p["xwv"])
        if cfg.q_per_kv > 1:
            kx = jnp.repeat(kx, cfg.q_per_kv, axis=2)
            vx = jnp.repeat(vx, cfg.q_per_kv, axis=2)
        ox = ATT.attention(qx, kx, vx, causal=False, partition=ctx.kernel_map)
        x = x + jnp.einsum("bshk,hkd->bsd", ox, p["xwo"])
    h = ctx.seq_gather(rms_norm(x, p["ln2"]))
    f, aux = _ffn(cfg, p, h, ctx)
    out = x + ctx.ckpt_constrain(f)
    if want_kv:
        return out, aux, {"k": kr, "v": vr}
    return out, aux


def attn_block_decode(
    cfg: ModelConfig, p: Dict, x: jnp.ndarray, cache: Dict, pos: jnp.ndarray,
    *, window: int = 0, ctx: ShardCtx = NULL_CTX,
    enc_out_kv: Optional[Tuple] = None,
    tables: Optional[jnp.ndarray] = None, page: int = 0, sc: int = 0,
    decode_kernel: str = "gather",
) -> Tuple[jnp.ndarray, Dict]:
    """x: (B, 1, D). cache: {"k": (B, Sc, Kv, Dh), "v": ...} (kv-head form;
    expansion to full heads happens at the attention einsum). ``pos`` is a
    scalar (whole batch at one depth) or a (B,) vector (rows at different
    generation depths — the row-addressable cache-pool decode shape).

    With ``tables``/``page``/``sc`` the cache is block-granular paged:
    k/v are flat ``(n_slots, Kv, Dh)`` slot stacks shared by all rows, and
    the write/read go through each row's page table (physical slot =
    ``table[i // page] * page + i % page``). ``decode_kernel`` is the
    plan-chosen physical operator for the paged read side: "paged" fuses
    the table indirection into the attention op (kernels/paged_attention),
    "gather" materializes the gathered view, "ref" runs the jnp oracle."""
    h = rms_norm(x, p["ln1"])
    rope_pos = pos[None] if pos.ndim == 0 else pos[:, None]
    q, k, v, _ = _qkv(cfg, p, h, rope_pos, ctx=ctx, expand=False)
    if tables is not None:
        kc, vc = ATT.paged_cache_write(cache["k"], cache["v"], k, v, pos,
                                       tables, page, sc, window=window)
        if decode_kernel == "paged":
            # committed-slot mask == decode validity mask for both dense
            # and rotating rows (see kernels/paged_attention.py), so the
            # fused op needs pos and sc but not the window
            o = kops.paged_attention(q, kc, vc, tables, pos, page=page, sc=sc,
                                     partition=ctx.kernel_map)
        elif decode_kernel == "ref":
            o = kref.paged_decode_ref(q, kc, vc, tables, pos, page=page,
                                      sc=sc, window=window)
        else:
            ke, ve = ATT.paged_gather_kv(kc, vc, tables, page, sc, pos=pos)
            if cfg.q_per_kv > 1:
                ke = jnp.repeat(ke, cfg.q_per_kv, axis=2)
                ve = jnp.repeat(ve, cfg.q_per_kv, axis=2)
            o = ATT.decode_attention(q, ke, ve, pos, window=window)
    else:
        kc, vc = ATT.cache_write(cache["k"], cache["v"], k, v, pos,
                                 window=window)
        ke, ve = kc, vc
        if cfg.q_per_kv > 1:
            ke = jnp.repeat(ke, cfg.q_per_kv, axis=2)
            ve = jnp.repeat(ve, cfg.q_per_kv, axis=2)
        o = ATT.decode_attention(q, ke, ve, pos, window=window)
    x = x + jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    cache = dict(cache, k=kc, v=vc)
    if enc_out_kv is not None:
        h = rms_norm(x, p["xln"])
        qx = jnp.einsum("bsd,dhk->bshk", h, p["xwq"])
        kx, vx = enc_out_kv
        if cfg.q_per_kv > 1:
            kx = jnp.repeat(kx, cfg.q_per_kv, axis=2)
            vx = jnp.repeat(vx, cfg.q_per_kv, axis=2)
        ox = ATT.attention(qx, kx, vx, causal=False, partition=ctx.kernel_map)
        x = x + jnp.einsum("bshk,hkd->bsd", ox, p["xwo"])
    h = rms_norm(x, p["ln2"])
    f, _ = _ffn(cfg, p, h, ctx)
    return x + f, cache


def attn_cache_spec(cfg: ModelConfig, batch: int, seq: int, dtype) -> Dict:
    """Per-layer cache specs + logical axes."""
    kvshape = (batch, seq, cfg.num_kv_heads, cfg.head_dim)
    axes = ("batch", "seq", "kv_heads", "head_dim")
    return {
        "k": (kvshape, axes),
        "v": (kvshape, axes),
    }


# ===========================================================================
# MoE FFN — sort-based grouped dispatch (static shapes, EP-shardable)
# ===========================================================================


def moe_ffn(cfg: ModelConfig, p: Dict, x: jnp.ndarray, ctx: ShardCtx):
    """x: (B, S, D) -> (B, S, D), aux load-balance loss.

    Grouped routing (the MaxText/GShard pattern): tokens are split into G
    groups aligned with the data shards; within each group they are routed
    top-k, sorted by expert and packed into a static (G, E, C, D) buffer
    (capacity-dropped). Pack/unpack scatters stay *local to a group* so
    GSPMD partitions them along the batch axis; the expert einsum against
    E-sharded weights is where the all-to-all materializes — visible in the
    dry-run HLO under EXPERT_PARALLEL.
    """
    b, s, d = x.shape
    e, kk = cfg.num_experts, cfg.experts_per_token
    t = b * s
    xf = x.reshape(t, d)
    logits = jnp.einsum("td,de->te", xf, p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = lax.top_k(probs, kk)                      # (t, k)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)

    # Switch-style load-balance aux loss
    density = jnp.mean(jax.nn.one_hot(idx[:, 0], e), axis=0)
    prob_mean = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(density * prob_mean)

    # group count: one group per data shard (1 when unplanned/local)
    g_cnt = 1
    if ctx.mesh_cfg is not None and ctx.plan is not None and ctx.plan.batch_axes:
        g_cnt = ctx.mesh_cfg.data_parallelism
    while t % g_cnt != 0:
        g_cnt //= 2
    tg = t // g_cnt
    cap = int(tg * kk * cfg.moe_capacity_factor / e) + 1  # lint: allow-tracer-host-sync (static shape math)
    cap = max(8, -(-cap // 8) * 8)

    tables = jax.vmap(lambda fe: _routing_tables(fe, e, cap, kk))(
        idx.reshape(g_cnt, tg * kk))

    xg = xf.reshape(g_cnt, tg, d)
    wj = gates.reshape(g_cnt, tg * kk).astype(x.dtype)
    buf = jax.vmap(lambda a, t: _moe_dispatch(kk, a, t))(xg, tables)
    buf = buf.reshape(g_cnt, e, cap, d)
    buf = ctx.constrain(buf, ("batch", "experts", None, None))

    gm = jnp.einsum("gecd,edf->gecf", buf, p["e_wg"])
    um = jnp.einsum("gecd,edf->gecf", buf, p["e_wu"])
    hsil = jax.nn.silu(gm.astype(jnp.float32)).astype(x.dtype) * um
    out_buf = jnp.einsum("gecf,efd->gecd", hsil, p["e_wd"])
    out_buf = ctx.constrain(out_buf, ("batch", "experts", None, None))

    y = jax.vmap(lambda o, w, t: _moe_combine(kk, o, w, t))(
        out_buf.reshape(g_cnt, e * cap, d), wj, tables)
    return y.reshape(b, s, d), aux


def _routing_tables(flat_e: jnp.ndarray, e: int, cap: int, kk: int):
    """Gather-only routing tables for one group.

    flat_e: (tg*k,) expert assignment per (token, k) pair ("j" index).
    Returns (j_of_slot, s_valid, slot_of_j, j_valid) — both directions of
    the token<->slot permutation, so dispatch/combine and their VJPs are
    all expressible as gathers (no scatter: XLA:CPU's scatter expander
    would otherwise materialize dense index tensors).
    """
    tgk = flat_e.shape[0]
    order = jnp.argsort(flat_e)                 # sorted position -> j
    inv = jnp.argsort(order)                    # j -> sorted position
    sorted_e = flat_e[order]
    starts = jnp.searchsorted(sorted_e, jnp.arange(e + 1))
    # slot -> j
    slot_ids = jnp.arange(e * cap)
    s_e, s_c = slot_ids // cap, slot_ids % cap
    spos = starts[s_e] + s_c
    s_valid = spos < starts[s_e + 1]
    j_of_slot = order[jnp.clip(spos, 0, tgk - 1)]
    # j -> slot
    pe = sorted_e[inv]                          # = flat_e
    pos_in_e = inv - starts[pe]
    slot_of_j = pe * cap + jnp.minimum(pos_in_e, cap - 1)
    j_valid = pos_in_e < cap
    return j_of_slot, s_valid, slot_of_j, j_valid



@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _moe_dispatch(kk, xg, tables):
    j_of_slot, s_valid, _, _ = tables
    return xg[j_of_slot // kk] * s_valid[:, None].astype(xg.dtype)


def _moe_dispatch_fwd(kk, xg, tables):
    return _moe_dispatch(kk, xg, tables), (tables, xg.shape)


def _moe_dispatch_bwd(kk, res, d_buf):
    (j_of_slot, s_valid, slot_of_j, j_valid), xshape = res
    vals = d_buf[slot_of_j] * j_valid[:, None].astype(d_buf.dtype)
    dx = vals.reshape(xshape[0], kk, xshape[1]).sum(axis=1).astype(d_buf.dtype)
    return dx, None


_moe_dispatch.defvjp(_moe_dispatch_fwd, _moe_dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _moe_combine(kk, out_flat, wj, tables):
    _, _, slot_of_j, j_valid = tables
    tg = wj.shape[0] // kk
    vals = out_flat[slot_of_j] * (wj * j_valid.astype(wj.dtype))[:, None]
    return vals.reshape(tg, kk, out_flat.shape[1]).sum(axis=1)


def _moe_combine_fwd(kk, out_flat, wj, tables):
    return _moe_combine(kk, out_flat, wj, tables), (out_flat, wj, tables)


def _moe_combine_bwd(kk, res, dy):
    out_flat, wj, tables = res
    j_of_slot, s_valid, slot_of_j, j_valid = tables
    # d_out[slot] = dy[token(slot)] * w[j(slot)]
    dyj = dy[j_of_slot // kk]
    wslot = wj[j_of_slot] * s_valid.astype(wj.dtype)
    d_out = (dyj * wslot[:, None]).astype(out_flat.dtype)
    # d_w[j] = <out[slot(j)], dy[token(j)]>
    dy_rep = jnp.repeat(dy, kk, axis=0)  # j-order tokens
    d_w = jnp.sum(out_flat[slot_of_j] * dy_rep, axis=-1) * j_valid.astype(wj.dtype)
    return d_out, d_w.astype(wj.dtype), None


_moe_combine.defvjp(_moe_combine_fwd, _moe_combine_bwd)


# ===========================================================================
# Mamba-2 SSD block
# ===========================================================================


def ssd_block_params(cfg: ModelConfig) -> Dict:
    d, di, n, h = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.ssm_num_heads
    wc = cfg.ssm_conv_width
    return {
        "ln": ((d,), (None,), "ones"),
        "wz": ((d, di), ("embed", "ssm_inner"), "normal"),
        "wx": ((d, di), ("embed", "ssm_inner"), "normal"),
        "wb": ((d, n), ("embed", None), "normal"),
        "wc": ((d, n), ("embed", None), "normal"),
        "wdt": ((d, h), ("embed", "ssm_heads"), "normal"),
        "dt_bias": ((h,), (None,), "zeros"),
        "conv_x": ((wc, di), ("conv", "ssm_inner"), "normal"),
        "conv_b": ((wc, n), ("conv", None), "normal"),
        "conv_c": ((wc, n), ("conv", None), "normal"),
        "a_log": ((h,), (None,), "ssm_a"),
        "d_skip": ((h,), (None,), "ones"),
        "gate_ln": ((di,), (None,), "ones"),
        "w_out": ((di, d), ("ssm_inner", "embed_out"), "normal"),
    }


def _ssd_pre(cfg, p, h):
    z = jnp.einsum("bsd,de->bse", h, p["wz"])
    xin = jnp.einsum("bsd,de->bse", h, p["wx"])
    bm = jnp.einsum("bsd,dn->bsn", h, p["wb"])
    cm = jnp.einsum("bsd,dn->bsn", h, p["wc"])
    dt = jax.nn.softplus(
        jnp.einsum("bsd,dh->bsh", h, p["wdt"]).astype(jnp.float32)
        + p["dt_bias"].astype(jnp.float32))
    return z, xin, bm, cm, dt


def _conv_tail(x_raw: jnp.ndarray, wd: int, lengths: jnp.ndarray) -> jnp.ndarray:
    """Decode conv state after a prefill of per-row length T: the last
    ``wd - 1`` *raw pre-conv* inputs before position T (zero-padded below
    position 0). x_raw: (B, S, C); lengths: (B,); returns (B, wd-1, C)."""
    b, s, c = x_raw.shape
    pad = jnp.zeros((b, wd - 1, c), x_raw.dtype)
    xp = jnp.concatenate([pad, x_raw], axis=1)      # index j ↔ position j-(wd-1)
    idx = lengths[:, None] + jnp.arange(wd - 1)[None, :]    # positions T-wd+1..T-1
    return jnp.take_along_axis(xp, idx[:, :, None], axis=1)


def ssd_block_apply(cfg: ModelConfig, p: Dict, x: jnp.ndarray,
                    positions=None, *, ctx: ShardCtx = NULL_CTX,
                    lengths: Optional[jnp.ndarray] = None,
                    want_cache: bool = False, **_):
    """Returns (x_out, aux), or with ``want_cache`` the 3-tuple
    (x_out, aux, cache) where cache is the decode state after a per-row
    prompt of ``lengths`` tokens: {"state", "conv_x", "conv_b", "conv_c"}
    exactly as :func:`ssd_block_decode` consumes them."""
    b, s, d = x.shape
    h = ctx.seq_gather(rms_norm(x, p["ln"]))
    z, xin_raw, bm_raw, cm_raw, dt = _ssd_pre(cfg, p, h)
    xin_f = jax.nn.silu(causal_conv1d(xin_raw, p["conv_x"]).astype(jnp.float32))
    bm_f = jax.nn.silu(causal_conv1d(bm_raw, p["conv_b"]).astype(jnp.float32))
    cm_f = jax.nn.silu(causal_conv1d(cm_raw, p["conv_c"]).astype(jnp.float32))
    xin, bm, cm = (t.astype(x.dtype) for t in (xin_f, bm_f, cm_f))
    nh, hd = cfg.ssm_num_heads, cfg.ssm_head_dim
    xh = xin.reshape(b, s, nh, hd)
    a = -jnp.exp(p["a_log"].astype(jnp.float32))
    y = kops.ssd(xh, dt, a, bm, cm, p["d_skip"].astype(jnp.float32),
                 partition=ctx.kernel_map)
    y = y.reshape(b, s, cfg.d_inner)
    y = rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype), p["gate_ln"])
    out = x + ctx.ckpt_constrain(jnp.einsum("bse,ed->bsd", y, p["w_out"]))
    if not want_cache:
        return out, 0.0
    # Final SSM state at per-row prompt length T, in closed form:
    #   state_T = Σ_{t<T} exp(Σ_{u=t+1..T-1} dt_u·a) · dt_t · x_t ⊗ b_t
    # via log-space prefix sums — no (B,S,H,P,N) per-position states held.
    if lengths is None:
        lengths = jnp.full((b,), s, jnp.int32)
    xh_f = xin_f.reshape(b, s, nh, hd)
    logdecay = dt * a[None, None, :]                       # (B,S,H), <= 0
    cum = jnp.cumsum(logdecay, axis=1)
    cum_t = jnp.take_along_axis(cum, (lengths - 1)[:, None, None], axis=1)
    tmask = (jnp.arange(s)[None, :] < lengths[:, None])
    w = jnp.exp(jnp.minimum(cum_t - cum, 0.0)) * tmask[..., None]
    state = jnp.einsum("bsh,bshp,bsn->bhpn", w * dt, xh_f, bm_f)
    wc = cfg.ssm_conv_width
    cache = {
        "state": state,
        "conv_x": _conv_tail(xin_raw, wc, lengths),
        "conv_b": _conv_tail(bm_raw, wc, lengths),
        "conv_c": _conv_tail(cm_raw, wc, lengths),
    }
    return out, 0.0, cache


def ssd_block_decode(cfg: ModelConfig, p: Dict, x: jnp.ndarray, cache: Dict,
                     pos, *, ctx: ShardCtx = NULL_CTX, **_):
    """cache: {"state": (B,H,P,N) f32, "conv_x": (B,W-1,Di),
    "conv_b"/"conv_c": (B,W-1,N)}."""
    b = x.shape[0]
    h = rms_norm(x, p["ln"])
    z, xin, bm, cm, dt = _ssd_pre(cfg, p, h)
    xin, cx = causal_conv1d(xin, p["conv_x"], state=cache["conv_x"])
    bm, cb = causal_conv1d(bm, p["conv_b"], state=cache["conv_b"])
    cm, cc = causal_conv1d(cm, p["conv_c"], state=cache["conv_c"])
    xin = jax.nn.silu(xin.astype(jnp.float32)).astype(x.dtype)
    bm = jax.nn.silu(bm.astype(jnp.float32))[:, 0]       # (B, N) f32
    cm = jax.nn.silu(cm.astype(jnp.float32))[:, 0]
    nh, hd = cfg.ssm_num_heads, cfg.ssm_head_dim
    xh = xin.reshape(b, nh, hd).astype(jnp.float32)      # (B, H, P)
    dtv = dt[:, 0]                                       # (B, H)
    a = -jnp.exp(p["a_log"].astype(jnp.float32))
    decay = jnp.exp(dtv * a[None, :])                    # (B, H)
    upd = (dtv[..., None] * xh)[..., None] * bm[:, None, None, :]
    state = decay[..., None, None] * cache["state"] + upd
    y = jnp.einsum("bhpn,bn->bhp", state, cm)
    y = y + p["d_skip"].astype(jnp.float32)[None, :, None] * xh
    y = y.reshape(b, 1, cfg.d_inner).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype), p["gate_ln"])
    out = x + jnp.einsum("bse,ed->bsd", y, p["w_out"])
    return out, dict(cache, state=state, conv_x=cx, conv_b=cb, conv_c=cc)


def ssd_cache_spec(cfg: ModelConfig, batch: int, dtype) -> Dict:
    wc = cfg.ssm_conv_width
    return {
        "state": ((batch, cfg.ssm_num_heads, cfg.ssm_head_dim, cfg.ssm_state),
                  ("batch", "ssm_heads", None, "ssm_state"), jnp.float32),
        "conv_x": ((batch, wc - 1, cfg.d_inner), ("batch", None, "ssm_inner"), dtype),
        "conv_b": ((batch, wc - 1, cfg.ssm_state), ("batch", None, None), dtype),
        "conv_c": ((batch, wc - 1, cfg.ssm_state), ("batch", None, None), dtype),
    }


# ===========================================================================
# RG-LRU (recurrentgemma) block
# ===========================================================================

LRU_C = 8.0


def rglru_block_params(cfg: ModelConfig) -> Dict:
    d = cfg.d_model
    w = cfg.lru_width or d
    return {
        "ln": ((d,), (None,), "ones"),
        "wx": ((d, w), ("embed", "lru"), "normal"),
        "wy": ((d, w), ("embed", "lru"), "normal"),
        "conv": ((4, w), ("conv", "lru"), "normal"),
        "w_r": ((w, w), (None, "lru"), "normal"),
        "w_i": ((w, w), (None, "lru"), "normal"),
        "b_r": ((w,), (None,), "zeros"),
        "b_i": ((w,), (None,), "zeros"),
        "a_log": ((w,), (None,), "ssm_a"),
        "w_out": ((w, d), ("lru", "embed_out"), "normal"),
    }


def _lru_gates(p, xb):
    r = jax.nn.sigmoid(
        (jnp.einsum("bsw,wv->bsv", xb, p["w_r"]) + p["b_r"]).astype(jnp.float32))
    i = jax.nn.sigmoid(
        (jnp.einsum("bsw,wv->bsv", xb, p["w_i"]) + p["b_i"]).astype(jnp.float32))
    log_a = -LRU_C * jax.nn.softplus(p["a_log"].astype(jnp.float32)) * r
    a = jnp.exp(log_a)
    beta = jnp.sqrt(jnp.maximum(1.0 - a * a, 1e-9))
    return a, beta * i


def rglru_block_apply(cfg: ModelConfig, p: Dict, x: jnp.ndarray,
                      positions=None, *, ctx: ShardCtx = NULL_CTX,
                      lengths: Optional[jnp.ndarray] = None,
                      want_cache: bool = False, **_):
    """Returns (x_out, aux), or with ``want_cache`` the 3-tuple
    (x_out, aux, cache): {"h", "conv"} — the recurrent state after a
    per-row prompt of ``lengths`` tokens, as :func:`rglru_block_decode`
    consumes it (handoff)."""
    h = ctx.seq_gather(rms_norm(x, p["ln"]))
    xb_raw = jnp.einsum("bsd,dw->bsw", h, p["wx"])
    yb = jax.nn.gelu(jnp.einsum("bsd,dw->bsw", h, p["wy"]).astype(jnp.float32))
    xb = causal_conv1d(xb_raw, p["conv"])
    a, gate = _lru_gates(p, xb)
    bt = gate * xb.astype(jnp.float32)
    # h_t = a_t * h_{t-1} + b_t  — associative scan (TPU-parallel recurrence)
    def combine(lhs, rhs):
        return (rhs[0] * lhs[0], rhs[0] * lhs[1] + rhs[1])
    _, hseq = lax.associative_scan(combine, (a, bt), axis=1)
    y = (hseq * yb).astype(x.dtype)
    out = x + ctx.ckpt_constrain(jnp.einsum("bsw,wd->bsd", y, p["w_out"]))
    if not want_cache:
        return out, 0.0
    if lengths is None:
        lengths = jnp.full((x.shape[0],), x.shape[1], jnp.int32)
    h_state = jnp.take_along_axis(hseq, (lengths - 1)[:, None, None], axis=1)[:, 0]
    wd = p["conv"].shape[0]
    cache = {"h": h_state, "conv": _conv_tail(xb_raw, wd, lengths)}
    return out, 0.0, cache


def rglru_block_decode(cfg: ModelConfig, p: Dict, x: jnp.ndarray, cache: Dict,
                       pos, *, ctx: ShardCtx = NULL_CTX, **_):
    """cache: {"h": (B, W) f32, "conv": (B, 3, W)}."""
    hn = rms_norm(x, p["ln"])
    xb = jnp.einsum("bsd,dw->bsw", hn, p["wx"])
    yb = jax.nn.gelu(jnp.einsum("bsd,dw->bsw", hn, p["wy"]).astype(jnp.float32))
    xb, conv_state = causal_conv1d(xb, p["conv"], state=cache["conv"])
    a, gate = _lru_gates(p, xb)
    hstate = a[:, 0] * cache["h"] + (gate * xb.astype(jnp.float32))[:, 0]
    y = (hstate[:, None, :] * yb).astype(x.dtype)
    out = x + jnp.einsum("bsw,wd->bsd", y, p["w_out"])
    return out, dict(cache, h=hstate, conv=conv_state)


def rglru_cache_spec(cfg: ModelConfig, batch: int, dtype) -> Dict:
    w = cfg.lru_width or cfg.d_model
    return {
        "h": ((batch, w), ("batch", "lru"), jnp.float32),
        "conv": ((batch, 3, w), ("batch", None, "lru"), dtype),
    }
