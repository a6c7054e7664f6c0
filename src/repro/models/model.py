"""Model assembly: config -> init/apply/loss/decode_step for every family.

Uniform-stack families (dense, moe, ssm, vlm, audio enc+dec) scan over a
layer-stacked param tree (compact HLO, required for the 126-layer dry-runs);
the hybrid family (recurrentgemma's interleaved RG-LRU/attention pattern)
unrolls its 26 layers.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.config import ModelConfig
from repro.models import blocks as B
from repro.models.common import NULL_CTX, ShardCtx, SpecBuilder, rms_norm, softmax_xent_logits

MOE_AUX_COEF = 0.01


def _stack(entries: Dict, n: int, prefix: str, sb: SpecBuilder):
    for name, (shape, axes, init) in entries.items():
        sb.add(f"{prefix}{name}", (n, *shape), ("layers", *axes), init)


def _subtree(params: Dict, prefix: str) -> Dict:
    plen = len(prefix)
    return {k[plen:]: v for k, v in params.items() if k.startswith(prefix)}


class Model:
    def __init__(self, cfg: ModelConfig, dtype=jnp.bfloat16):
        self.cfg = cfg
        self.dtype = dtype
        self.sb = self._build_specs()

    # ------------------------------------------------------------------
    # parameter specs
    # ------------------------------------------------------------------
    def _build_specs(self) -> SpecBuilder:
        cfg = self.cfg
        sb = SpecBuilder(self.dtype)
        sb.add("embed", (cfg.vocab_size, cfg.d_model), ("vocab", "embed"),
               "normal", scale=0.02)
        if cfg.family == "hybrid":
            pat = cfg.layer_pattern()
            n_r, n_a = pat.count("r"), pat.count("a")
            _stack(B.rglru_block_params(cfg), n_r, "r.", sb)
            _stack(B.attn_block_params(cfg), n_a, "a.", sb)
        elif cfg.family == "ssm":
            _stack(B.ssd_block_params(cfg), cfg.num_layers, "l.", sb)
        elif cfg.is_encdec:
            _stack(B.attn_block_params(cfg), cfg.encoder_layers, "e.", sb)
            _stack(B.attn_block_params(cfg, cross=True), cfg.num_layers, "d.", sb)
        else:
            _stack(B.attn_block_params(cfg), cfg.num_layers, "l.", sb)
        sb.add("final_ln", (cfg.d_model,), (None,), "ones")
        if not cfg.tie_embeddings:
            sb.add("head", (cfg.d_model, cfg.vocab_size), ("embed", "vocab"),
                   "normal", scale=0.02)
        return sb

    def param_specs(self):
        return self.sb.specs()

    def param_axes(self):
        return self.sb.axes()

    def init_params(self, key, shardings=None):
        return self.sb.init(key, shardings)

    def param_count(self) -> int:
        return sum(math.prod(s.shape) for s in self.param_specs().values())

    # ------------------------------------------------------------------
    # embedding / head
    # ------------------------------------------------------------------
    def _embed(self, params, tokens):
        x = params["embed"][tokens]
        if self.cfg.tie_embeddings:
            # tied table serves both roles; sqrt(d) output scaling (gemma/
            # whisper convention) keeps logit and embedding scales sane
            x = x * (self.cfg.d_model ** 0.5)
        return x

    def _logits(self, params, x):
        if self.cfg.tie_embeddings:
            return jnp.einsum("bsd,vd->bsv", x, params["embed"])
        return jnp.einsum("bsd,dv->bsv", x, params["head"])

    # ------------------------------------------------------------------
    # full-sequence forward (train / prefill)
    # ------------------------------------------------------------------
    def apply(self, params, tokens: jnp.ndarray,
              extra: Optional[Dict[str, jnp.ndarray]] = None,
              ctx: ShardCtx = NULL_CTX,
              window_override: Optional[int] = None,
              last_only: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Returns (logits, aux_loss). ``extra``: frames / patch_embeds.
        ``last_only`` projects logits for the final position only — the
        serving prefill path needs one next-token distribution, and the
        (seq x vocab) logits matmul dominates an otherwise forward-only
        pass."""
        cfg = self.cfg
        extra = extra or {}
        x = self._embed(params, tokens)
        prefix = 0
        if cfg.frontend == "vision" and "patch_embeds" in extra:
            pe = extra["patch_embeds"].astype(x.dtype)
            x = jnp.concatenate([pe, x], axis=1)
            prefix = pe.shape[1]
        positions = jnp.arange(x.shape[1])
        window = cfg.window_size if window_override is None else window_override

        enc_out = None
        if cfg.is_encdec:
            enc_out = self._encode(params, extra["frames"].astype(x.dtype), ctx)

        if cfg.family == "hybrid":
            x, aux = self._hybrid_apply(params, x, positions, ctx)
        else:
            x, aux = self._scan_apply(params, x, positions, ctx,
                                      window=window, enc_out=enc_out,
                                      prefix="d." if cfg.is_encdec else "l.")
        x = rms_norm(x, params["final_ln"])
        if last_only:
            # the last position is never inside the vision prefix
            return self._logits(params, x[:, -1:]), aux
        logits = self._logits(params, x)
        if prefix:
            logits = logits[:, prefix:]
        return logits, aux

    def _layer_apply(self, kind, lp, x, positions, ctx, *, causal=True,
                     window=0, enc_out=None):
        if kind == "a":
            return B.attn_block_apply(self.cfg, lp, x, positions,
                                      causal=causal, window=window, ctx=ctx,
                                      enc_out=enc_out)
        if kind == "s":
            return B.ssd_block_apply(self.cfg, lp, x, positions, ctx=ctx)
        return B.rglru_block_apply(self.cfg, lp, x, positions, ctx=ctx)

    def _scan_apply(self, params, x, positions, ctx, *, window, enc_out,
                    prefix, causal=True, kind="a"):
        cfg = self.cfg
        stacked = _subtree(params, prefix)
        if cfg.family == "ssm":
            kind = "s"

        def layer_fn(carry, lp):
            h, _ = self._layer_apply(kind, lp, carry, positions, ctx,
                                     causal=causal, window=window,
                                     enc_out=enc_out)
            h = ctx.ckpt_constrain(h)
            return h, jnp.float32(0.0) if not cfg.num_experts else None

        if cfg.num_experts:
            def layer_fn(carry, lp):  # noqa: F811 (aux-carrying variant)
                h, aux = self._layer_apply(kind, lp, carry, positions, ctx,
                                           causal=causal, window=window,
                                           enc_out=enc_out)
                h = ctx.ckpt_constrain(h)
                return h, aux

        fn = layer_fn
        if ctx.plan is not None and ctx.plan.remat:
            fn = jax.checkpoint(layer_fn, prevent_cse=False)
        x, auxs = lax.scan(fn, x, stacked)
        aux = jnp.mean(auxs) if cfg.num_experts else jnp.float32(0.0)
        return x, aux

    def _encode(self, params, frames, ctx):
        positions = jnp.arange(frames.shape[1])
        x, _ = self._scan_apply(params, frames, positions, ctx, window=0,
                                enc_out=None, prefix="e.", causal=False)
        return rms_norm(x, params["final_ln"])

    def _hybrid_apply(self, params, x, positions, ctx):
        cfg = self.cfg
        pat = cfg.layer_pattern()
        rp = _subtree(params, "r.")
        ap = _subtree(params, "a.")
        ri = ai = 0
        def rglru_fn(lp_, x_):
            return B.rglru_block_apply(cfg, lp_, x_, positions, ctx=ctx)[0]

        def attn_fn(lp_, x_):
            return B.attn_block_apply(cfg, lp_, x_, positions, causal=True,
                                      window=cfg.window_size, ctx=ctx)[0]

        for kind in pat:
            if kind == "r":
                lp = jax.tree.map(lambda v, i=ri: v[i], rp)
                fn = rglru_fn
                ri += 1
            else:
                lp = jax.tree.map(lambda v, i=ai: v[i], ap)
                fn = attn_fn
                ai += 1
            if ctx.plan is not None and ctx.plan.remat:
                fn = jax.checkpoint(fn, prevent_cse=False)
            x = ctx.ckpt_constrain(fn(lp, x))
        return x, jnp.float32(0.0)

    # ------------------------------------------------------------------
    # loss
    # ------------------------------------------------------------------
    def loss(self, params, batch: Dict[str, jnp.ndarray],
             ctx: ShardCtx = NULL_CTX) -> Tuple[jnp.ndarray, Dict]:
        logits, aux = self.apply(params, batch["tokens"],
                                 extra=batch, ctx=ctx)
        xent = softmax_xent_logits(logits, batch["targets"])
        total = xent + MOE_AUX_COEF * aux
        return total, {"xent": xent, "aux": aux}

    # ------------------------------------------------------------------
    # serving: cache construction + one-token decode
    # ------------------------------------------------------------------
    def attn_cache_len(self, seq_len: int) -> int:
        """Attention cache slots for a ``seq_len`` context: the window for
        sliding-window archs, min(seq, serve_window) beyond the long-context
        threshold (DESIGN §5), the full context otherwise."""
        cfg = self.cfg
        if cfg.window_size:
            return min(seq_len, cfg.window_size)
        if seq_len > 262_144 and cfg.serve_window:
            return min(seq_len, cfg.serve_window)
        return seq_len

    def cache_entries(self, batch: int, seq_len: int) -> Dict[str, Tuple]:
        """{name: (shape, axes, dtype)} for the decode cache. ``seq_len`` is
        the max context; full-attention caches hold min(seq, serve_window)
        slots beyond the long-context threshold (DESIGN §5)."""
        cfg = self.cfg
        ent: Dict[str, Tuple] = {}
        pat = cfg.layer_pattern()

        if cfg.family == "hybrid":
            n_r, n_a = pat.count("r"), pat.count("a")
            for name, (shape, axes, dt) in B.rglru_cache_spec(cfg, batch, self.dtype).items():
                ent[f"r.{name}"] = ((n_r, *shape), ("layers", *axes), dt)
            sc = self.attn_cache_len(seq_len)
            for name, (shape, axes) in B.attn_cache_spec(cfg, batch, sc, self.dtype).items():
                ent[f"a.{name}"] = ((n_a, *shape), ("layers", *axes), self.dtype)
        elif cfg.family == "ssm":
            for name, (shape, axes, dt) in B.ssd_cache_spec(cfg, batch, self.dtype).items():
                ent[f"l.{name}"] = ((cfg.num_layers, *shape), ("layers", *axes), dt)
        else:
            sc = self.attn_cache_len(seq_len)
            n = cfg.num_layers
            pfx = "d." if cfg.is_encdec else "l."
            for name, (shape, axes) in B.attn_cache_spec(cfg, batch, sc, self.dtype).items():
                ent[f"{pfx}{name}"] = ((n, *shape), ("layers", *axes), self.dtype)
            if cfg.is_encdec:
                kv = (n, batch, cfg.encoder_seq, cfg.num_kv_heads, cfg.head_dim)
                axes = ("layers", "batch", None, "kv_heads", "head_dim")
                ent["x.k"] = (kv, axes, self.dtype)
                ent["x.v"] = (kv, axes, self.dtype)
        return ent

    @staticmethod
    def is_paged_cache_key(key: str) -> bool:
        """Whether a cache entry pages its sequence dimension: attention
        K/V stacks do; recurrent state (SSD/RG-LRU/conv) and enc-dec cross
        K/V are O(1) or fixed in sequence and stay per-row."""
        return (key.endswith(".k") or key.endswith(".v")) \
            and not key.startswith("x.")

    def paged_cache_entries(self, batch: int, seq_len: int, page: int):
        """Block-granular cache layout: attention K/V entries trade their
        per-row sequence dimension ``(L, B, sc, Kv, Dh)`` for one flat
        per-arena slot stack ``(L, n_pages * page, Kv, Dh)`` shared by all
        rows through per-row page tables; everything else keeps its
        ``(L, B, ...)`` row layout. Returns ``(entries, n_pages, sc)``
        where ``sc`` is the logical slots per row and ``n_pages`` the
        physical page capacity (``batch * ceil(sc / page)``)."""
        ent = self.cache_entries(batch, seq_len)
        sc = self.attn_cache_len(seq_len)
        has_paged = any(self.is_paged_cache_key(k) for k in ent)
        n_pages = batch * -(-sc // page) if has_paged else 0
        out: Dict[str, Tuple] = {}
        for k, (shape, axes, dt) in ent.items():
            if self.is_paged_cache_key(k):
                ll, _b, s, *rest = shape
                assert s == sc, (k, s, sc)
                out[k] = ((ll, n_pages * page, *rest),
                          (axes[0], "kv_slots", *axes[3:]), dt)
            else:
                out[k] = (shape, axes, dt)
        return out, n_pages, sc

    def init_paged_cache(self, batch: int, seq_len: int, page: int):
        ent, _n_pages, _sc = self.paged_cache_entries(batch, seq_len, page)
        return {k: jnp.zeros(s, d) for k, (s, a, d) in ent.items()}

    def cache_specs(self, batch: int, seq_len: int):
        ent = self.cache_entries(batch, seq_len)
        specs = {k: jax.ShapeDtypeStruct(s, d) for k, (s, a, d) in ent.items()}
        axes = {k: a for k, (s, a, d) in ent.items()}
        return specs, axes

    def init_cache(self, batch: int, seq_len: int):
        ent = self.cache_entries(batch, seq_len)
        return {k: jnp.zeros(s, d) for k, (s, a, d) in ent.items()}

    def decode_window(self, seq_len: int) -> int:
        cfg = self.cfg
        if cfg.window_size:
            return cfg.window_size
        if seq_len > 262_144 and cfg.serve_window:
            return cfg.serve_window
        return 0

    def decode_step(self, params, cache: Dict, tokens: jnp.ndarray,
                    pos: jnp.ndarray, ctx: ShardCtx = NULL_CTX,
                    window_override: Optional[int] = None,
                    tables: Optional[jnp.ndarray] = None, page: int = 0,
                    seq_len: int = 0, decode_kernel: str = "gather"):
        """tokens: (B, 1); pos: scalar int32 *or* a (B,) per-row position
        vector — rows of one batch may sit at different generation depths
        (the row-addressable cache-pool decode shape). Returns
        (logits, new_cache). ``window_override``: force rotating-cache
        semantics with this window (otherwise inferred: arch window or
        long-context serve_window).

        ``tables``/``page``: block-granular paged decode — attention K/V in
        ``cache`` are flat per-arena slot stacks (``paged_cache_entries``)
        addressed through the (B, max_pages) page table; ``seq_len`` is
        then the logical context bucket the arena was sized for (the flat
        layout no longer carries it). ``decode_kernel`` is the plan-chosen
        physical operator for the paged read side (paged | gather | ref,
        see ``blocks.attn_block_decode``); ignored on the dense path."""
        cfg = self.cfg
        pos = jnp.asarray(pos, jnp.int32)
        x = self._embed(params, tokens)
        paged = tables is not None and page > 0
        sc = self.attn_cache_len(seq_len) if paged else 0
        window = (window_override if window_override is not None
                  else self.decode_window(seq_len if paged
                                          else cache_seq(cache)))
        if not paged:
            tables, page, sc = None, 0, 0

        if cfg.family == "hybrid":
            x, cache = self._hybrid_decode(params, x, cache, pos, window, ctx,
                                           tables=tables, page=page, sc=sc,
                                           decode_kernel=decode_kernel)
        elif cfg.family == "ssm":
            x, cache = self._scan_decode(params, x, cache, pos, 0, ctx,
                                         prefix="l.", kind="s")
        elif cfg.is_encdec:
            x, cache = self._scan_decode(params, x, cache, pos, window, ctx,
                                         prefix="d.", kind="a", cross=True,
                                         tables=tables, page=page, sc=sc,
                                         decode_kernel=decode_kernel)
        else:
            x, cache = self._scan_decode(params, x, cache, pos, window, ctx,
                                         prefix="l.", kind="a",
                                         tables=tables, page=page, sc=sc,
                                         decode_kernel=decode_kernel)
        x = rms_norm(x, params["final_ln"])
        return self._logits(params, x), cache

    def _scan_decode(self, params, x, cache, pos, window, ctx, *, prefix,
                     kind, cross=False, tables=None, page=0, sc=0,
                     decode_kernel="gather"):
        cfg = self.cfg
        stacked = _subtree(params, prefix)
        lcache = _subtree({k: v for k, v in cache.items()
                           if not k.startswith("x.")}, prefix)
        xkv = (cache.get("x.k"), cache.get("x.v")) if cross else None

        def layer_fn(carry, xs):
            if cross:
                lp, lc, xk, xv = xs
                h, lc2 = B.attn_block_decode(cfg, lp, carry, lc, pos,
                                             window=window, ctx=ctx,
                                             enc_out_kv=(xk, xv),
                                             tables=tables, page=page, sc=sc,
                                             decode_kernel=decode_kernel)
            elif kind == "s":
                lp, lc = xs
                h, lc2 = B.ssd_block_decode(cfg, lp, carry, lc, pos, ctx=ctx)
            else:
                lp, lc = xs
                h, lc2 = B.attn_block_decode(cfg, lp, carry, lc, pos,
                                             window=window, ctx=ctx,
                                             tables=tables, page=page, sc=sc,
                                             decode_kernel=decode_kernel)
            return h, lc2

        xs = (stacked, lcache, *xkv) if cross else (stacked, lcache)
        x, new_lcache = lax.scan(layer_fn, x, xs)
        out = dict(cache)
        for k, v in new_lcache.items():
            out[prefix + k] = v
        return x, out

    def _hybrid_decode(self, params, x, cache, pos, window, ctx,
                       tables=None, page=0, sc=0, decode_kernel="gather"):
        cfg = self.cfg
        pat = cfg.layer_pattern()
        rp, ap = _subtree(params, "r."), _subtree(params, "a.")
        rc = _subtree({k: v for k, v in cache.items() if k.startswith("r.")}, "r.")
        ac = _subtree({k: v for k, v in cache.items() if k.startswith("a.")}, "a.")
        new_rc = {k: v for k, v in rc.items()}
        new_ac = {k: v for k, v in ac.items()}
        ri = ai = 0
        for kind in pat:
            if kind == "r":
                lp = jax.tree.map(lambda v, i=ri: v[i], rp)
                lc = {k: v[ri] for k, v in rc.items()}
                x, lc2 = B.rglru_block_decode(cfg, lp, x, lc, pos, ctx=ctx)
                for k, v in lc2.items():
                    new_rc[k] = new_rc[k].at[ri].set(v)
                ri += 1
            else:
                lp = jax.tree.map(lambda v, i=ai: v[i], ap)
                lc = {k: v[ai] for k, v in ac.items()}
                x, lc2 = B.attn_block_decode(cfg, lp, x, lc, pos,
                                             window=cfg.window_size, ctx=ctx,
                                             tables=tables, page=page, sc=sc,
                                             decode_kernel=decode_kernel)
                for k, v in lc2.items():
                    new_ac[k] = new_ac[k].at[ai].set(v)
                ai += 1
        out = dict(cache)
        out.update({f"r.{k}": v for k, v in new_rc.items()})
        out.update({f"a.{k}": v for k, v in new_ac.items()})
        return x, out

    def build_cross_cache(self, params, frames, ctx: ShardCtx = NULL_CTX):
        """Enc-dec serving setup: run the encoder once and precompute every
        decoder layer's cross-attention K/V over the encoder output.
        Returns {"x.k": (L,B,Senc,Kv,Dh), "x.v": ...} to merge into the
        decode cache."""
        assert self.cfg.is_encdec
        enc_out = self._encode(params, frames, ctx)
        dp = _subtree(params, "d.")
        xk = jnp.einsum("bsd,ldhk->lbshk", enc_out, dp["xwk"])
        xv = jnp.einsum("bsd,ldhk->lbshk", enc_out, dp["xwv"])
        return {"x.k": xk.astype(self.dtype), "x.v": xv.astype(self.dtype)}

    # ------------------------------------------------------------------
    # prefill: full prompt pass that *populates* the decode cache
    # ------------------------------------------------------------------
    @property
    def supports_handoff(self) -> bool:
        """Whether prefill can hand a populated cache to decode. Decoder-
        only text stacks (dense / moe / ssm / hybrid) do; enc-dec and
        modality-prefix frontends still start decode from a zero cache."""
        return not self.cfg.is_encdec and self.cfg.frontend == "none"

    def prefill(self, params, tokens, extra=None, ctx: ShardCtx = NULL_CTX,
                *, lengths: Optional[jnp.ndarray] = None,
                cache_len: Optional[int] = None):
        """Prompt pass returning ``(last_logits, cache)``.

        ``last_logits`` is each row's next-token distribution at its own
        final prompt position (``(B, vocab)``); ``cache`` is a *populated*
        decode cache — the same pytree as :meth:`init_cache` at
        ``(batch, cache_len)`` — so decode continues from the prompt instead
        of restarting on zeros (prefill→decode handoff). ``lengths`` gives
        the per-row prompt length inside the padded ``tokens`` (default: the
        full width); ``cache_len`` sizes the cache context (default: the
        tokens width). Families without handoff return ``cache=None``.
        """
        cfg = self.cfg
        b, s = tokens.shape[0], tokens.shape[1]
        if not self.supports_handoff:
            logits, _ = self.apply(params, tokens, extra=extra, ctx=ctx,
                                   last_only=True)
            return logits[:, -1], None
        if lengths is None:
            lengths = jnp.full((b,), s, jnp.int32)
        lengths = jnp.asarray(lengths, jnp.int32)
        cache_len = int(cache_len) if cache_len else s  # lint: allow-tracer-host-sync (static python int)
        x = self._embed(params, tokens)
        positions = jnp.arange(s)
        if cfg.family == "hybrid":
            x, cache = self._hybrid_prefill(params, x, positions, lengths, ctx)
        else:
            x, cache = self._stack_prefill(params, x, positions, lengths, ctx)
        x = rms_norm(x, params["final_ln"])
        xl = jnp.take_along_axis(x, (lengths - 1)[:, None, None], axis=1)
        logits = self._logits(params, xl)[:, 0]
        # attention K/V land in their decode-slot layout (rotating-window
        # aware); recurrent state entries are already in decode form
        sc = self.attn_cache_len(cache_len)
        cache = {k: (gather_cache_slots(v, lengths, sc)
                     if k.endswith(".k") or k.endswith(".v") else v)
                 for k, v in cache.items()}
        # exact init_cache pytree contract: hybrids whose reduced pattern
        # drops a block kind still carry that kind's zero-layer entries
        for k, (shape, _axes, dt) in self.cache_entries(b, cache_len).items():
            if k not in cache:
                cache[k] = jnp.zeros(shape, dt)
        return logits, cache

    def _stack_prefill(self, params, x, positions, lengths, ctx):
        cfg = self.cfg
        stacked = _subtree(params, "l.")
        if cfg.family == "ssm":
            def layer_fn(carry, lp):
                h, _, c = B.ssd_block_apply(cfg, lp, carry, positions,
                                            ctx=ctx, lengths=lengths,
                                            want_cache=True)
                return ctx.ckpt_constrain(h), c
        else:
            window = cfg.window_size

            def layer_fn(carry, lp):
                h, _, c = B.attn_block_apply(cfg, lp, carry, positions,
                                             causal=True, window=window,
                                             ctx=ctx, want_kv=True)
                return ctx.ckpt_constrain(h), c
        x, ccache = lax.scan(layer_fn, x, stacked)
        return x, {f"l.{k}": v for k, v in ccache.items()}

    def _hybrid_prefill(self, params, x, positions, lengths, ctx):
        cfg = self.cfg
        rp, ap = _subtree(params, "r."), _subtree(params, "a.")
        ri = ai = 0
        rcs, acs = [], []
        for kind in cfg.layer_pattern():
            if kind == "r":
                lp = jax.tree.map(lambda v, i=ri: v[i], rp)
                x, _, c = B.rglru_block_apply(cfg, lp, x, positions, ctx=ctx,
                                              lengths=lengths, want_cache=True)
                rcs.append(c)
                ri += 1
            else:
                lp = jax.tree.map(lambda v, i=ai: v[i], ap)
                x, _, c = B.attn_block_apply(cfg, lp, x, positions,
                                             causal=True,
                                             window=cfg.window_size, ctx=ctx,
                                             want_kv=True)
                acs.append(c)
                ai += 1
            x = ctx.ckpt_constrain(x)
        cache = {}
        for prefix, layer_caches in (("r.", rcs), ("a.", acs)):
            for k in (layer_caches[0] if layer_caches else ()):
                cache[prefix + k] = jnp.stack([c[k] for c in layer_caches])
        return x, cache


def gather_cache_slots(kv: jnp.ndarray, lengths: jnp.ndarray,
                       sc: int) -> jnp.ndarray:
    """Map full-sequence K/V ``(L, B, S, Kv, Dh)`` onto decode-cache slots
    ``(L, B, sc, Kv, Dh)``: slot ``i`` of row ``r`` holds the latest prompt
    position ``p ≡ i (mod sc)`` with ``p < lengths[r]`` — the rotating-
    window layout :func:`attention.decode_attention` masks against (the
    identity layout is the ``sc >= S`` special case). Slots with no valid
    position are zeroed; the decode mask never exposes them."""
    s = kv.shape[2]
    last = lengths - 1
    i = jnp.arange(sc)[None, :]
    p = last[:, None] - jnp.mod(last[:, None] - i, sc)          # (B, sc)
    valid = (p >= 0)[None, :, :, None, None]
    pc = jnp.clip(p, 0, s - 1)[None, :, :, None, None]
    out = jnp.take_along_axis(kv, pc, axis=2)
    return jnp.where(valid, out, jnp.zeros((), kv.dtype))


def cache_seq(cache: Dict) -> int:
    for k, v in cache.items():
        if k.endswith(".k") and not k.startswith("x."):
            return v.shape[2]
    return 0


def build_model(cfg: ModelConfig, dtype=jnp.bfloat16) -> Model:
    return Model(cfg, dtype)
