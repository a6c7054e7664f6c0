"""Serving runtime: prefill + batched decode under a plan.

The decode step is the paper's "low-latency scoring" end of the
"ranging from low-latency scoring to large-scale training" claim; batched
request scoring uses the parfor engine (``test_algo="allreduce"``).

:class:`PlanServer` is the dynamic-recompilation serving session: incoming
(batch, context) requests are rounded up to power-of-two shape buckets, the
plan + jitted decode step for each bucket lives in a :class:`PlanCache`,
and observed runtime statistics (live-bytes watermark, actual shape) feed
back into the compiler when they breach the plan's compile-time estimates.
"""

from __future__ import annotations

import itertools
import time
from dataclasses import dataclass, field
from dataclasses import replace as dc_replace
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.config import (InputShape, MeshConfig, ModelConfig, TPU_V5E,
                          HardwareSpec, hardware_for)
from repro.core.plan_cache import (BucketPolicy, CacheEntry, PlanCache,
                                   PlanKey)
from repro.core.planner import OVER_HBM_BUDGET, PlanCompiler
from repro.core.sharding import make_mesh, tree_specs
from repro.core.strategies import ExecutionPlan, PlanConfig, RuntimeStats
from repro.kernels import ops as kops
from repro.models.common import ShardCtx
from repro.models.model import build_model
from repro.runtime.engine import ServingEngine, WallClock
from repro.runtime.engine_config import (_UNSET, EngineConfig,
                                         fold_legacy_kwargs)
from repro.runtime.kv_cache import KVCachePool
from repro.runtime.metrics import LatencyStats, serve_summary


def make_decode_step(model, plan: PlanConfig, mesh_cfg: MeshConfig,
                     page: int = 0, seq_len: int = 0, mesh=None):
    """``page > 0`` builds the block-granular paged decode step: it takes a
    fifth argument — the (B, max_pages) page-table array — and the cache's
    attention K/V are flat per-arena slot stacks (``paged_cache_entries``).
    ``seq_len`` is the bucket context the arena is sized for (the flat
    layout no longer carries it). The physical decode-attention operator
    (paged Pallas kernel / jnp gather / ref oracle) is read off the plan:
    the compiler chose it per bucket, so the jitted step bakes it in.
    ``mesh``: the concrete mesh of a multi-device server."""
    ctx = ShardCtx(plan, mesh_cfg, mesh)
    kernel = plan.decode_kernel if plan.decode_kernel in ("paged", "ref") \
        else "gather"

    if page:
        # tables defaults to None for families with no paged entries
        # (pure-recurrent stacks): same step signature, dense semantics
        def decode_step(params, cache, tokens, pos, tables=None):
            return model.decode_step(params, cache, tokens, pos, ctx,
                                     tables=tables, page=page,
                                     seq_len=seq_len, decode_kernel=kernel)
    else:
        def decode_step(params, cache, tokens, pos):
            return model.decode_step(params, cache, tokens, pos, ctx)

    return decode_step


def make_prefill(model, plan: PlanConfig, mesh_cfg: MeshConfig, mesh=None):
    ctx = ShardCtx(plan, mesh_cfg, mesh)

    def prefill(params, batch):
        extra = {k: v for k, v in batch.items()
                 if k not in ("tokens", "lengths")}
        return model.prefill(params, batch["tokens"], extra=extra, ctx=ctx,
                             lengths=batch.get("lengths"))

    return prefill


def cache_shardings(model, batch: int, seq_len: int, plan: PlanConfig,
                    mesh_cfg: MeshConfig, mesh):
    specs, axes = model.cache_specs(batch, seq_len)
    parts = tree_specs(specs, axes, plan, mesh_cfg, "cache")
    shards = jax.tree.map(lambda sp: NamedSharding(mesh, sp), parts,
                          is_leaf=lambda x: isinstance(x, P))
    return specs, parts, shards


def greedy_decode(model, params, cache, first_token, start_pos, num_tokens,
                  decode_step=None, tables=None):
    """Greedy generation loop (example/driver use). ``start_pos`` may be a
    scalar (whole batch at one depth) or a (B,) per-row position vector —
    rows handed off from prefill start at their own prompt length.
    ``tables``: page-table array for a paged decode step (the step then
    takes it as a fifth argument; rows must be page-admitted eagerly)."""
    step = decode_step or (lambda p, c, t, q: model.decode_step(p, c, t, q))
    toks = first_token
    out = []
    pos = jnp.asarray(start_pos, jnp.int32)
    for _ in range(num_tokens):
        if tables is not None:
            logits, cache = step(params, cache, toks, pos, tables)
        else:
            logits, cache = step(params, cache, toks, pos)
        toks = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        out.append(toks)
        pos = pos + 1
    if not out:
        return jnp.zeros((first_token.shape[0], 0), jnp.int32), cache
    return jnp.concatenate(out, axis=1), cache


# ===========================================================================
# PlanServer: shape-bucketed serving with plan cache + dynamic recompilation
# ===========================================================================


_NEXT_RID = itertools.count()


@dataclass(frozen=True)
class ServeRequest:
    """One decode request: ``batch`` sequences with ``context`` cache slots,
    generating up to ``new_tokens`` tokens greedily.

    ``rid`` is stamped at construction (process-wide monotone counter), so
    engine handles, scheduler results, and metrics all key on the same id —
    it is no longer minted at queue admission. Stop conditions end a
    request before ``new_tokens``: ``eos_id`` stops a row at its first
    end-of-sequence token, ``stop`` is a tuple of token-id sequences any of
    which terminates a row when its output ends with one (a request
    finishes when every row has stopped)."""

    batch: int
    context: int
    new_tokens: int = 8
    eos_id: Optional[int] = None
    stop: Tuple[Tuple[int, ...], ...] = ()
    rid: int = field(default_factory=lambda: next(_NEXT_RID))


class PlanOverBudgetError(RuntimeError):
    """A server on a TPU was handed a plan that does not fit its HBM."""


def device_hardware() -> HardwareSpec:
    """The chip the server runs on: on a TPU backend, the published figures
    for ``jax.devices()[0].device_kind`` (an unknown kind raises); off TPU,
    ``TPU_V5E`` stays the analytic target the CPU tests plan for."""
    if not kops.on_tpu():
        return TPU_V5E
    return hardware_for(jax.devices()[0].device_kind)


def _tree_bytes(tree) -> float:
    return float(sum(x.nbytes for x in jax.tree.leaves(tree)  # lint: allow-tracer-host-sync (host-side sizing)
                     if hasattr(x, "nbytes")))


class PlanServer:
    """Serving session that amortizes plan compilation across requests.

    Request flow (mirrors SystemML's recompilation loop):

    1. the request shape rounds up to its power-of-two bucket
       (:class:`BucketPolicy`) and forms a :class:`PlanKey`;
    2. cache hit → reuse the bucket's compiled plan and jitted decode step;
       miss → one planner walk + one ``jax.jit`` trace, installed in the
       LRU cache;
    3. after execution, observed :class:`RuntimeStats` (live-bytes
       watermark, actual shape) are checked against the plan's compile-time
       estimates; a breach beyond ``recompile_margin`` re-enters the
       compiler with runtime-corrected statistics and installs the new
       plan — at most once per divergence, since the corrected estimate
       covers the observation.

    With ``enable_cache=False`` every request pays the full compile+trace
    path (the pre-cache behaviour, kept for A/B benchmarking).
    """

    def __init__(
        self,
        cfg: ModelConfig,
        mesh_cfg: Optional[MeshConfig] = None,
        dtype=_UNSET,
        *,
        hw: Optional[HardwareSpec] = None,
        config: Optional[EngineConfig] = None,
        enable_cache: bool = _UNSET,
        capacity: int = _UNSET,
        recompile_margin: float = _UNSET,
        policy: BucketPolicy = BucketPolicy(),
        seed: int = _UNSET,
        prefill: bool = _UNSET,
        pool_arenas: int = _UNSET,
        pool_max_arenas: int = _UNSET,
        pool_max_bytes: float = _UNSET,
        page_size: int = _UNSET,
    ):
        # one config surface (EngineConfig); the per-knob kwargs are the
        # deprecated shims, overlaid on top so existing call sites keep
        # their exact behaviour for one release
        self.config = fold_legacy_kwargs(
            config, "PlanServer",
            dtype=(np.dtype(dtype).name if dtype is not _UNSET else _UNSET),
            enable_cache=enable_cache, cache_capacity=capacity,
            recompile_margin=recompile_margin, seed=seed, prefill=prefill,
            pool_arenas=pool_arenas, pool_max_arenas=pool_max_arenas,
            pool_max_bytes=pool_max_bytes, page_size=page_size)
        c = self.config
        self.cfg = cfg
        self.mesh_cfg = mesh_cfg or MeshConfig(
            shape=(len(jax.devices()),), axis_names=("data",))
        # several devices: a real mesh; params are created under it and
        # every step constrains its tensors on it
        self.mesh = (make_mesh(self.mesh_cfg.shape, self.mesh_cfg.axis_names)
                     if self.mesh_cfg.num_devices > 1 else None)
        self.dtype = c.jnp_dtype()
        self.dtype_name = c.dtype
        self.model = build_model(cfg, dtype=self.dtype)
        # block-granular paged arenas (0 = row-granular PR-3 behaviour):
        # rows commit pages, not bucket-shaped sequence slack
        self.page_size = max(0, int(c.page_size))  # lint: allow-tracer-host-sync (config int)
        # compile-time cache statistics are sized for a pool provisioned
        # with ``pool_arenas`` concurrent bucket arenas; the pool's live
        # bytes are checked against them at observe() time
        self.pool_arenas = max(1, c.pool_arenas)
        self.policy = policy
        self.hw = hw if hw is not None else device_hardware()
        self.compiler = PlanCompiler(self.hw, cache_pool_arenas=self.pool_arenas,
                                     cache_page_size=self.page_size,
                                     decode_kernel=c.decode_kernel,
                                     donate_cache=c.donate)
        self.params = self.model.init_params(jax.random.PRNGKey(c.seed),
                                             self._param_shardings(c))
        self._params_bytes = _tree_bytes(self.params)
        self.pool = KVCachePool(self.model, max_arenas=c.pool_max_arenas,
                                max_bytes=c.pool_max_bytes,
                                page_size=self.page_size)
        self.cache = PlanCache(capacity=c.cache_capacity)
        self.metrics = self.cache.metrics
        self.latency = LatencyStats()
        self.enable_cache = c.enable_cache
        self.recompile_margin = c.recompile_margin
        # prefill=True: handle() runs the cached-prefill prompt pass, hands
        # the populated cache rows to decode (no zero-cache restart), and
        # the prefill-produced first token opens the output; False keeps the
        # PR-1 decode-only request shape. The scheduler always prefills.
        self.prefill = c.prefill
        self._engine: Optional[ServingEngine] = None

    def _param_shardings(self, c: EngineConfig):
        """Where the params live on a multi-device mesh (None: one device).
        Params are shared by every bucket's plan, so they follow the plan
        for the widest decode group at the smallest context; a bucket whose
        plan lays them out differently still runs correctly, its
        constraints resharding activations around them."""
        if self.mesh is None:
            return None
        shape = InputShape("serve_params", self.policy.min_seq,
                           c.max_group_batch, "decode")
        plan = self.compiler.compile(self.cfg, shape, self.mesh_cfg,
                                     dtype=self.dtype_name)
        self._check_budget(plan)
        specs = tree_specs(self.model.param_specs(), self.model.param_axes(),
                           plan.config, self.mesh_cfg, "param")
        return {k: NamedSharding(self.mesh, sp) for k, sp in specs.items()}

    def _check_budget(self, plan: ExecutionPlan) -> None:
        """On a real chip, refuse a plan the compiler could only emit with
        its over-budget warning: it would not fit the device's HBM."""
        if OVER_HBM_BUDGET in plan.config.notes and kops.on_tpu():
            raise PlanOverBudgetError(
                f"{self.cfg.name} {plan.shape.kind} "
                f"{plan.shape.global_batch}x{plan.shape.seq_len} on "
                f"{self.hw.name}: {OVER_HBM_BUDGET} "
                f"({plan.memory.total / 1e9:.2f} GB per chip estimated)")

    # ------------------------------------------------------------------
    def _build_step(self, plan: ExecutionPlan):
        self._check_budget(plan)
        if plan.shape.kind == "prefill":
            # nothing safe to donate: the prompt pass has no cache input
            # and params are shared by every plan
            return jax.jit(make_prefill(self.model, plan.config,
                                        self.mesh_cfg, self.mesh))
        step = make_decode_step(self.model, plan.config, self.mesh_cfg,
                                page=self.page_size,
                                seq_len=plan.shape.seq_len, mesh=self.mesh)
        if plan.config.donate_cache:
            # donate the cache pytree (positional arg 1): XLA aliases each
            # cache output onto its input buffer, so the slot stacks and
            # recurrent state update in place instead of double-buffering.
            # The engine relinquishes the arena's pytree for the step and
            # re-adopts the output (CacheArena.relinquish/adopt).
            return jax.jit(step, donate_argnums=(1,))
        return jax.jit(step)

    def _compile_entry(self, key: PlanKey) -> CacheEntry:
        t0 = time.perf_counter()
        plan = self.compiler.compile(self.cfg, key.bucket_shape(),
                                     self.mesh_cfg, dtype=self.dtype_name)
        entry = CacheEntry(key=key, plan=plan, step_fn=self._build_step(plan))
        self.metrics.compile_seconds += time.perf_counter() - t0
        return entry

    def _key_for(self, batch: int, context: int, kind: str) -> PlanKey:
        shape = InputShape(f"req_{batch}x{context}", context, batch, kind)
        return PlanKey.for_request(self.cfg, self.mesh_cfg, self.dtype_name,
                                   shape, self.policy)

    def _entry_for(self, key: PlanKey) -> CacheEntry:
        if self.enable_cache:
            return self.cache.get_or_compile(
                key, lambda: self._compile_entry(key))
        # pre-cache behaviour: full planner walk + fresh XLA trace
        self.metrics.misses += 1
        self.metrics.compiles += 1
        return self._compile_entry(key)

    def decode_entry(self, batch: int, context: int) -> CacheEntry:
        """Bucketed decode plan + jitted decode step (cache-backed)."""
        return self._entry_for(self._key_for(batch, context, "decode"))

    def prefill_entry(self, batch: int, context: int) -> CacheEntry:
        """Bucketed prefill plan + jitted prefill fn from the same cache.

        The prefill path shares the :class:`PlanCache` with decode —
        ``PlanKey.kind`` keeps the key spaces disjoint, so one server holds
        both plan families and the scheduler draws each from the cache."""
        return self._entry_for(self._key_for(batch, context, "prefill"))

    def run_prefill(self, entry: CacheEntry, tokens=None, lengths=None):
        """Execute a cached prefill plan at its bucket shape; returns
        ``(logits, cache)``: per-row last-prompt-position logits
        ``(batch_bucket, vocab)`` plus the populated decode cache (None for
        families without handoff). ``lengths`` is the per-row prompt length
        inside the padded bucket (default: the full bucket width)."""
        b, s = entry.key.batch_bucket, entry.key.seq_bucket
        if tokens is None:
            tokens = jnp.ones((b, s), jnp.int32)
        if lengths is None:
            lengths = jnp.full((b,), s, jnp.int32)
        logits, kv = entry.step_fn(
            self.params, {"tokens": tokens, "lengths": lengths})
        jax.block_until_ready(logits)
        return logits, kv

    def prefill_first_token(self, batch: int, context: int,
                            lengths=None) -> Tuple[Any, Any]:
        """Prompt pass through the cached prefill plan; returns the greedy
        first decode token per bucket row ``(batch_bucket, 1)`` *and* the
        populated decode cache for the handoff. Prefill and decode share
        the bucket policy, so the rows and cache slots line up with the
        decode bucket of the same request shape."""
        entry = self.prefill_entry(batch, context)
        logits, kv = self.run_prefill(entry, lengths=lengths)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None], kv

    # ------------------------------------------------------------------
    def observed_stats(self, entry: CacheEntry, shape: InputShape,
                       toks, double_buffer_bytes: float = 0.0
                       ) -> RuntimeStats:
        """Measured runtime statistics for one executed request: the live-
        bytes watermark per chip (params + the *whole* KV-cache pool +
        in-flight tokens) and the pool's own per-chip bytes. Each tensor
        class only divides across the chips the plan actually shards it
        over; replicated layouts hold a full copy per chip.

        ``double_buffer_bytes``: extra cache-class bytes observed live
        during the tick — the engine passes the group's arena footprint
        when the step did *not* consume its donated cache input (the
        un-donated step holds input + output copies simultaneously), so
        the watermark reflects what the device actually held."""
        cfgp = entry.plan.config
        mesh = self.mesh_cfg
        param_div = 1
        if cfgp.tensor_parallel or cfgp.expert_parallel:
            param_div *= mesh.model_parallelism
        if cfgp.params_over_data:
            param_div *= mesh.data_parallelism
        kv_div = 1
        for ax, sz in zip(mesh.axis_names, mesh.shape):
            if ax in cfgp.cache_batch_axes or ax in cfgp.cache_seq_axes:
                kv_div *= sz
        if cfgp.cache_heads_over_model:
            kv_div *= mesh.model_parallelism
        pool_bytes = self.pool.live_bytes()
        watermark = (self._params_bytes / param_div
                     + (pool_bytes + double_buffer_bytes + toks.nbytes)
                     / kv_div)
        return RuntimeStats(shape=shape, watermark_bytes=watermark,
                            cache_pool_bytes=pool_bytes / kv_div)

    def observe(self, key: PlanKey, stats: RuntimeStats
                ) -> Tuple[Optional[CacheEntry], Tuple[str, ...]]:
        """Feed observed runtime statistics back into the cache (dynamic
        recompilation). Compile time is billed only when ``refresh``
        actually re-entered the compiler — a rebucket that reuses an
        existing entry at the grown bucket compiles nothing and costs
        nothing."""
        if not self.enable_cache:
            return None, ()
        t_r = time.perf_counter()
        recompiles_before = self.metrics.recompiles
        refreshed, reasons = self.cache.refresh(
            key, stats, self.compiler, margin=self.recompile_margin,
            build_step=self._build_step, policy=self.policy)
        if self.metrics.recompiles > recompiles_before:
            self.metrics.compile_seconds += time.perf_counter() - t_r
        return refreshed, reasons

    def request_span(self, req: ServeRequest) -> int:
        """Context slots a request needs end-to-end: prompt plus every
        generated token. Bucketing on the span (not the bare context) is
        what keeps a context sitting exactly on a power-of-two boundary
        from overflowing its cache rows mid-decode."""
        return req.context + req.new_tokens

    # ------------------------------------------------------------------
    def handle(self, req: ServeRequest) -> Dict[str, Any]:
        """Serve one request synchronously; returns tokens + accounting.

        This is a thin submit-and-drain adapter over
        :class:`~repro.runtime.engine.ServingEngine` — the one request-
        lifecycle implementation — configured for the sequential shape:
        wall-clock time, no mid-decode joins, whole-span page commitment at
        admission. With ``prefill=True`` the prompt pass populates the
        request's cache rows (prefill→decode handoff): decode step 0
        consumes the prefill-produced token *at the prompt's position*,
        that token opens the output, and no token is recomputed against an
        empty cache. Stop conditions (``eos_id`` / ``stop``) and the
        engine's cancellation path apply here too.
        """
        if self._engine is None:
            # count_first: with a handoff the prefill token is output token
            # #1; enc-dec / modality frontends (and the decode-only PR-1
            # shape) emit exactly new_tokens decode outputs instead
            # sync_per_tick=False: nobody streams this request, so the
            # decode steps dispatch asynchronously (the pre-engine greedy
            # loop's behaviour) and one block at the end settles the work
            self._engine = ServingEngine(
                self,
                config=dc_replace(self.config, join_mid_decode=False),
                clock=WallClock(), prefill=self.prefill,
                count_first=self.prefill and self.model.supports_handoff,
                eager_pages=True, sync_per_tick=False)
        eng = self._engine
        t0 = time.perf_counter()
        handle = eng.submit(req)
        while handle.result is None and not eng.idle:
            eng.step()
        rec = handle.result
        jax.block_until_ready(rec["tokens"])
        # latency includes any in-request recompilation — that cost is the
        # mechanism under measurement, not overhead to hide
        latency = time.perf_counter() - t0
        self.latency.record(latency)
        out = {
            "tokens": rec["tokens"],
            "latency_s": latency,
            "bucket": rec["bucket"],
            "plan": rec["plan"],
            "recompiled": rec["recompiled"],
            "recompile_reasons": rec["recompile_reasons"],
            "watermark_bytes": rec["watermark_bytes"],
            "pool_bytes": rec["pool_bytes"],
            "finish_reason": rec["finish_reason"],
            "rid": req.rid,
        }
        eng.discard(handle)   # one-shot: don't accumulate engine records
        return out

    # ------------------------------------------------------------------
    def summary(self) -> str:
        return serve_summary(self.metrics, self.latency)
