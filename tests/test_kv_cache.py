"""Row-addressable KV-cache pool (PR 3): arena/row lifecycle, the
prefill→decode handoff (prompt-conditioning equivalence per family),
span-covering request buckets at power-of-two context boundaries,
mid-decode group joins, and the pool-breach recompilation predicate."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import InputShape
from repro.configs import get_config
from repro.core.plan_cache import BucketPolicy, recompile_reasons
from repro.core.strategies import RuntimeStats
from repro.models.model import build_model
from repro.runtime.kv_cache import KVCachePool
from repro.runtime.metrics import pool_summary
from repro.runtime.scheduler import (ContinuousBatchingScheduler,
                                     RequestQueue, simulate_arrivals)
from repro.runtime.serve_loop import PlanServer, ServeRequest

KEY = jax.random.PRNGKey(0)
CFG = get_config("yi-6b-smoke")


# ---------------------------------------------------------------------------
# pool: arena + row lifecycle
# ---------------------------------------------------------------------------


def _pool(model=None, **kw):
    model = model or build_model(CFG, dtype=jnp.float32)
    return KVCachePool(model, **kw)


def test_pool_arena_bytes_match_materialized_cache():
    model = build_model(CFG, dtype=jnp.float32)
    pool = _pool(model)
    kv = model.init_cache(4, 128)
    assert pool.arena_bytes(4, 128) == sum(v.nbytes for v in kv.values())


def test_pool_lease_reuse_and_row_accounting():
    pool = _pool()
    a = pool.acquire(4, 64)
    assert (a.batch, a.seq) == (4, 64) and a.rows_free == 4
    rows = pool.alloc_rows(a, 3)
    assert rows == [0, 1, 2] and a.rows_used == 3
    assert pool.occupancy() == pytest.approx(0.75)
    pool.free_rows(a, rows[:1])
    assert a.rows_free == 2
    pool.release(a)
    assert pool.live_bytes() == 0 and pool.total_bytes() > 0
    # same-bucket lease recycles the arena; its rows count as reused
    b = pool.acquire(4, 64)
    assert b is a and pool.metrics.arenas_reused == 1
    pool.alloc_rows(b, 2)
    assert pool.metrics.rows_reused == 2


def test_pool_double_free_rejected():
    pool = _pool()
    a = pool.acquire(2, 64)
    rows = pool.alloc_rows(a, 1)
    pool.free_rows(a, rows)
    with pytest.raises(ValueError):
        pool.free_rows(a, rows)


def test_pool_budget_denies_then_force_overrides():
    pool = _pool(max_arenas=1)
    a = pool.acquire(2, 64)
    assert pool.acquire(2, 128) is None
    assert pool.metrics.arenas_denied == 1
    forced = pool.acquire(2, 128, force=True)
    assert forced is not None
    pool.release(a)
    pool.release(forced)
    # a pooled free arena of the right bucket is always acquirable
    assert pool.can_acquire(2, 64)


def test_pool_free_arenas_lru_evicted():
    """Retired shape buckets cannot pin HBM forever: the free pool is
    LRU-capped, oldest release evicted first."""
    pool = _pool(max_free=2)
    arenas = [pool.acquire(1, s) for s in (16, 32, 64)]
    for a in arenas:
        pool.release(a)
    assert pool.metrics.arenas_evicted == 1
    assert pool.arena_count == 2
    assert not any((a.batch, a.seq) == (1, 16) for a in pool._pooled)


def test_pool_budget_evicts_idle_free_arenas_before_denying():
    """An idle free arena of another bucket never blocks a lease the
    budget could otherwise serve — it is evicted instead."""
    pool = _pool(max_arenas=2)
    a = pool.acquire(1, 16)
    pool.release(a)                  # one idle free arena
    pool.acquire(1, 32)              # leased; arena count at the cap
    c = pool.acquire(1, 64)          # evicts the idle (1,16) to make room
    assert c is not None
    assert pool.metrics.arenas_evicted == 1 and pool.metrics.arenas_denied == 0


def test_pool_zeroing_on_reuse():
    pool = _pool()
    a = pool.acquire(2, 64)
    k = next(iter(a.cache))
    a.cache[k] = a.cache[k] + 1.0
    pool.release(a)
    b = pool.acquire(2, 64, zero=True)
    assert float(jnp.max(jnp.abs(b.cache[k]))) == 0.0


def test_pool_write_rows_scatters_per_row():
    model = build_model(CFG, dtype=jnp.float32)
    pool = _pool(model)
    a = pool.acquire(4, 64)
    src = {k: jnp.full_like(v, 7.0) for k, v in model.init_cache(4, 64).items()}
    pool.write_rows(a, [1, 3], src, src_rows=[0, 1])
    for v in a.cache.values():
        got = np.asarray(jnp.abs(v).max(axis=tuple(
            i for i in range(v.ndim) if i != 1)))
        np.testing.assert_array_equal(got > 0, [False, True, False, True])


# ---------------------------------------------------------------------------
# prefill→decode handoff: prompt-conditioning equivalence per family
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["yi-6b", "mamba2-1.3b", "recurrentgemma-2b"])
def test_handoff_decode_matches_full_forward(arch):
    """Decode over a prefill-populated cache — per-row prompt lengths, rows
    at different depths in one batch — must match the full-sequence forward
    at every generated position (attention, SSD, hybrid)."""
    cfg = get_config(arch + "-smoke")
    model = build_model(cfg, dtype=jnp.float32)
    params = model.init_params(KEY)
    B, S = 2, 16
    lengths = jnp.array([12, 9], jnp.int32)
    toks = jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)
    logits, cache = model.prefill(params, toks, lengths=lengths, cache_len=32)
    # prefill logits == full forward at each row's last prompt position
    seqs = []
    for r in range(B):
        T = int(lengths[r])
        full, _ = model.apply(params, toks[r:r + 1, :T])
        np.testing.assert_allclose(np.asarray(logits[r]),
                                   np.asarray(full[0, T - 1]),
                                   rtol=5e-3, atol=5e-3)
        seqs.append(list(np.asarray(toks[r, :T])))
    # cache pytree is exactly the init_cache layout
    ref = model.init_cache(B, 32)
    assert {k: (v.shape, v.dtype) for k, v in cache.items()} \
        == {k: (v.shape, v.dtype) for k, v in ref.items()}
    # greedy decode from the handoff, per-row positions
    pos = lengths
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
    for r in range(B):
        seqs[r].append(int(tok[r, 0]))
    for step in range(3):
        lg, cache = model.decode_step(params, cache, tok, pos)
        for r in range(B):
            full, _ = model.apply(params, jnp.asarray([seqs[r]]))
            np.testing.assert_allclose(
                np.asarray(lg[r, 0]), np.asarray(full[0, -1]),
                rtol=5e-3, atol=5e-3,
                err_msg=f"{arch} row {r} decode step {step}")
        tok = jnp.argmax(lg[:, -1:], axis=-1).astype(jnp.int32)
        pos = pos + 1
        for r in range(B):
            seqs[r].append(int(tok[r, 0]))


def test_handoff_rotating_window_prompt_longer_than_window():
    """Hybrid prompts longer than the attention window land in rotated
    cache slots that decode's rotating mask reads back correctly. The
    reduced config's pattern is all-RG-LRU, so force one real windowed
    attention layer into the stack."""
    cfg = get_config("recurrentgemma-2b-smoke").replace(  # window_size=32
        block_pattern="ra")
    model = build_model(cfg, dtype=jnp.float32)
    params = model.init_params(jax.random.PRNGKey(1))
    lengths = jnp.array([45, 38], jnp.int32)
    toks = jax.random.randint(KEY, (2, 48), 0, cfg.vocab_size)
    logits, cache = model.prefill(params, toks, lengths=lengths, cache_len=64)
    lg, _ = model.decode_step(
        params, cache, jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32),
        lengths)
    for r in range(2):
        T = int(lengths[r])
        seq = list(np.asarray(toks[r, :T])) + [int(jnp.argmax(logits[r]))]
        full, _ = model.apply(params, jnp.asarray([seq]))
        np.testing.assert_allclose(np.asarray(lg[r, 0]),
                                   np.asarray(full[0, -1]),
                                   rtol=5e-3, atol=5e-3)


def test_plan_server_handoff_first_token_not_recomputed():
    """Satellite fix: the prefill-produced greedy token opens the output,
    decode consumes it at the prompt's position, and the whole output
    equals the greedy chain of the full-sequence forward — i.e. generated
    text actually conditions on the prompt."""
    srv = PlanServer(CFG, dtype=jnp.float32, capacity=16, prefill=True)
    req = ServeRequest(1, 20, 4)
    out = srv.handle(req)
    # reference greedy chain from full forwards (prompt = the same all-ones
    # bucket tokens the server prefills with)
    seq = [1] * req.context
    expect = []
    for _ in range(req.new_tokens):
        logits, _ = srv.model.apply(srv.params, jnp.asarray([seq]))
        t = int(jnp.argmax(logits[0, -1]))
        expect.append(t)
        seq.append(t)
    assert out["tokens"].shape == (1, req.new_tokens)
    assert out["tokens"][0].tolist() == expect


def test_scheduler_group_tokens_condition_on_prompt():
    """The scheduler path hands prefill rows to decode too: every member's
    tokens equal its own full-forward greedy chain, even when coalesced
    rows sit at different prompt lengths."""
    srv = PlanServer(CFG, dtype=jnp.float32, capacity=16)
    sched = ContinuousBatchingScheduler(srv, max_group_batch=8)
    reqs = [ServeRequest(1, 20, 3), ServeRequest(1, 28, 3)]  # one group
    results = sched.run(simulate_arrivals(reqs))
    assert len(results) == 2
    for rec in results:
        seq = [1] * rec["context"]
        expect = []
        for _ in range(3):
            logits, _ = srv.model.apply(srv.params, jnp.asarray([seq]))
            t = int(jnp.argmax(logits[0, -1]))
            expect.append(t)
            seq.append(t)
        assert rec["tokens"][0].tolist() == expect, rec["rid"]


# ---------------------------------------------------------------------------
# RequestQueue: span buckets at exact power-of-two context boundaries
# ---------------------------------------------------------------------------


def test_queue_buckets_cover_generation_span():
    q = RequestQueue(BucketPolicy(min_batch=1, min_seq=16))
    # context exactly on a power-of-two boundary: the span pushes it up a
    # bucket, so decode rows always have slots for every generated token
    assert q.seq_bucket(ServeRequest(1, 64, 8)) == 128
    assert q.seq_bucket(ServeRequest(1, 128, 1)) == 256
    # spans landing exactly on the boundary stay in it
    assert q.seq_bucket(ServeRequest(1, 56, 8)) == 64
    assert q.seq_bucket(ServeRequest(1, 127, 1)) == 128


def test_boundary_context_request_decodes_full_span():
    """A context sitting exactly on its bucket boundary still gets cache
    rows for every generated token (the old context-only bucketing would
    have overflowed the cache mid-decode)."""
    srv = PlanServer(CFG, dtype=jnp.float32, capacity=16, prefill=True)
    req = ServeRequest(1, 64, 4)
    out = srv.handle(req)
    assert out["bucket"] == (1, 128)
    assert out["tokens"].shape == (1, 4)
    assert not out["recompiled"]


def test_queue_take_joinable_filters_bucket_and_stays_fifo():
    q = RequestQueue(BucketPolicy(min_batch=1, min_seq=16))
    q.admit(ServeRequest(1, 100, 8))    # bucket 128 — fits
    q.admit(ServeRequest(1, 40, 8))     # bucket 64 — other bucket, skipped
    q.admit(ServeRequest(4, 100, 8))    # bucket 128 — too big: scan STOPS
    q.admit(ServeRequest(2, 90, 8))     # bucket 128 — behind the wide one
    taken = q.take_joinable(128, max_rows=3)
    # FIFO within the bucket: nothing behind the unfitting wide request may
    # leapfrog it (no join starvation of wide same-bucket heads)
    assert [t.req.context for t in taken] == [100]
    assert [(t.req.batch, t.req.context) for t in q.pending] \
        == [(1, 40), (4, 100), (2, 90)]


def test_wide_head_not_starved_by_joiners():
    """A wide same-bucket request at the head of the line blocks further
    joins into the arena it is waiting for, so the in-flight group drains
    and the head gets served (regression: join starvation)."""
    srv = PlanServer(CFG, dtype=jnp.float32, capacity=16, pool_max_arenas=1)
    sched = ContinuousBatchingScheduler(srv, max_group_batch=8,
                                        join_mid_decode=True)
    reqs = [ServeRequest(5, 100, 8),    # leases the only arena
            ServeRequest(5, 100, 4),    # wide: can't fit 3 rows
            ServeRequest(1, 90, 2),     # narrow, same bucket
            ServeRequest(1, 92, 2)]
    arrivals = [(0.001 * i, r) for i, r in enumerate(reqs)]
    results = sched.run(arrivals)
    assert len(results) == 4
    # the narrow requests did not leapfrog the wide head mid-decode: no
    # joins happened, and everyone queued behind the head rode the head's
    # own (post-drain) group instead of starting earlier
    assert sched.metrics.joins == 0
    wide = next(r for r in results if r["rid"] == reqs[1].rid)
    narrow = [r for r in results
              if r["rid"] in (reqs[2].rid, reqs[3].rid)]
    assert wide["group_size"] == 3
    assert all(n["joined_at_step"] == 0 for n in narrow)
    assert all(n["bucket"] == wide["bucket"] for n in narrow)


def test_queue_requeue_front_preserves_order():
    q = RequestQueue()
    a = q.admit(ServeRequest(1, 40))
    b = q.admit(ServeRequest(1, 44))
    group = q.next_group()
    assert [m.rid for m in group] == [a.rid, b.rid]
    q.admit(ServeRequest(1, 100))
    q.requeue_front(group)
    assert [m.req.context for m in q.pending] == [40, 44, 100]


# ---------------------------------------------------------------------------
# mid-decode joins
# ---------------------------------------------------------------------------


def test_mid_decode_join_absorbs_into_free_rows():
    """With the pool capped at one arena, requests arriving behind a long
    decode join its free rows mid-flight instead of waiting for the drain
    — and their outputs still condition on their own prompts."""
    srv = PlanServer(CFG, dtype=jnp.float32, capacity=16, pool_max_arenas=1)
    sched = ContinuousBatchingScheduler(srv, max_group_batch=8,
                                        join_mid_decode=True)
    head = ServeRequest(5, 100, 12)
    arrivals = [(0.0, head)] + \
               [(0.001, ServeRequest(1, 90 + 2 * i, 3)) for i in range(3)]
    results = sched.run(arrivals)
    assert len(results) == 4
    assert sched.metrics.joins == 3 and sched.metrics.join_rows == 3
    joined = [r for r in results if r["rid"] != head.rid]
    assert all(r["joined_at_step"] >= 1 for r in joined)
    assert all(r["tokens"].shape == (1, 3) for r in joined)
    # one arena served everything; the head's group never widened past it
    assert srv.pool.metrics.arenas_created == 1
    assert srv.metrics.recompiles == 0
    assert "joins=3" in sched.summary()


def test_admission_only_waits_for_arena():
    """join_mid_decode=False with a full pool: tail requests queue until
    the in-flight group drains (the A/B baseline the benchmark gates)."""
    srv = PlanServer(CFG, dtype=jnp.float32, capacity=16, pool_max_arenas=1)
    sched = ContinuousBatchingScheduler(srv, max_group_batch=8,
                                        join_mid_decode=False)
    head_req, tail_req = ServeRequest(5, 100, 12), ServeRequest(1, 90, 2)
    arrivals = [(0.0, head_req), (0.001, tail_req)]
    results = sched.run(arrivals)
    assert len(results) == 2
    assert sched.metrics.joins == 0
    tail = next(r for r in results if r["rid"] == tail_req.rid)
    head = next(r for r in results if r["rid"] == head_req.rid)
    # the tail could not start before the head finished
    assert tail["queue_s"] >= head["exec_s"] * 0.5
    assert srv.pool.metrics.arenas_denied > 0


# ---------------------------------------------------------------------------
# planner: pool bytes in estimates + pool-breach recompilation
# ---------------------------------------------------------------------------


def test_pool_arenas_scale_compile_time_cache_statistic():
    srv1 = PlanServer(CFG, dtype=jnp.float32, pool_arenas=1)
    srv4 = PlanServer(CFG, dtype=jnp.float32, pool_arenas=4)
    e1 = srv1.decode_entry(2, 128)
    e4 = srv4.decode_entry(2, 128)
    assert e4.plan.memory.per_device["kv_cache"] == pytest.approx(
        4 * e1.plan.memory.per_device["kv_cache"])


def test_pool_breach_triggers_recompile_and_converges():
    """A pool outgrowing the plan's cache statistic recompiles once — the
    corrected statistic covers the observation, so identical occupancy does
    not re-trigger (SystemML's converge-after-one contract)."""
    srv = PlanServer(CFG, dtype=jnp.float32, capacity=16)
    srv.handle(ServeRequest(2, 100, 1))
    key = srv._key_for(2, 101, "decode")
    entry = srv.cache.get(key)
    kv_est = entry.plan.memory.per_device["kv_cache"]
    stats = RuntimeStats(shape=key.bucket_shape(),
                         cache_pool_bytes=3.0 * kv_est)
    reasons = recompile_reasons(entry.plan, stats, margin=0.25)
    assert reasons and "kv-cache pool" in reasons[0]
    refreshed, reasons = srv.observe(key, stats)
    assert reasons and srv.metrics.recompiles == 1
    assert refreshed.plan.memory.per_device["kv_cache"] >= 3.0 * kv_est
    # converged: the same pool occupancy is covered now
    _, again = srv.observe(key, stats)
    assert not again and srv.metrics.recompiles == 1


def test_observed_stats_carry_pool_bytes():
    """Paged pools report *page-exact* live bytes: an idle arena costs
    nothing, an admitted row costs its committed span pages — far below
    the arena's bucket-shaped capacity (the slack that used to over-trigger
    the recompile predicate)."""
    srv = PlanServer(CFG, dtype=jnp.float32, capacity=16)
    entry = srv.decode_entry(2, 200)              # seq bucket 256, 4 pages
    arena = srv.pool.acquire(entry.key.batch_bucket, entry.key.seq_bucket,
                             force=True)
    stats = srv.observed_stats(
        entry, InputShape("t", 200, 2, "decode"), jnp.ones((2, 1), jnp.int32))
    assert stats.cache_pool_bytes == 0.0          # nothing committed yet
    rows = srv.pool.alloc_rows(arena, 2)
    for r in rows:
        srv.pool.admit_row(arena, r, prompt=30, span=40)
    stats = srv.observed_stats(
        entry, InputShape("t", 200, 2, "decode"), jnp.ones((2, 1), jnp.int32))
    expect = 2 * srv.pool.member_bytes(entry.key.seq_bucket, 1, 40)
    assert stats.cache_pool_bytes == pytest.approx(expect)
    assert 0 < stats.cache_pool_bytes < arena.nbytes
    assert stats.watermark_bytes > stats.cache_pool_bytes  # + params
    srv.pool.release(arena)


def test_observed_stats_row_granular_pool_charges_arena():
    """page_size=0 keeps the PR-3 row-granular accounting: a leased arena
    charges its full bucket-shaped capacity."""
    srv = PlanServer(CFG, dtype=jnp.float32, capacity=16, page_size=0)
    entry = srv.decode_entry(2, 64)
    arena = srv.pool.acquire(entry.key.batch_bucket, entry.key.seq_bucket,
                             force=True)
    stats = srv.observed_stats(
        entry, InputShape("t", 64, 2, "decode"), jnp.ones((2, 1), jnp.int32))
    assert stats.cache_pool_bytes == pytest.approx(arena.nbytes)
    srv.pool.release(arena)


def test_pool_gauge_counts_free_paged_arenas():
    """A free paged arena keeps its whole slot stack on the device though
    the budget charges it nothing: ``pooled_bytes`` shows those bytes while
    the arena waits in the free pool, and admission is unchanged."""
    pool = _pool(page_size=16, max_free=1)
    a = pool.acquire(2, 64)
    held = sum(x.nbytes for x in a.cache.values())
    assert a.nbytes == held > 0
    assert pool.metrics.pooled_bytes == 0
    pool.release(a)
    assert pool.metrics.pooled_bytes == held
    assert pool.total_bytes() == 0            # still page-exact
    b = pool.acquire(2, 64)                   # recycled: leaves the free pool
    assert b is a and pool.metrics.pooled_bytes == 0
    c = pool.acquire(1, 32)
    pool.release(b)
    pool.release(c)                           # max_free=1 evicts b
    assert pool.metrics.arenas_evicted == 1
    assert pool.metrics.pooled_bytes == c.nbytes
    assert "pooled=" in pool_summary(pool)
