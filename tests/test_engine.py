"""ServingEngine request lifecycle (PR 5): online submission into a live
engine, per-token streaming byte-identical to batch-mode results per family
(attention / SSD / hybrid), cancellation with same-tick pool reclamation
whose freed pages become mid-decode join capacity, EOS / stop-sequence
early exits that are prefixes of the full-length decode, and the
construction-stamped request id shared by handles, results, and metrics."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.model import build_model
from repro.runtime.engine import (ServingEngine, WallClock,
                                  cache_has_length_axis)
from repro.runtime.engine_config import EngineConfig
from repro.runtime.scheduler import (ContinuousBatchingScheduler,
                                     simulate_arrivals)
from repro.runtime.serve_loop import PlanServer, ServeRequest

CFG = get_config("yi-6b-smoke")


# ---------------------------------------------------------------------------
# streaming == batch, per family
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["yi-6b", "mamba2-1.3b", "recurrentgemma-2b"])
def test_streamed_tokens_match_batch_mode(arch):
    """Consuming per-token events yields byte-identical tokens to reading
    the completion records of a batch run — streaming is observation, not
    a different execution path."""
    cfg = get_config(arch + "-smoke")
    srv = PlanServer(cfg, dtype=jnp.float32, capacity=16)
    reqs = [ServeRequest(1, 20, 3), ServeRequest(2, 28, 3),
            ServeRequest(1, 24, 4)]
    batch = ContinuousBatchingScheduler(srv, max_group_batch=8).run(
        simulate_arrivals(reqs))
    batch_toks = {r["rid"]: np.asarray(r["tokens"]) for r in batch}

    # same server (same params, warm plans), fresh engine, event consumers
    eng = ServingEngine(srv)
    again = [ServeRequest(r.batch, r.context, r.new_tokens) for r in reqs]
    handles = [eng.submit(r) for r in again]
    streamed = {h.rid: [] for h in handles}
    for ev in eng.events():
        if ev.token is not None:
            streamed[ev.rid].append(np.asarray(ev.token))
    for orig, h in zip(reqs, handles):
        got = np.concatenate(streamed[h.rid], axis=1)
        np.testing.assert_array_equal(got, batch_toks[orig.rid])
        # the completion record agrees with the event stream
        np.testing.assert_array_equal(got, np.asarray(h.result["tokens"]))
        assert h.result["finish_reason"] == "length"


def test_handle_is_engine_adapter_with_same_tokens():
    """PlanServer.handle (sequential front door) and the engine (batch
    front door) produce identical tokens for the same request shape."""
    srv = PlanServer(CFG, dtype=jnp.float32, capacity=16, prefill=True)
    out = srv.handle(ServeRequest(2, 20, 4))
    assert out["finish_reason"] == "length"
    eng = ServingEngine(srv)
    h = eng.submit(ServeRequest(2, 20, 4))
    eng.drain()
    np.testing.assert_array_equal(np.asarray(out["tokens"]),
                                  np.asarray(h.result["tokens"]))


# ---------------------------------------------------------------------------
# online submission (no pre-sorted trace)
# ---------------------------------------------------------------------------


def test_online_submission_joins_live_engine():
    """Requests submitted while the engine is mid-decode are absorbed into
    in-flight groups — the scenario the run(arrivals) API could not
    express (it demanded the whole trace up front)."""
    srv = PlanServer(CFG, dtype=jnp.float32, capacity=16)
    eng = ServingEngine(srv, clock=WallClock())
    a = eng.submit(ServeRequest(5, 100, 6))    # (8, 128) bucket: 3 free rows
    eng.step()                                 # a's group is now in flight
    b = eng.submit(ServeRequest(1, 90, 2))     # same span bucket (128)
    eng.drain()
    assert a.result is not None and b.result is not None
    assert b.result["joined_at_step"] >= 1
    assert eng.metrics.joins == 1
    # streaming latency accounting ran for both requests
    assert eng.metrics.ttft_latency.count == 2
    assert eng.metrics.itl_latency.count > 0
    assert "ttft" in eng.summary()


def test_stream_yields_incrementally_and_cancels():
    srv = PlanServer(CFG, dtype=jnp.float32, capacity=16)
    eng = ServingEngine(srv)
    h = eng.submit(ServeRequest(1, 40, 16))
    it = h.stream()
    evs = [next(it), next(it), next(it)]
    assert [e.index for e in evs] == [0, 1, 2]
    assert h.result is None                    # still mid-decode
    assert h.tokens().shape[1] >= 3            # partial output visible
    assert h.cancel()
    rest = list(it)
    assert rest and rest[-1].done
    assert rest[-1].finish_reason == "cancelled"
    assert h.state == "cancelled"
    # the partial output is what was streamed
    n = np.asarray(h.result["tokens"]).shape[1]
    assert n == 3 + sum(1 for e in rest if e.token is not None)
    eng.drain()


# ---------------------------------------------------------------------------
# cancellation frees pool capacity the same tick
# ---------------------------------------------------------------------------


def test_cancel_mid_decode_reclaims_pool_and_admits_join():
    srv = PlanServer(CFG, dtype=jnp.float32, capacity=16, pool_max_arenas=1)
    eng = ServingEngine(srv)
    a = eng.submit(ServeRequest(4, 100, 24))
    b = eng.submit(ServeRequest(2, 100, 24))
    for _ in range(3):
        eng.step()                       # one group: a + b, long decode
    c = eng.submit(ServeRequest(4, 90, 3))
    eng.step()
    # c fits neither the group's 2 free rows nor a second arena (pool cap)
    assert c.state == "queued"
    live = srv.pool.live_bytes()
    assert eng.cancel(a)
    assert a.state == "cancelled"
    assert a.result["finish_reason"] == "cancelled"
    # rows, committed pages, and the undrawn span reservation came back
    # the moment cancel() ran — no tick in between
    assert srv.pool.live_bytes() < live
    assert srv.pool.metrics.pages_reclaimed > 0
    assert np.asarray(a.result["tokens"]).shape[1] >= 1   # partial output
    eng.drain()
    # the freed rows admitted c mid-decode into the surviving group
    assert c.result["finish_reason"] == "length"
    assert c.result["joined_at_step"] >= 1
    assert eng.metrics.joins >= 1
    assert eng.metrics.cancelled == 1
    assert b.result["finish_reason"] == "length"


def test_cancel_queued_request_never_runs():
    srv = PlanServer(CFG, dtype=jnp.float32, capacity=16, pool_max_arenas=1)
    eng = ServingEngine(srv)
    a = eng.submit(ServeRequest(4, 100, 6))
    eng.step()
    b = eng.submit(ServeRequest(8, 100, 4))    # 8 rows: can't join or form
    eng.step()
    assert b.state == "queued"
    assert eng.cancel(b)
    assert b.state == "cancelled"
    assert np.asarray(b.result["tokens"]).shape == (8, 0)
    eng.drain()
    assert a.result["finish_reason"] == "length"
    assert eng.metrics.cancelled == 1 and eng.metrics.completed == 1
    assert not eng.cancel(b)                   # already finished


# ---------------------------------------------------------------------------
# stop conditions: eos + stop sequences
# ---------------------------------------------------------------------------


def _full_decode(srv, req):
    rec = ContinuousBatchingScheduler(srv, max_group_batch=8).run(
        simulate_arrivals([req]))[0]
    return np.asarray(rec["tokens"])[0]


def test_eos_early_exit_is_prefix_of_full_decode():
    srv = PlanServer(CFG, dtype=jnp.float32, capacity=16)
    full = _full_decode(srv, ServeRequest(1, 30, 8))
    eos = int(full[2])
    j = int(np.argmax(full == eos))            # first occurrence wins
    eng = ServingEngine(srv)
    h = eng.submit(ServeRequest(1, 30, 8, eos_id=eos))
    eng.drain()
    out = np.asarray(h.result["tokens"])[0]
    assert h.result["finish_reason"] == "eos"
    assert out.tolist() == full[: j + 1].tolist()
    assert eng.metrics.early_exits == 1
    # early exit reclaimed the row's remaining capacity
    assert srv.pool.metrics.pages_reclaimed > 0


def test_stop_sequence_early_exit_is_prefix_of_full_decode():
    srv = PlanServer(CFG, dtype=jnp.float32, capacity=16)
    full = _full_decode(srv, ServeRequest(1, 30, 8))
    stop = (int(full[1]), int(full[2]))
    j = next(i for i in range(len(full))
             if i + 1 >= len(stop)
             and full[i - 1: i + 1].tolist() == list(stop))
    eng = ServingEngine(srv)
    h = eng.submit(ServeRequest(1, 30, 8, stop=(stop,)))
    eng.drain()
    out = np.asarray(h.result["tokens"])[0]
    assert h.result["finish_reason"] == "stop"
    assert out.tolist() == full[: j + 1].tolist()


def test_eos_with_max_tokens_still_bounded():
    """eos that never fires: the request completes at new_tokens with
    reason 'length' (stop conditions never extend a decode)."""
    srv = PlanServer(CFG, dtype=jnp.float32, capacity=16)
    full = _full_decode(srv, ServeRequest(1, 30, 4))
    eos = int(max(full)) + 1                   # not a token it emits
    eng = ServingEngine(srv)
    h = eng.submit(ServeRequest(1, 30, 4, eos_id=eos))
    eng.drain()
    assert h.result["finish_reason"] == "length"
    assert np.asarray(h.result["tokens"])[0].tolist() == full.tolist()


# ---------------------------------------------------------------------------
# stable request ids
# ---------------------------------------------------------------------------


def test_rid_stamped_at_construction():
    r1 = ServeRequest(1, 40, 2)
    r2 = ServeRequest(1, 40, 2)
    assert r2.rid == r1.rid + 1                # monotone, stamped at birth
    srv = PlanServer(CFG, dtype=jnp.float32, capacity=16)
    eng = ServingEngine(srv)
    h = eng.submit(r2)
    eng.drain()
    # handle, queue record, completion record, and request all agree
    assert h.rid == r2.rid == h.result["rid"] == h.qr.rid
    out = srv.handle(r1)
    assert out["rid"] == r1.rid


# ---------------------------------------------------------------------------
# time stamps: first token after prefill, queue wait at admission
# ---------------------------------------------------------------------------


class _StepClock:
    """Engine clock that moves only when the test moves it."""

    def __init__(self):
        self.t = 0.0

    def now(self) -> float:
        return self.t

    def advance_to(self, t: float) -> None:
        self.t = max(self.t, t)


def _slow_prefill(srv, clock, seconds):
    run_prefill = srv.run_prefill

    def slow(entry, tokens=None, lengths=None):
        clock.t += seconds
        return run_prefill(entry, tokens, lengths)

    srv.run_prefill = slow


def test_first_token_is_stamped_after_prefill():
    """The prefill token exists once the prefill program has run: its event
    time and the recorded TTFT include a slow prefill, while the queue wait
    ends at admission, before it."""
    srv = PlanServer(CFG, config=EngineConfig())
    clock = _StepClock()
    eng = ServingEngine(srv, clock=clock)
    _slow_prefill(srv, clock, 5.0)
    h = eng.submit(ServeRequest(1, 20, 3))     # arrives at 0
    clock.t = 1.0
    events = eng.step()
    first = next(e for e in events if e.rid == h.rid and e.index == 0)
    assert first.t == 6.0
    assert eng.metrics.ttft_latency.samples == [6.0]
    assert h.qr.start_s == 1.0
    assert eng.metrics.queue_latency.samples == [1.0]
    eng.drain()
    assert eng.metrics.queue_latency.count == 1   # not again at completion


def test_queue_wait_counts_requests_that_never_complete():
    """A request admitted and then cancelled still waited in the queue; a
    request still queued has no admission yet."""
    srv = PlanServer(CFG, config=EngineConfig(pool_max_arenas=1))
    clock = _StepClock()
    eng = ServingEngine(srv, clock=clock)
    a = eng.submit(ServeRequest(1, 20, 8))
    clock.t = 2.0
    eng.step()
    b = eng.submit(ServeRequest(1, 100, 8))   # another bucket: no arena
    eng.step()
    assert b.state == "queued"
    assert eng.cancel(a)
    assert eng.metrics.completed == 0
    assert eng.metrics.queue_latency.samples == [2.0]
    eng.drain()
    assert eng.metrics.queue_latency.count == 2


# ---------------------------------------------------------------------------
# one decode group for a decode cache with no length axis (SSD)
# ---------------------------------------------------------------------------


SSD = get_config("mamba2-1.3b-smoke")
# (context, new_tokens) spanning three seq buckets: 32, 64 and 128
MIXED = ((20, 4), (40, 5), (100, 3))


@pytest.fixture(scope="module")
def ssd_srv():
    return PlanServer(SSD, config=EngineConfig(dtype="float32",
                                               cache_capacity=32,
                                               prefill=True, sanitize=True))


def _alone(srv, ctx, new):
    """Tokens of one request served by itself through the sequential
    adapter: no joins, its own (batch, span) bucket, a 1-row decode."""
    return np.asarray(srv.handle(ServeRequest(1, ctx, new))["tokens"])


def _record_admissions(eng):
    """Wrap ``_admit_members``: one ``(tick, [seq buckets of the admitted
    requests], prefill seq bucket)`` per admission."""
    log, admit, run_prefill = [], eng._admit_members, eng.server.run_prefill
    seen = {}

    def prefill(entry, tokens=None, lengths=None):
        seen["s"] = entry.key.seq_bucket
        return run_prefill(entry, tokens, lengths)

    def admit_members(group, queued, join_step, now):
        seen.clear()
        out = admit(group, queued, join_step, now)
        log.append((eng.metrics.ticks,
                    [eng.queue.seq_bucket(qr.req) for qr in queued],
                    seen.get("s")))
        return out

    eng._admit_members = admit_members
    eng.server.run_prefill = prefill
    return log


@pytest.mark.parametrize("arch,seq_free", [
    ("yi-6b", False), ("mamba2-1.3b", True), ("recurrentgemma-2b", False),
    ("dbrx-132b", False), ("whisper-medium", False), ("internvl2-2b", False)])
def test_length_axis_read_from_the_cache_spec(arch, seq_free):
    """Only a cache whose shapes ignore the context takes the one-group
    path: attention, sliding-window attention (the hybrid's), moe and
    enc-dec caches all grow with it."""
    model = build_model(get_config(arch + "-smoke"))
    assert cache_has_length_axis(model) is not seq_free


def test_ssd_buckets_share_one_arena_and_one_decode_per_tick(ssd_srv):
    srv = ssd_srv
    created = srv.pool.metrics.arenas_created
    eng = ServingEngine(srv)
    assert eng.seq_free
    hs = [eng.submit(ServeRequest(1, c, n)) for c, n in MIXED]
    assert len({eng.queue.seq_bucket(h.req) for h in hs}) == 3
    arenas = set()
    while not eng.idle:
        eng.step()
        assert len(eng.active) <= 1
        arenas |= {id(g.arena) for g in eng.active}
        for g in eng.active:
            assert (g.arena.batch, g.arena.seq) == (8, srv.policy.min_seq)
    assert len(arenas) == 1 and eng.metrics.groups == 1
    assert srv.pool.metrics.arenas_created - created <= 1
    assert eng.metrics.joins == 2
    # fewer than max_group_batch rows live: one decode program per tick
    assert eng.metrics.decode_dispatches == eng.metrics.ticks
    for h in hs:
        assert h.result["bucket"] == (8, srv.policy.min_seq)
        assert h.result["finish_reason"] == "length"
    # each request's greedy tokens are those it gets served alone
    for h, (c, n) in zip(hs, MIXED):
        np.testing.assert_array_equal(np.asarray(h.result["tokens"]),
                                      _alone(srv, c, n))


def test_ssd_joiner_from_another_bucket_matches_alone(ssd_srv):
    """A joiner of another bucket lands on the row a finished member freed
    and decodes exactly as it does alone: the handoff overwrites the row's
    recurrent state wholesale."""
    srv = ssd_srv
    eng = ServingEngine(srv)
    a = eng.submit(ServeRequest(1, 20, 10))
    b = eng.submit(ServeRequest(1, 24, 2))      # done after one decode step
    eng.step()
    b_rows = eng._member_of(b.rid).rows
    eng.step()
    assert b.result is not None
    c = eng.submit(ServeRequest(1, 100, 4))     # bucket 128, not 32
    eng.step()
    member = eng._member_of(c.rid)
    assert member.rows == b_rows and member.join_step >= 1
    assert eng.queue.seq_bucket(c.req) != eng.queue.seq_bucket(a.req)
    eng.drain()
    assert eng.metrics.groups == 1
    np.testing.assert_array_equal(np.asarray(c.result["tokens"]),
                                  _alone(srv, 100, 4))
    np.testing.assert_array_equal(np.asarray(a.result["tokens"]),
                                  _alone(srv, 20, 10))


def test_ssd_join_prefills_are_bucket_pure(ssd_srv):
    """Joiners of several buckets pending in one tick: the group admits one
    bucket per tick, in one prefill keyed on that bucket."""
    srv = ssd_srv
    eng = ServingEngine(srv)
    log = _record_admissions(eng)
    eng.submit(ServeRequest(1, 20, 12))
    eng.step()
    for c in (40, 100, 44, 110, 20):            # buckets 64, 128, 64, 128, 32
        eng.submit(ServeRequest(1, c, 6))
    eng.drain()
    assert eng.metrics.groups == 1
    ticks = [t for t, _sbs, _s in log]
    assert len(ticks) == len(set(ticks))        # one admission per tick
    for _t, sbs, s in log:
        assert len(set(sbs)) == 1 and sbs[0] == s
    # hol: the oldest pending request picks the bucket, riders of its
    # bucket come along
    assert [sbs for _t, sbs, _s in log] == [[32], [64, 64], [128, 128], [32]]


def test_ssd_second_group_forms_only_when_the_first_is_full(ssd_srv):
    srv = ssd_srv
    eng = ServingEngine(srv)
    hs = [eng.submit(ServeRequest(1, c, 8)) for c in (20, 40, 100) * 3 + (40,)]
    while len(eng.active) < 2 and not eng.idle:
        eng.step()
    first, second = eng.active
    assert first.arena.rows_free == 0
    assert (second.arena.batch, second.arena.seq) == (8, srv.policy.min_seq)
    eng.drain()
    assert all(h.result["finish_reason"] == "length" for h in hs)
    assert eng.metrics.groups == 2


@pytest.mark.parametrize("arch", ["yi-6b", "recurrentgemma-2b"])
def test_attention_groups_stay_keyed_by_seq_bucket(arch):
    srv = PlanServer(get_config(arch + "-smoke"),
                     config=EngineConfig(dtype="float32", cache_capacity=32,
                                         sanitize=True))
    eng = ServingEngine(srv)
    assert not eng.seq_free
    log = _record_admissions(eng)
    hs = [eng.submit(ServeRequest(1, c, n)) for c, n in MIXED]
    eng.drain()
    buckets = [eng.queue.seq_bucket(h.req) for h in hs]
    assert len(set(buckets)) == 3
    # one group per bucket, each decoding in an arena of its own bucket
    assert eng.metrics.groups == 3 and eng.metrics.joins == 0
    assert [h.result["bucket"] for h in hs] == [(1, s) for s in buckets]
    assert [sbs for _t, sbs, _s in log] == [[s] for s in buckets]


@pytest.mark.parametrize("arch", ["yi-6b", "mamba2-1.3b"])
def test_decode_rows_counts_live_rows_per_decode(arch):
    srv = PlanServer(get_config(arch + "-smoke"),
                     config=EngineConfig(dtype="float32", cache_capacity=32))
    eng = ServingEngine(srv)
    live, tick = [], eng._phase_tick

    def counting_tick(group):
        live.append(sum(m.req.batch for m in group.members if not m.done))
        return tick(group)

    eng._phase_tick = counting_tick
    eng.submit(ServeRequest(2, 20, 6))
    eng.step()
    eng.submit(ServeRequest(1, 24, 3))
    eng.submit(ServeRequest(1, 100, 4))
    eng.drain()
    m = eng.metrics
    assert m.decode_dispatches == len(live)
    assert m.decode_rows == sum(live)
    assert m.rows_per_decode == pytest.approx(np.mean(live))
    assert f"rows/decode={np.mean(live):.2f}" in eng.summary()
