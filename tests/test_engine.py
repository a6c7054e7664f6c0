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
from repro.runtime.engine import ServingEngine, WallClock
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
