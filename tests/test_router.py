"""EngineRouter + EngineConfig/EngineClient (PR 6): one config surface and
one client protocol over N replicas — replicas=1 is the bare engine with
identical tokens; placement is deterministic for identical traces;
no replica idles while another holds queued work (work stealing); a
drained replica's in-flight requests finish on the survivors with token
streams byte-identical to an undisturbed run; and the legacy per-class
kwargs still work but warn."""

import argparse

import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.runtime.engine import (EngineClient, RequestQueue, ServingEngine,
                                  WallClock)
from repro.runtime.engine_config import EngineConfig
from repro.runtime.router import EngineRouter
from repro.runtime.scheduler import (ContinuousBatchingScheduler,
                                     simulate_arrivals)
from repro.runtime.serve_loop import PlanServer, ServeRequest

try:
    import hypothesis.strategies as st
    from hypothesis import given, settings
except ImportError:
    from repro.testing.hypothesis_compat import given, settings, st

CFG = get_config("yi-6b-smoke")
ECFG = EngineConfig(replicas=2)


@pytest.fixture(scope="module")
def fleet_servers():
    """Two replica servers shared by the decode-heavy tests (plan caches
    warm up across tests; params are seed-identical by construction)."""
    return [ECFG.build_server(CFG) for _ in range(2)]


# ---------------------------------------------------------------------------
# EngineConfig: one surface, legacy kwargs as deprecated shims
# ---------------------------------------------------------------------------


def test_legacy_kwargs_fold_into_config_and_warn():
    # conftest's autouse fixture resets the once-per-process registry
    with pytest.warns(DeprecationWarning, match="PlanServer"):
        srv = PlanServer(CFG, dtype=jnp.float32, capacity=4)
    assert srv.config.cache_capacity == 4
    assert srv.config.dtype == "float32"
    with pytest.warns(DeprecationWarning, match="ServingEngine"):
        eng = ServingEngine(srv, max_group_batch=4)
    assert eng.config.max_group_batch == 4
    # the config the server carries seeds the engine's unless overridden
    assert eng.config.cache_capacity == 4


def test_config_from_args_maps_argparse_spellings():
    ns = argparse.Namespace(dtype="bfloat16", no_cache=True, replicas=3,
                            placement="load", bucket_select="arrival",
                            max_group_batch=4, seed=7)
    cfg = EngineConfig.from_args(ns)
    assert cfg.dtype == "bfloat16"
    assert cfg.enable_cache is False
    assert cfg.replicas == 3 and cfg.placement == "load"
    assert cfg.bucket_select == "arrival" and cfg.max_group_batch == 4
    assert cfg.seed == 7
    # partial namespaces keep defaults
    assert EngineConfig.from_args(argparse.Namespace()).replicas == 1


def test_config_validates_choices():
    with pytest.raises(ValueError):
        EngineConfig(dtype="float16")
    with pytest.raises(ValueError):
        EngineConfig(placement="random")
    with pytest.raises(ValueError):
        EngineConfig(bucket_select="lifo")
    with pytest.raises(ValueError):
        EngineConfig(replicas=0)


# ---------------------------------------------------------------------------
# EngineClient: one protocol, engine and router both satisfy it
# ---------------------------------------------------------------------------


def test_engine_client_protocol_both_implementations(fleet_servers):
    eng = ServingEngine(fleet_servers[0], config=ECFG)
    router = EngineRouter(fleet_servers, config=ECFG)
    assert isinstance(eng, EngineClient)
    assert isinstance(router, EngineClient)
    # build_client is the topology switch: 1 -> bare engine, N -> router
    assert isinstance(EngineConfig().build_client(
        CFG, servers=[fleet_servers[0]]), ServingEngine)
    assert isinstance(ECFG.build_client(CFG, servers=fleet_servers),
                      EngineRouter)


def test_replicas_one_is_the_bare_engine_with_identical_tokens():
    """--replicas 1 through build_client must be indistinguishable from
    constructing the engine directly: same type, same tokens."""
    cfg = EngineConfig()
    client = cfg.build_client(CFG)
    assert isinstance(client, ServingEngine)
    reqs = [ServeRequest(1, 20, 3), ServeRequest(2, 28, 3)]
    via_client = {r["rid"] - reqs[0].rid: np.asarray(r["tokens"])
                  for r in client.run(simulate_arrivals(reqs))}
    eng = cfg.build_engine(cfg.build_server(CFG))
    again = [ServeRequest(r.batch, r.context, r.new_tokens) for r in reqs]
    direct = {r["rid"] - again[0].rid: np.asarray(r["tokens"])
              for r in eng.run(simulate_arrivals(again))}
    assert via_client.keys() == direct.keys()
    for k in via_client:
        np.testing.assert_array_equal(via_client[k], direct[k])


# ---------------------------------------------------------------------------
# router lifecycle: completion, balance, summary
# ---------------------------------------------------------------------------


def test_router_completes_all_and_uses_both_replicas(fleet_servers):
    router = EngineRouter(fleet_servers, config=ECFG)
    reqs = [ServeRequest(4, 48, 4) for _ in range(8)]
    recs = router.run(simulate_arrivals(reqs))
    assert len(recs) == len(reqs)
    assert {r["rid"] for r in recs} == {r.rid for r in reqs}
    per = [r.engine.metrics.admitted for r in router.replicas]
    assert all(n > 0 for n in per), per
    assert router.metrics.completed >= len(reqs)
    s = router.summary()
    assert "replica[0]" in s and "replica[1]" in s and "fleet:" in s


def test_router_stream_and_cancel(fleet_servers):
    router = EngineRouter(fleet_servers, config=ECFG)
    keep = router.submit(ServeRequest(1, 40, 4))
    victim = router.submit(ServeRequest(1, 40, 12))
    seen = 0
    for ev in victim.stream():
        if ev.token is not None:
            seen += 1
            if seen == 2:
                assert victim.cancel()
        if ev.done:
            assert ev.finish_reason == "cancelled"
    router.drain()
    assert keep.done and keep.result["finish_reason"] == "length"
    assert victim.result["tokens"].shape[1] == 2


# ---------------------------------------------------------------------------
# placement: deterministic for identical traces (property)
# ---------------------------------------------------------------------------


@settings(max_examples=6, deadline=None)
@given(st.lists(st.tuples(st.sampled_from([1, 2, 4]),
                          st.sampled_from([40, 52, 100, 112])),
                min_size=2, max_size=6))
def test_placement_determinism_property(shapes):
    """Identical request sequences into identically-built fleets place
    identically: the affinity score reads only discrete replica state,
    never the wall clock."""
    decisions = []
    for _ in range(2):
        router = EngineRouter([ECFG.build_server(CFG) for _ in range(2)],
                              config=ECFG)
        for b, c in shapes:
            router.submit(ServeRequest(b, c, 4), arrival_s=0.0)
        decisions.append([(d.replica, d.reason) for d in router.decisions])
    assert decisions[0] == decisions[1]


# ---------------------------------------------------------------------------
# starvation-freedom: no replica idles while another holds queued work
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def warm_fleet():
    """One two-replica fleet shared across the property's examples, so the
    plan caches fill once instead of once per example."""
    return EngineRouter([ECFG.build_server(CFG) for _ in range(2)],
                        config=ECFG)


@settings(max_examples=5, deadline=None)
@given(st.lists(st.sampled_from([(1, 40, 4), (1, 100, 4), (2, 44, 4)]),
                min_size=3, max_size=7))
def test_starvation_freedom_property(warm_fleet, shapes):
    """At every tick boundary (after the tick's rebalance), no replica
    sits idle while another replica still holds queued work — placement
    prefers idle replicas and work stealing migrates leftover backlog."""
    router = warm_fleet
    for b, c, n in shapes:
        router.submit(ServeRequest(b, c, n))
    while not router.idle:
        router.step()
        router._rebalance()  # what the next tick would apply first
        for r in router.replicas:
            queued_elsewhere = any(len(d.engine.queue)
                                   for d in router.replicas if d is not r)
            assert not (r.engine.idle and queued_elsewhere), (
                f"replica {r.idx} idle while another replica has "
                f"queued work")
    assert not router.handles


# ---------------------------------------------------------------------------
# failover: drain moves live work, zero loss, byte-identical streams
# ---------------------------------------------------------------------------


def test_drain_replica_failover_zero_loss_token_equality():
    shapes = [(1, 40, 8), (1, 44, 8), (1, 52, 8),
              (1, 40, 8), (1, 56, 8), (1, 48, 8)]

    # undisturbed reference decode per shape: replicas share seed-derived
    # params and greedy decode is group-composition-invariant, so one
    # clean run is ground truth for any replica
    ref_srv = ECFG.build_server(CFG)
    reqs_ref = [ServeRequest(*s) for s in shapes]
    ref = {}
    for rec in ContinuousBatchingScheduler(ref_srv).run(
            simulate_arrivals(reqs_ref)):
        ref[rec["rid"]] = np.asarray(rec["tokens"])
    by_shape = {}
    for r, s in zip(reqs_ref, shapes):
        by_shape.setdefault(s, ref[r.rid])

    router = EngineRouter([ECFG.build_server(CFG) for _ in range(2)],
                          config=ECFG)
    reqs = [ServeRequest(*s) for s in shapes]
    streamed = {}
    fired = {"done": False}

    def on_event(ev):
        if (not fired["done"] and ev.token is not None and ev.index >= 2
                and any(h.replica is not None and h.replica.idx == 1
                        for h in router.handles.values())):
            moved = router.drain_replica(1)
            assert moved, "drain found no live work to move"
            fired["done"] = True
        if ev.token is not None:
            streamed.setdefault(ev.rid, []).append(np.asarray(ev.token))

    res = router.run(simulate_arrivals(reqs, rate_per_s=200, seed=3),
                     on_event=on_event)
    assert fired["done"], "drain trigger never fired"
    assert len(res) == len(reqs)                      # zero loss
    assert router.router_metrics.resubmitted > 0
    for r, s in zip(reqs, shapes):
        toks = np.concatenate(streamed[r.rid], axis=1)
        # gapless, byte-identical stream despite the mid-decode move
        np.testing.assert_array_equal(toks, by_shape[s])
        rec = next(x for x in res if x["rid"] == r.rid)
        np.testing.assert_array_equal(toks, np.asarray(rec["tokens"]))
    # the drained replica took no further placements
    assert all(d.replica != 1 for d in router.decisions
               if d.t > 0 and d.reason == "failover")


def test_cannot_drain_last_replica_and_restore_rejoins(fleet_servers):
    router = EngineRouter(fleet_servers, config=ECFG)
    router.drain_replica(1)
    with pytest.raises(ValueError):
        router.drain_replica(0)
    assert router.router_metrics.drained == 1
    router.restore_replica(1)
    assert router.router_metrics.drained == 0
    assert not router.replicas[1].draining


# ---------------------------------------------------------------------------
# arrival-aware bucket selection (RequestQueue select="arrival")
# ---------------------------------------------------------------------------


def test_arrival_select_prefers_most_coalescable_bucket():
    q = RequestQueue(select="arrival", max_group_batch=8)
    head = ServeRequest(1, 50, 8)          # span 58  -> bucket 64
    q.admit(head)
    wide = [ServeRequest(1, 100, 8) for _ in range(3)]   # bucket 128
    for r in wide:
        q.admit(r)
    g1 = q.next_group()
    assert {qr.rid for qr in g1} == {r.rid for r in wide}
    g2 = q.next_group()                    # deferred head forms next
    assert [qr.rid for qr in g2] == [head.rid]

    # strict head-of-line forms the oldest request's bucket first
    q_hol = RequestQueue(select="hol", max_group_batch=8)
    q_hol.admit(ServeRequest(1, 50, 8))
    for _ in range(3):
        q_hol.admit(ServeRequest(1, 100, 8))
    assert len(q_hol.next_group()) == 1    # the lone bucket-64 head


def test_arrival_select_bounded_deferral_forces_head():
    q = RequestQueue(select="arrival", max_group_batch=8, max_defer=3)
    head = ServeRequest(1, 50, 8)          # bucket 64: a one-row minority
    q.admit(head)
    served_head_after = None
    for i in range(10):
        q.admit(ServeRequest(1, 100, 8))   # bucket 128 keeps arriving
        q.admit(ServeRequest(1, 100, 8))
        g = q.next_group()
        if head.rid in {qr.rid for qr in g}:
            served_head_after = i
            break
    # the head bucket is passed over at most max_defer times
    assert served_head_after is not None and served_head_after <= 3
