"""The engine's spans and counters reduced to numbers (``lib/spans.py``): a
hand-made trace with known answers, and the counters of a smoke-width
engine on the CPU."""

import pytest

from chipbench.lib import spans as S
from chipbench.lib import trace as TR

MS = 1_000_000


def hand_made():
    """Device busy 0-3 and 6-8 ms of a 10 ms window. Host: the benchmark's
    tick span around the engine's tick, which waits 2-4 ms and emits 4-5
    ms; the benchmark idles 8.5-10 ms."""
    ops = [(0, 3 * MS, "%fusion.1 = f32[] fusion()"),
           (6 * MS, 8 * MS, "%fusion.2 = f32[] fusion()")]
    modules = [(0, 3 * MS, "jit_prefill(1)"), (6 * MS, 8 * MS, "jit_decode_step(2)")]
    host = [(0, 10 * MS, TR.WINDOW), (0, 7 * MS, "engine.step"),
            (1 * MS, 7 * MS, "engine.tick"), (2 * MS, 4 * MS, "engine.wait"),
            (4 * MS, 5 * MS, "engine.emit"), (8 * MS + MS // 2, 10 * MS, "chipbench.idle")]
    return TR.Trace([ops], [modules], host)


def test_host_seconds_by_name():
    t = hand_made()
    assert S.window_spans(t, 0.010)[0] == {
        "engine.step": pytest.approx(0.007), "engine.tick": pytest.approx(0.006),
        "engine.wait": pytest.approx(0.002), "engine.emit": pytest.approx(0.001),
        "chipbench.idle": pytest.approx(0.0015)}


def test_idle_split_across_the_spans_a_gap_crosses():
    # the 3-6 ms gap: 3-4 under wait, 4-5 under emit, 5-6 under the tick;
    # the 8-10 ms gap: 8-8.5 under no span, the rest under chipbench.idle
    _host, idle, _gaps = S.window_spans(hand_made(), 0.010)
    assert idle == {"engine.wait": pytest.approx(0.001),
                    "engine.emit": pytest.approx(0.001),
                    "engine.tick": pytest.approx(0.001),
                    "no span": pytest.approx(0.0005),
                    "chipbench.idle": pytest.approx(0.0015)}


def test_idle_sums_to_window_minus_busy():
    t = hand_made()
    red = TR.reduce(t, {})
    _host, idle, _gaps = S.window_spans(t, red.window_s)
    assert sum(idle.values()) == pytest.approx(red.window_s - red.busy_s)
    # a traced window cut short at 7 ms
    _host, idle, _gaps = S.window_spans(t, 0.007)
    assert sum(idle.values()) == pytest.approx(0.007 - 0.004)


def test_longest_gaps_with_the_spans_open_in_them():
    _host, _idle, gaps = S.window_spans(hand_made(), 0.010)
    assert gaps == [(pytest.approx(0.003), ["engine.step", "engine.tick", "engine.emit"]),
                    (pytest.approx(0.002), ["chipbench.idle"])]


def test_idle_goes_to_the_shortest_of_equal_starts():
    gaps = [(0, 4)]
    spans = [(0, 4, "outer"), (0, 2, "inner")]
    assert S.idle_by_span(gaps, spans) == {"inner": pytest.approx(2e-9),
                                          "outer": pytest.approx(2e-9)}


def test_numbers_from_a_fabricated_run():
    t = hand_made()
    red = TR.reduce(t, {})
    host_s, idle_s, _gaps = S.window_spans(t, red.window_s)
    program = {"open": {"prefill_tokens": 100, "prefill_slots": 256},
               "close": {"prefill_tokens": 260, "prefill_slots": 768},
               "queue_waits": [0.01 * i for i in range(1, 10)],
               "queued_waits": [0.5]}
    got = S.numbers(red.window_s, red.host_counts, host_s, idle_s, program)
    # idle under engine.*: wait, emit and tick, 3 ms of the 10
    assert got["idle_in_engine_share"] == pytest.approx(30.0)
    # one tick of 6 ms, 2 of them waiting
    assert got["host_ms_per_tick"] == pytest.approx(4.0)
    # ten waits: the ninth smallest is the nearest-rank p90
    assert got["queue_wait_p90_ms"] == pytest.approx(90.0)
    assert got["prefill_padding_share"] == pytest.approx(100 * (1 - 160 / 512))


def test_numbers_of_a_program_without_spans_or_counters():
    got = S.numbers(10.0, {"engine.step": 4}, {"engine.step": 1.0},
                    {"engine.step": 0.5}, {"open": {}, "close": {}})
    assert got == {"idle_in_engine_share": None, "host_ms_per_tick": None,
                   "queue_wait_p90_ms": None, "prefill_padding_share": None}


def test_engine_counters_over_a_window():
    from repro.configs import get_config
    from repro.runtime.engine import ServingEngine
    from repro.runtime.engine_config import EngineConfig
    from repro.runtime.serve_loop import PlanServer, ServeRequest

    srv = PlanServer(get_config("yi-6b-smoke"),
                     config=EngineConfig(pool_max_arenas=1))
    eng = ServingEngine(srv)
    eng.submit(ServeRequest(1, 20, 2))
    eng.drain()
    opened = {"counters": S.counters(eng),
              "queued": len(eng.metrics.queue_latency.samples)}
    assert opened["counters"]["ticks"] >= 1 and opened["queued"] == 1
    eng.submit(ServeRequest(1, 20, 4))
    eng.step()
    eng.submit(ServeRequest(1, 100, 4))      # another bucket: no arena yet
    eng.step()
    got = S.window_program(eng, opened)
    assert got["close"]["ticks"] - got["open"]["ticks"] == 2
    assert got["close"]["prefill_tokens"] - got["open"]["prefill_tokens"] == 20
    assert len(got["queue_waits"]) == 1 and len(got["queued_waits"]) == 1
    assert got["queued_waits"][0] > 0
    eng.drain()


def test_probe_reads_the_counters_of_a_smoke_run(monkeypatch):
    """The probe's wrappers around a whole run of the cell at smoke widths
    on the CPU (untraced: the trace numbers stay None)."""
    import jax

    from chipbench import probe as P
    from chipbench.lib import runner as R
    from chipbench.tests import smoke

    got = {}
    monkeypatch.setattr(R, "drive", P.wrap_drive(R.drive, got))
    monkeypatch.setattr(TR, "reduce", P.wrap_reduce(TR.reduce, got))
    result, _lines = smoke.run(jax, "mamba2-1.3b.chat-burst", seconds=1.5)
    rep = P.report(got)
    assert rep["counters"]["close"]["ticks"] > rep["counters"]["open"]["ticks"]
    assert rep["admitted_in_window"] >= 1
    nums = rep["spans"]
    assert 0 <= nums["prefill_padding_share"] < 100
    assert nums["queue_wait_p90_ms"] >= 0
    assert nums["idle_in_engine_share"] is None and nums["host_ms_per_tick"] is None
    assert rep["client"]["ttft_p90_ms"] > 0
