"""Trace reduction: a hand-made trace with known answers, and a small trace
recorded on a v5e (the engine at smoke widths, five ticks: a prefill with
the flash kernel and decode steps with the paged kernel)."""

import json
from pathlib import Path

import pytest

from chipbench.lib import trace as TR

FIXTURE = Path(__file__).parent / "fixtures" / "trace_small.json"
MS = 1_000_000


def hand_made():
    ops = [
        (0 * MS, 2 * MS, "%fusion.1 = f32[] fusion()"),
        (1 * MS, 3 * MS, "%flash_attention.4 = bf16[] custom-call()"),   # overlaps
        (6 * MS, 7 * MS, "%paged_decode_attention.2 = bf16[] custom-call()"),
        (7 * MS, 8 * MS, "%while.3 = (f32[]) while()"),
        (12 * MS, 13 * MS, "%copy.1 = f32[] copy()"),                     # outside
    ]
    modules = [(0, 3 * MS, "jit_prefill(1)"), (6 * MS, 8 * MS, "jit_decode_step(2)"),
               (12 * MS, 13 * MS, "jit_decode_step(2)")]
    host = [(0, 10 * MS, TR.WINDOW), (0, 4 * MS, "engine.step"),
            (4 * MS, 9 * MS, "engine.step"), (9 * MS, 10 * MS, "chipbench.submit")]
    return TR.Trace([ops], [modules], host)


def test_hand_made_trace():
    r = TR.reduce(hand_made(), {"flash_prefill": ("flash_attention",),
                                "paged_decode": ("paged_decode_attention",)})
    assert r.window_s == pytest.approx(0.010)
    # union of [0,3] and [6,8] ms inside the window
    assert r.busy_s == pytest.approx(0.005)
    assert r.kernel_s == {"flash_prefill": pytest.approx(0.002),
                          "paged_decode": pytest.approx(0.001)}
    assert [(m, round(s, 6)) for m, s, _ in r.programs] == [
        ("jit_prefill", 0.003), ("jit_decode_step", 0.002)]
    assert r.programs[1][2] == {"paged_decode": pytest.approx(0.001)}
    assert r.module_count("jit_decode_step") == 1
    assert r.host_counts == {"engine.step": 2, "chipbench.submit": 1}
    # gaps: 3-6 ms (inside the first step at its middle, 4.5 ms: second
    # step), 8-10 ms (middle 9 ms: the submit span)
    assert r.idle_gaps == [("engine.step", pytest.approx(0.003)),
                           ("chipbench.submit", pytest.approx(0.002))]
    # the enclosing while op is left out of the op totals
    assert dict(r.top_ops) == {"jit_prefill/flash_attention": pytest.approx(0.002),
                               "jit_prefill/fusion": pytest.approx(0.002),
                               "jit_decode_step/paged_decode_attention": pytest.approx(0.001)}


def test_a_trace_that_ends_early_cuts_the_window():
    """The host dispatched four step programs in a 20 ms window; the trace
    holds three, the last ending at 13 ms, so the traced window ends there."""
    t = hand_made()
    t.host[0] = (0, 20 * MS, TR.WINDOW)
    stems = ("jit_prefill", "jit_decode_step")
    r = TR.reduce(t, {}, dispatched=(stems, 4))
    assert r.window_s == pytest.approx(0.013)
    assert r.busy_s == pytest.approx(0.006)
    full = TR.reduce(t, {}, dispatched=(stems, 3))
    assert full.window_s == pytest.approx(0.020)
    assert full.busy_s == pytest.approx(0.006)


def test_no_window_no_reduction():
    t = hand_made()
    t.host = [h for h in t.host if h[2] != TR.WINDOW]
    assert TR.reduce(t, {}) is None


def test_recorded_trace():
    t = TR.from_dict(json.loads(FIXTURE.read_text()))
    r = TR.reduce(t, {"flash_prefill": ("flash_attention",),
                      "paged_decode": ("paged_decode_attention",)})
    assert 0 < r.busy_s < r.window_s
    # every kernel second lies inside a program of the right kind
    inside = {}
    for stem, _s, ks in r.programs:
        for k, v in ks.items():
            inside[(stem, k)] = inside.get((stem, k), 0.0) + v
    assert set(inside) == {("jit_prefill", "flash_prefill"),
                           ("jit_decode_step", "paged_decode")}
    assert sum(inside.values()) == pytest.approx(sum(r.kernel_s.values()))
    assert r.host_counts["engine.step"] == 5
    assert r.module_count("jit_prefill") == 1
    assert r.module_count("jit_decode_step") == 9
    assert len(r.idle_gaps) == 10 and len(r.top_ops) == 10
    assert all(a[1] >= b[1] for a, b in zip(r.idle_gaps, r.idle_gaps[1:]))


def test_op_and_module_stems():
    assert TR.op_stem("%fusion.143 = bf16[2] fusion(x)") == "fusion"
    assert TR.op_stem("%ssd_scan = f32[] custom-call()") == "ssd_scan"
    assert TR.module_stem("jit_decode_step(1627003917074399440)") == "jit_decode_step"
    assert TR.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
