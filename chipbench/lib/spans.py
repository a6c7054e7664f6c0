"""What the serving engine's own spans and counters say about one run.

The engine runs each phase of its tick under a ``jax.profiler.TraceAnnotation``
named ``engine.*`` (``engine.tick`` around the whole tick; ``engine.wait``
where the host blocks on the device) and counts its program calls in
``SchedulerMetrics``, where it also records each request's queue wait when
the request is admitted. This module reduces both to four numbers:

- ``idle_in_engine_share``: device-0 idle time under an ``engine.*`` span, in
  percent of the traced window (the rest of the idle time is spent waiting
  for traffic, under ``chipbench.idle`` / ``chipbench.submit`` or no span);
- ``host_ms_per_tick``: host time of a tick outside ``engine.wait``;
- ``queue_wait_p90_ms``: nearest-rank p90 of arrival-to-admission waits of
  the requests admitted in the window and of those still queued at its
  close;
- ``prefill_padding_share``: prefill slots (``batch_bucket x seq_bucket``)
  that held padding, in percent, over the window.

``chipbench/probe.py`` prints them beside a cell's result line. A program
without the spans or counters gives None for the numbers that read them.
"""

from __future__ import annotations

import heapq
from typing import Dict, List, Optional, Sequence, Tuple

from chipbench.lib import trace as TR
from chipbench.lib.readers import pct

COUNTERS = ("ticks", "decode_dispatches", "prefill_dispatches",
            "prefill_tokens", "prefill_slots")


def host_seconds(spans: Sequence[TR.Interval]) -> Dict[str, float]:
    """Summed seconds of each span name."""
    out: Dict[str, float] = {}
    for s, e, n in spans:
        out[n] = out.get(n, 0.0) + (e - s) / 1e9
    return out


def idle_by_span(gaps: Sequence[Tuple[int, int]],
                 spans: Sequence[TR.Interval]) -> Dict[str, float]:
    """Seconds of ``gaps`` (sorted, disjoint) under the innermost span open
    at each instant (the shortest, on ties), or under "no span". One sweep
    over the sorted boundaries: a gap that crosses span boundaries is split
    across the spans, and the values sum to the gaps' length."""
    points = sorted({p for g in gaps for p in g}
                    | {p for s, e, _n in spans for p in (s, e)})
    order = sorted(spans)
    out: Dict[str, float] = {}
    heap: List[Tuple[int, int, str]] = []     # (length, end, name) of open spans
    si = gi = 0
    for p, q in zip(points, points[1:]):
        while si < len(order) and order[si][0] <= p:
            s, e, n = order[si]
            heapq.heappush(heap, (e - s, e, n))
            si += 1
        while heap and heap[0][1] <= p:       # closed spans leave from the top
            heapq.heappop(heap)
        while gi < len(gaps) and gaps[gi][1] <= p:
            gi += 1
        if gi < len(gaps) and gaps[gi][0] <= p:
            name = heap[0][2] if heap else "no span"
            out[name] = out.get(name, 0.0) + (q - p) / 1e9
    return out


def window_spans(trace: TR.Trace, window_s: float, top: int = 10
                 ) -> Tuple[Dict[str, float], Dict[str, float],
                            List[Tuple[float, List[str]]]]:
    """Over the traced window that ``trace.reduce`` found (from the start of
    ``chipbench.window``, for ``window_s``): host seconds by span name,
    device-0 idle seconds by span name, and the ``top`` longest idle gaps,
    each with the spans open at its middle, outermost first."""
    lo = next(s for s, _e, n in trace.host if n == TR.WINDOW)
    hi = lo + round(window_s * 1e9)
    spans = TR._clip([h for h in trace.host if h[2] != TR.WINDOW], lo, hi)
    busy = TR.union([(s, e) for s, e, _n in TR._clip(trace.ops[0], lo, hi)])
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    stacks = []
    for gs, ge in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = (gs + ge) // 2
        open_ = sorted((h for h in spans if h[0] <= mid < h[1]),
                       key=lambda h: h[0] - h[1])
        stacks.append(((ge - gs) / 1e9, [n for _s, _e, n in open_]))
    return host_seconds(spans), idle_by_span(gaps, spans), stacks


def counters(eng) -> Dict[str, int]:
    """The engine's program counters (those this program has)."""
    m = eng.metrics
    return {k: getattr(m, k) for k in COUNTERS if hasattr(m, k)}


def window_program(eng, opened: Dict) -> Dict:
    """What the engine counted between ``opened`` (``{"counters",
    "queued"}`` taken at the window's open) and now, the close. Queue waits
    only where the engine records them at admission."""
    m = eng.metrics
    out = {"open": opened["counters"], "close": counters(eng)}
    if hasattr(m, "observe_admission"):
        now = eng.clock.now()
        out["queue_waits"] = list(m.queue_latency.samples[opened["queued"]:])
        out["queued_waits"] = [now - qr.arrival_s for qr in eng.queue.pending]
    return out


def numbers(window_s: float, host_counts: Dict[str, int],
            host_s: Dict[str, float], idle_s: Dict[str, float],
            program: Dict) -> Dict[str, Optional[float]]:
    ticks = host_counts.get("engine.tick", 0)
    out: Dict[str, Optional[float]] = {
        "idle_in_engine_share": None, "host_ms_per_tick": None,
        "queue_wait_p90_ms": None, "prefill_padding_share": None}
    if ticks and window_s > 0:
        out["idle_in_engine_share"] = 100.0 * sum(
            s for n, s in idle_s.items() if n.startswith("engine.")) / window_s
        out["host_ms_per_tick"] = (host_s["engine.tick"]
                                   - host_s.get("engine.wait", 0.0)) / ticks * 1e3
    if "queue_waits" in program:
        out["queue_wait_p90_ms"] = pct(
            program["queue_waits"] + program["queued_waits"], 90, 1e3)
    o, c = program.get("open", {}), program.get("close", {})
    if "prefill_slots" in o and "prefill_slots" in c:
        slots = c["prefill_slots"] - o["prefill_slots"]
        if slots > 0:
            out["prefill_padding_share"] = 100.0 * (
                1.0 - (c["prefill_tokens"] - o["prefill_tokens"]) / slots)
    return out
