#!/usr/bin/env python3
"""Run one benchmark cell once, as ``chipbench/run.py`` does, and print the
serving engine's own span and counter numbers after its result line.

    python3 chipbench/probe.py --workload mamba2-1.3b.chat-burst --seed 7 --seconds 50 --trace 1

The run is run.py's. The probe wraps two of the harness's calls from
outside: ``runner.drive``, to take the engine's counters at the window's
open and close, and ``trace.reduce``, to split the traced window's host
and device-idle time by span (``lib/spans.py``). Its last line of standard
output is one JSON object: the four numbers of ``lib/spans.py``, the
client's ``ttft_p90_ms`` and ``itl_p99_ms`` (which a traced result line
leaves out), idle and host seconds by span name, span counts, the longest
idle gaps with the spans open in each, and the counters at open and close.
Numbers that read the trace are None with ``--trace 0``.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path
from typing import Dict

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import run as RUN  # noqa: E402  (process start: run.T_START)
from chipbench.lib import runner as R  # noqa: E402
from chipbench.lib import spans as S  # noqa: E402
from chipbench.lib import trace as TR  # noqa: E402
from chipbench.lib.readers import pct  # noqa: E402


def wrap_drive(drive, got: Dict):
    """``runner.drive`` that also takes the engine's counters at the
    window's open (``got["opened"]``) and close (``got["program"]``)."""
    def counted(jax, eng, rec, sched, seconds, seed, vocab, watch=(),
                on_open=None):
        def opened():
            got["opened"] = {"counters": S.counters(eng),
                             "queued": len(eng.metrics.queue_latency.samples)}
            if on_open is not None:
                on_open()
        served, measured = drive(jax, eng, rec, sched, seconds, seed, vocab,
                                 watch, opened)
        got["program"] = S.window_program(eng, got["opened"])
        seen = R.client_numbers(served, measured)
        got["client"] = {"ttft_p90_ms": pct(seen["ttft"], 90, 1e3),
                         "itl_p99_ms": pct(seen["gaps"], 99, 1e3)}
        return served, measured
    return counted


def wrap_reduce(reduce, got: Dict):
    """``trace.reduce`` that also splits the traced window's host and idle
    time by span."""
    def reduced(trace, kernels, top=10, dispatched=((), 0)):
        red = reduce(trace, kernels, top, dispatched)
        if red is not None:
            got["host_s"], got["idle_s"], got["gap_spans"] = S.window_spans(
                trace, red.window_s)
            got["window_s"], got["host_counts"] = red.window_s, red.host_counts
        return red
    return reduced


def report(got: Dict) -> Dict:
    program = got.get("program", {})
    return {
        "spans": S.numbers(got.get("window_s", 0.0), got.get("host_counts", {}),
                           got.get("host_s", {}), got.get("idle_s", {}),
                           program),
        "client": got.get("client"),
        "window_s": got.get("window_s"),
        "idle_s": got.get("idle_s"),
        "host_s": got.get("host_s"),
        "host_counts": got.get("host_counts"),
        "gap_spans": got.get("gap_spans"),
        "counters": {"open": program.get("open"), "close": program.get("close")},
        "admitted_in_window": len(program.get("queue_waits", [])),
        "queued_at_close": len(program.get("queued_waits", [])),
    }


def main(argv=None) -> int:
    got: Dict = {}
    R.drive = wrap_drive(R.drive, got)
    TR.reduce = wrap_reduce(TR.reduce, got)
    rc = RUN.main(argv)
    print(json.dumps(report(got)), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
