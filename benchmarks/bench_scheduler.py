"""Continuous-batching benchmark: coalesced scheduler throughput vs.
sequential per-request ``PlanServer.handle``, the mid-decode-join
tail-latency gate, and the paged-vs-row-granular residency gate, on the
same mixed-shape streams.

Sequential serving pads every request up to its own power-of-two bucket and
decodes it alone; the scheduler fills a bucket's batch dimension with
compatible pending requests, so the same number of decode-step launches
serves several requests at once. With the row-addressable KV-cache pool,
requests arriving behind a long decode additionally *join* free rows of the
in-flight group mid-decode instead of queueing for an arena of their own.
Block-granular paged arenas charge a byte budget only for the pages a
request's span commits — not the bucket-shaped capacity row-granular
leases pin — so the same ``--pool-max-bytes`` holds more concurrently
resident requests.

Acceptance targets (CI-enforced):

- >= 1.7x request throughput for the coalesced path over sequential;
- >= 1.3x p95 queueing-latency improvement for mid-decode joins over
  admission-only coalescing on a budget-bound pool (one arena);
- >= 1.5x peak concurrently-resident requests for paged arenas over
  row-granular under the same fixed byte budget;
- zero recompiles anywhere — dtype-, pool- and page-aware estimates mean
  no stream ever breaches its compile-time cache statistic.

    PYTHONPATH=src python benchmarks/bench_scheduler.py [--smoke]

Prints ``name,us_per_call,derived`` CSV rows (harness contract), writes the
full result set to ``BENCH_scheduler.json`` (the perf-trajectory artifact
CI uploads), and exits non-zero below any gate or on a spurious recompile.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from dataclasses import replace

import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.models.model import build_model
from repro.runtime.engine_config import EngineConfig
from repro.runtime.kv_cache import KVCachePool
from repro.runtime.scheduler import (ContinuousBatchingScheduler,
                                     simulate_arrivals)
from repro.runtime.serve_loop import ServeRequest

try:
    from benchmarks.bench_meta import scenario_meta
except ImportError:  # run as a script from the benchmarks/ directory
    from bench_meta import scenario_meta


# The coalesced-vs-sequential target was 2.0x when sequential serving
# re-decoded the prompt's first token against a zero cache and allocated a
# fresh cache blob per request. The KV-pool handoff made that *baseline*
# legitimately faster (prefill's token opens the output — one decode step
# fewer — and arenas are recycled), compressing the coalescing margin to
# ~2.0-2.4x observed; the gate sits below that floor with headroom.
TARGET_SPEEDUP = 1.7
TARGET_JOIN_P95 = 1.3
TARGET_RESIDENCY = 1.5
RESULTS_JSON = "BENCH_scheduler.json"


def _stream(smoke: bool):
    """Default mixed-shape stream: single-sequence requests (one user query
    each) over two context buckets. Sequential serving decodes each at a
    batch-1 bucket; the scheduler coalesces 8 of them into one group."""
    mix = [(1, 40), (1, 90), (1, 60), (1, 100), (1, 50), (1, 120),
           (1, 40), (1, 100), (1, 60), (1, 90), (1, 50), (1, 100),
           (1, 40), (1, 120), (1, 60), (1, 90)]
    if smoke:
        return mix, 8, 4
    return mix * 2, 8, 6


def _join_arrivals(smoke: bool):
    """Join scenario: a wide long-decode head occupies the only arena the
    pool budget allows; single-row requests arrive just behind it in the
    *same* span bucket (128). With joins they ride the head group's free
    rows mid-decode; without, they queue until the head drains."""
    head_tokens = 48 if smoke else 64            # span 60+48 -> bucket 128
    head = (0.0, (5, 60, head_tokens))
    tail = [(0.001, (1, 90 + 2 * i, 4)) for i in range(6)]   # spans ≤ 128
    return [head] + tail


def _residency(smoke: bool, arch: str):
    """Paged-vs-row-granular fragmentation scenario: batch-5 requests whose
    80-slot span sits inside a (8, 128) bucket arena, under one fixed byte
    budget. Row-granular leases charge the whole bucket arena (1024 slots)
    per group; 16-slot pages charge 5 rows x 80 slots — so the same budget
    keeps ~2.5x more requests concurrently resident. Returns
    (rows, gain, recompiles, detail)."""
    cfg = get_config(arch)
    n_req = 8 if smoke else 12
    reqs = [ServeRequest(5, 68, 12) for _ in range(n_req)]
    # budget: ~2.2 row-granular arenas' worth of bytes, from the cache spec
    # alone (no PlanServer probe — that would materialize a parameter tree)
    probe = KVCachePool(build_model(cfg, dtype=jnp.float32))
    budget = 2.2 * probe.arena_bytes(8, 128)

    peaks, recompiles, pools = {}, 0, {}
    for name, page in (("row_granular", 0), ("paged", 16)):
        ecfg = EngineConfig(cache_capacity=16, page_size=page,
                            pool_max_bytes=budget)
        srv = ecfg.build_server(cfg)
        sched = ContinuousBatchingScheduler(srv, config=ecfg)
        results = sched.run(simulate_arrivals(reqs))
        assert len(results) == n_req, (name, len(results))
        peaks[name] = sched.metrics.peak_resident
        recompiles += srv.metrics.recompiles
        pools[name] = srv.pool.metrics
    gain = peaks["paged"] / peaks["row_granular"] if peaks["row_granular"] \
        else 0.0
    pm = pools["paged"]
    rows = [
        f"paged_residency,{peaks['paged']},"
        f"row_granular={peaks['row_granular']};x={gain:.1f};"
        f"target={TARGET_RESIDENCY};pool_max_bytes={budget:.0f}",
        f"paged_page_churn,{pm.pages_leased},"
        f"freed={pm.pages_freed};denied={pm.pages_denied};"
        f"peak_pages={pm.peak_pages};"
        f"arenas_denied={pm.arenas_denied}",
    ]
    detail = {"paged_peak_resident": peaks["paged"],
              "row_granular_peak_resident": peaks["row_granular"],
              "residency_gain": gain, "pool_max_bytes": budget,
              "paged_pool": pm.as_dict()}
    return rows, gain, recompiles, detail


def _measure(smoke: bool, arch: str):
    """Returns (rows, speedup, join_gain, recompiles): CSV rows plus the
    numeric gates so CI doesn't re-parse its own formatting. All paths run
    from warm plan caches; each is timed over several trials and the best
    trial is compared (noise floor, not luck)."""
    cfg = get_config(arch)
    ecfg = EngineConfig(cache_capacity=16)
    shapes, new_tokens, trials = _stream(smoke)
    reqs = [ServeRequest(b, c, new_tokens) for b, c in shapes]

    # warm both paths: compile + trace every bucket outside measurement
    srv_seq = EngineConfig(cache_capacity=16, prefill=True).build_server(cfg)
    for b, c in sorted(set(shapes)):
        srv_seq.handle(ServeRequest(b, c, new_tokens))
    srv = ecfg.build_server(cfg)
    ContinuousBatchingScheduler(srv, config=ecfg).run(
        simulate_arrivals(reqs))

    # interleave trials so transient box load penalizes both paths alike;
    # compare best-of-trials (the noise floor, not the luck of one run)
    seq_s, coal_s, sched = None, None, None
    for _ in range(trials):
        dt = _time_trial(lambda: [srv_seq.handle(r) for r in reqs])
        if seq_s is None or dt < seq_s:
            seq_s = dt
        trial = ContinuousBatchingScheduler(srv, config=ecfg)
        dt = _time_trial(lambda: trial.run(simulate_arrivals(reqs)))
        if coal_s is None or dt < coal_s:
            coal_s, sched = dt, trial
    seq_rps = len(reqs) / seq_s
    coal_rps = len(reqs) / coal_s
    speedup = coal_rps / seq_rps if seq_rps else 0.0

    # mid-decode joins vs admission-only on a one-arena pool budget
    jcfg = EngineConfig(cache_capacity=16, pool_max_arenas=1)
    srv_join = jcfg.build_server(cfg)
    arrivals = [(t, ServeRequest(*r)) for t, r in _join_arrivals(smoke)]
    # warm every plan (incl. the batch-1 join prefill bucket) off the clock
    ContinuousBatchingScheduler(srv_join, config=jcfg).run(arrivals)
    p95 = {}
    joins = 0
    for mode in (True, False):
        best = None
        for _ in range(trials):
            trial = ContinuousBatchingScheduler(
                srv_join, config=replace(jcfg, join_mid_decode=mode))
            trial.run(arrivals)
            q95 = trial.metrics.queue_latency.percentile(95)
            if best is None or q95 < best:
                best = q95
                if mode:
                    joins = trial.metrics.joins
        p95[mode] = best
    join_gain = p95[False] / p95[True] if p95[True] else 0.0

    recompiles = (srv.metrics.recompiles + srv_seq.metrics.recompiles
                  + srv_join.metrics.recompiles)
    m = sched.metrics
    rows = [
        f"scheduler_sequential,{seq_s / len(reqs) * 1e6:.0f},"
        f"rps={seq_rps:.2f};recompiles={srv_seq.metrics.recompiles}",
        f"scheduler_coalesced,{coal_s / len(reqs) * 1e6:.0f},"
        f"rps={coal_rps:.2f};groups={m.groups};"
        f"bucket_fill={m.bucket_fill:.2f};recompiles={srv.metrics.recompiles}",
        f"scheduler_speedup,{coal_s / len(reqs) * 1e6:.0f},"
        f"x={speedup:.1f};target={TARGET_SPEEDUP}",
        f"join_p95_queue,{p95[True] * 1e6:.0f},"
        f"admission_only_us={p95[False] * 1e6:.0f};joins={joins};"
        f"x={join_gain:.1f};target={TARGET_JOIN_P95}",
    ]
    return rows, speedup, join_gain, recompiles


def _time_trial(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def run(smoke: bool = False, arch: str = "yi-6b-smoke"):
    """Harness entry point (benchmarks/run.py contract): CSV rows only."""
    rows = _measure(smoke, arch)[0]
    rows += _residency(smoke, arch)[0]
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny stream for CI (seconds, not minutes)")
    ap.add_argument("--arch", default="yi-6b-smoke")
    args = ap.parse_args(argv)

    print("name,us_per_call,derived")
    rows, speedup, join_gain, recompiles = _measure(args.smoke, args.arch)
    res_rows, res_gain, res_recompiles, res_detail = _residency(
        args.smoke, args.arch)
    rows += res_rows
    recompiles += res_recompiles
    for row in rows:
        print(row, flush=True)
    ok = True
    if speedup < TARGET_SPEEDUP:
        print(f"FAIL: coalesced speedup {speedup:.1f}x < "
              f"{TARGET_SPEEDUP}x target", file=sys.stderr)
        ok = False
    if join_gain < TARGET_JOIN_P95:
        print(f"FAIL: mid-decode join p95 queueing gain {join_gain:.2f}x < "
              f"{TARGET_JOIN_P95}x target", file=sys.stderr)
        ok = False
    if res_gain < TARGET_RESIDENCY:
        print(f"FAIL: paged residency gain {res_gain:.2f}x < "
              f"{TARGET_RESIDENCY}x target", file=sys.stderr)
        ok = False
    if recompiles:
        print(f"FAIL: fp32 streams burned {recompiles} recompiles "
              f"(dtype-, pool- and page-aware estimates should need zero)",
              file=sys.stderr)
        ok = False
    with open(RESULTS_JSON, "w") as f:
        json.dump({
            "bench": "scheduler", "smoke": args.smoke, "arch": args.arch,
            "meta": scenario_meta(args.arch),
            "rows": rows, "ok": ok,
            "gates": {
                "coalesced_speedup": {"value": speedup,
                                      "target": TARGET_SPEEDUP},
                "join_p95_gain": {"value": join_gain,
                                  "target": TARGET_JOIN_P95},
                "paged_residency_gain": {"value": res_gain,
                                         "target": TARGET_RESIDENCY},
                "recompiles": {"value": recompiles, "target": 0},
            },
            "residency": res_detail,
        }, f, indent=2)
        f.write("\n")
    print(f"# results -> {RESULTS_JSON}", file=sys.stderr)
    return 0 if ok else 1


if __name__ == "__main__":
    enable_compile_cache()
    sys.exit(main())
