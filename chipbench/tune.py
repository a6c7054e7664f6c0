#!/usr/bin/env python3
"""Measurements that set a cell's fixed numbers, made once per benchmark
change and never by the benchmark's own runs. One process sets the cell
up once and then makes many short windows.

    # the knee: steady Poisson with the cell's lengths at several rates; a
    # rate is sustained while no request is left waiting for admission when
    # the window closes
    python3 chipbench/tune.py --workload mamba2-1.3b.chat-burst --sweep 2,3,4 --seconds 50
    # the check's readings: the program's numbers on many seeds; on the
    # first few, the lower-precision control's (and whether the same limits
    # judge it correct) and a planted wrong token's gap
    python3 chipbench/tune.py --workload mamba2-1.3b.chat-burst --calibrate 7,8,9 \\
        --control-seeds 3 --seconds 20

Each window prints one JSON line.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--sweep", default="")
    ap.add_argument("--calibrate", default="")
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from chipbench.lib import cell as CELL
    from chipbench.lib import check as C
    from chipbench.lib import runner as R
    from chipbench.lib import traffic as T
    from chipbench.lib.readers import pct
    import jax
    from chipbench.run import require_chip

    bench = CELL.Bench(ROOT / "BENCHMARK.json")
    cell = bench.cell(args.workload)
    require_chip(jax, cell["chips"])
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    config, traffic = bench.config(cell), bench.traffic(cell)
    family = R.load_family(config["family"])
    srv, eng, rec = R.build(jax, config, args.seed)
    R.warm_up(jax, eng, rec, config, traffic)
    print(json.dumps({"setup_s": time.perf_counter() - T_START}), flush=True)

    for rate in [float(r) for r in args.sweep.split(",") if r]:
        t = copy.deepcopy(traffic)
        t["arrival"] = {"process": "poisson", "rate_per_s": rate}
        sched = T.schedule(t, args.seconds)
        rec.calls.clear()
        served, measured = R.drive(jax, eng, rec, sched, args.seconds,
                                   args.seed, config["sizes"]["vocab_size"])
        c = R.client_numbers(served, measured)
        done = [r for r in served if r.handle is not None and r.handle.done]
        print(json.dumps({
            "rate": rate, "attempted": c["attempted"], "finished": len(done),
            "queued_end": len(eng.queue), "sustained": len(eng.queue) == 0,
            "ttft_p50_ms": pct(c["ttft"], 50, 1e3), "ttft_p90_ms": pct(c["ttft"], 90, 1e3),
            "itl_p50_ms": pct(c["gaps"], 50, 1e3), "itl_p99_ms": pct(c["gaps"], 99, 1e3),
            "out_tokens_per_s": c["out_tokens"] / measured,
            "decode_calls": sum(1 for x in rec.calls if x["kind"] == "decode"),
            "prefill_calls": sum(1 for x in rec.calls if x["kind"] == "prefill")}),
            flush=True)
        R.finish(eng, rec, served)

    seeds = [int(s) for s in args.calibrate.split(",") if s]
    for i, seed in enumerate(seeds):
        R.replace_weights(jax, srv, config, seed)
        sched = T.schedule(traffic, args.seconds)
        ck = config["check"]
        watch = C.watch_list(sched, seed, args.seconds, ck)
        served, measured = R.drive(jax, eng, rec, sched, args.seconds, seed,
                                   config["sizes"]["vocab_size"], watch)
        R.finish(eng, rec, served, watch)
        for r in served:
            res = r.handle.result if r.handle is not None else None
            if res is not None and res["finish_reason"] == "length":
                r.result = jax.device_get(res["tokens"])[0]
        srv.params = None
        gc.collect()
        picked = [served[j] for j in watch if served[j].result is not None]
        t0 = time.perf_counter()
        nums = C.check(jax, family, config["sizes"], seed,
                       config["engine"]["dtype"], picked,
                       with_control=i < args.control_seeds)
        nums["correct"] = C.verdict(nums, ck)[0]
        if "control_gap" in nums:
            nums["control_correct"] = C.verdict(C.as_control(nums), ck)[0]
        nums.update(seed=seed, check_s=time.perf_counter() - t0,
                    requests=len(picked), finished=sum(
                        1 for r in served if r.result is not None))
        print(json.dumps(nums), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
