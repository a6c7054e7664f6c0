#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this process finds.

    python3 chipbench/run.py --workload mamba2-1.3b.chat-burst --seed 7 --seconds 50 --trace 0

Prints the device, refuses anything but a TPU with as many chips as the
cell asks for, builds the served system from the cell's configuration,
warms every shape its traffic reaches, offers the traffic for ``--seconds``
and prints, as the last line of standard output, one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and with
``--trace 1`` the per-layer metrics and a ``breakdown``), ending with the
numbers of the correctness check beside their limits. The same numbers
close standard error.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def require_chip(jax, chips: int) -> dict:
    """Print the device; refuse to run anywhere but on ``chips`` TPUs."""
    devs = jax.devices()
    dev = devs[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devs)}", flush=True)
    if dev.platform != "tpu":
        raise SystemExit(f"chipbench: needs a TPU, JAX found {dev.platform!r}")
    if len(devs) < chips:
        raise SystemExit(f"chipbench: the cell needs {chips} chips, JAX "
                         f"found {len(devs)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        raise SystemExit("chipbench: --seed must be a non-negative whole number")
    if not (ROOT / "BENCHMARK.json").is_file():
        raise SystemExit(f"chipbench: no BENCHMARK.json beside {ROOT / 'chipbench'}")
    if not (ROOT / "src" / "repro").is_dir():
        raise SystemExit(f"chipbench: no repro package under {ROOT / 'src'}; "
                         "run from a checkout of the repository")
    # libtpu would otherwise log under a fixed /tmp path
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

    from chipbench.lib import cell as CELL
    bench = CELL.Bench(ROOT / "BENCHMARK.json")
    cell = bench.cell(args.workload)

    import jax
    device = require_chip(jax, cell["chips"])
    chip_peaks = CELL.peaks(device["kind"])
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    # keep every program, however quick to compile, so that only a cell's
    # first run in a checkout compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    result, lines = CELL.run_cell(
        jax, bench, args.workload, args.seed, args.seconds, bool(args.trace),
        T_START, device, chip_peaks)
    for line in lines:
        print(line, file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
