"""A cell's run from set-up to the result line, found by names alone.

``BENCHMARK.json`` names the cell; the cell names its configuration file
and its traffic file; every metric is read by ``chipbench/metrics/<name>.py``
(or, for a metric split by a last ``.suffix``, by the reader of the name
without it); every kernel is costed by ``chipbench/kernels/<kernel>.py``.
So a later cell, configuration, metric or kernel is a new file and an
entry, with no edit here.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from chipbench.lib import check as C
from chipbench.lib import runner as R
from chipbench.lib import trace as TR
from chipbench.lib import traffic as T
from chipbench.lib.readers import PROGRAM

HERE = Path(__file__).resolve().parents[1]           # chipbench/
ROOT = HERE.parent


class Bench:
    """BENCHMARK.json and the files it names."""

    def __init__(self, path: Path = ROOT / "BENCHMARK.json"):
        self.spec = json.loads(Path(path).read_text())
        self.root = Path(path).resolve().parent

    def cell(self, name: str) -> Dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise SystemExit(f"chipbench: no workload {name!r} in BENCHMARK.json")

    def config(self, cell: Dict) -> Dict:
        for c in self.spec["configs"]:
            if c["name"] == cell["config"]:
                return json.loads((self.root / c["file"]).read_text())
        raise SystemExit(f"chipbench: no config {cell['config']!r}")

    def traffic(self, cell: Dict) -> Dict:
        path = HERE / "traffic" / f"{cell['traffic']}.json"
        return json.loads(path.read_text())

    def metrics(self, cell: Dict, section: str) -> List[Dict]:
        """The cell's metrics of ``section`` (end_to_end | per_layer)."""
        e2e_here = {m["name"] for m in self.spec["end_to_end"]
                    if cell["name"] in m.get("workloads", [cell["name"]])}
        out = []
        for m in self.spec[section]:
            if "workloads" in m:
                if cell["name"] in m["workloads"]:
                    out.append(m)
            elif section == "end_to_end" or m["moves"] in e2e_here:
                out.append(m)
        return out


def _load(path: Path):
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(name: str):
    """The reader module of metric ``name``."""
    d = HERE / "metrics"
    for cand in (name, name.rsplit(".", 1)[0]):
        if (d / f"{cand}.py").is_file():
            return _load(d / f"{cand}.py")
    raise SystemExit(f"chipbench: no reader for metric {name!r}")


def kernels(family: str) -> Dict[str, Any]:
    """{kernel name: cost module} of the kernels this family runs."""
    out = {}
    for p in sorted((HERE / "kernels").glob("*.py")):
        mod = _load(p)
        if family in getattr(mod, "FAMILIES", ()):
            out[p.stem] = mod
    return out


def peaks(kind: str) -> Dict:
    table = json.loads((HERE / "peaks.json").read_text())
    if kind not in table["chips"]:
        raise SystemExit(f"chipbench: no peaks for device kind {kind!r} in "
                         "chipbench/peaks.json")
    return table["chips"][kind]


def _start_trace(jax, path: str) -> None:
    """Host events at level 1 (the benchmark's own annotations, not the
    runtime's), device events of XLA operations only: the fewer events a
    window makes, the longer the profiler's buffers last."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.advanced_configuration = {"tpu_trace_mode": "TRACE_ONLY_XLA"}
    try:
        jax.profiler.start_trace(path, profiler_options=opts)
    except Exception as e:                       # a runtime without the mode
        print(f"chipbench: profiler refused TRACE_ONLY_XLA ({e}); "
              "tracing with its defaults", file=sys.stderr, flush=True)
        opts.advanced_configuration = {}
        jax.profiler.start_trace(path, profiler_options=opts)


def run_cell(jax, bench: Bench, name: str, seed: int, seconds: float,
             trace: bool, t_start: float, device: Dict, chip_peaks: Dict,
             control: bool = False,
             fault: Optional[Callable] = None) -> Tuple[Dict, List[str]]:
    """One run. Returns (result line, check lines)."""
    cell = bench.cell(name)
    config, traffic = bench.config(cell), bench.traffic(cell)
    family = R.load_family(config["family"])
    counter = R.CompileCounter(jax).__enter__()
    t_build = time.perf_counter()
    srv, eng, rec = R.build(jax, config, seed)
    if fault is not None:
        fault(srv, eng)
    t_warm = time.perf_counter()
    R.warm_up(jax, eng, rec, config, traffic)
    print(f"chipbench: imports {t_build - t_start:.1f} s, build "
          f"{t_warm - t_build:.1f} s, warm-up {time.perf_counter() - t_warm:.1f} s, "
          f"backend compiles {counter.compiles} ({counter.seconds:.1f} s), "
          f"persistent-cache hits {counter.cache_hits}", file=sys.stderr, flush=True)
    sched = T.schedule(traffic, seconds)
    ck = config["check"]
    watch = C.watch_list(sched, seed, seconds, ck)
    denied0 = srv.pool.metrics.pages_denied + srv.pool.metrics.arenas_denied
    compiles0 = counter.compiles
    tdir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    gc.collect()
    gc.disable()
    opened = {}
    try:
        if trace:
            _start_trace(jax, tdir)

        def on_open():
            opened["t"] = time.perf_counter()
            jax.config.update("jax_log_compiles", True)

        with jax.profiler.TraceAnnotation(TR.WINDOW):
            served, measured = R.drive(jax, eng, rec, sched, seconds, seed,
                                       config["sizes"]["vocab_size"], watch,
                                       on_open)
        jax.config.update("jax_log_compiles", False)
        compiles = counter.compiles - compiles0
        if trace:
            jax.profiler.stop_trace()
    finally:
        gc.enable()
    setup_s = opened["t"] - t_start
    denials = (srv.pool.metrics.pages_denied + srv.pool.metrics.arenas_denied
               - denied0)
    failed = sum(1 for r in served if r.due < measured and r.handle is not None
                 and r.handle.result is not None
                 and r.handle.result["finish_reason"] != "length")
    R.finish(eng, rec, served, watch)
    for i in watch:
        res = served[i].handle.result
        if res is not None and res["finish_reason"] == "length":
            served[i].result = np.asarray(res["tokens"])[0]
    mem = R.memory_peak(jax)
    rec_calls, recorder_s = rec.calls, rec.host_s
    run = R.Run(cell=cell, config=config, sizes=config["sizes"], family=family,
                peaks=chip_peaks, seconds=measured, served=served,
                calls=rec_calls, compiles_in_window=compiles,
                pool_denials=denials, memory_peak_bytes=mem,
                kernels=kernels(config["family"]), setup_s=setup_s)
    if trace:
        files = list(Path(tdir).rglob("*.xplane.pb"))
        if files:
            print(f"chipbench: trace {files[0].stat().st_size / 1e6:.0f} MB",
                  file=sys.stderr, flush=True)
            run.red = TR.reduce(TR.load(str(files[0])),
                                {k: m.TRACE_OPS for k, m in run.kernels.items()},
                                dispatched=(tuple(PROGRAM.values()), len(rec_calls)))
            if run.red is not None and run.red.window_s < measured - 1.0:
                print(f"chipbench: the device trace ends {run.red.window_s:.1f} s "
                      "into the window; per-layer metrics read that part",
                      file=sys.stderr, flush=True)
        shutil.rmtree(tdir, ignore_errors=True)
    for r in served:
        r.handle = None
    del srv, eng, rec
    gc.collect()
    counter.__exit__()
    stats = jax.devices()[0].memory_stats() or {}
    print(f"chipbench: window {measured:.1f} s, {len(rec_calls)} program calls, "
          f"{compiles} compiles in it, recorder host work in it "
          f"{1e3 * recorder_s:.1f} ms; after freeing the served system "
          f"bytes_in_use={stats.get('bytes_in_use')}", file=sys.stderr, flush=True)

    picked = [served[i] for i in watch if served[i].result is not None]
    nums = C.check(jax, family, config["sizes"], seed,
                   config["engine"]["dtype"], picked, with_control=control)
    # the control stands in the program's place: its tokens and logits are
    # judged against the same limits, and should not pass them
    correct, checks, lines = C.verdict(C.as_control(nums) if control else nums, ck)

    section = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in bench.metrics(cell, section):
        value = reader(m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = dict(device, memory_peak_bytes=mem)
    result: Dict[str, Any] = {
        "correct": bool(correct), "attempted": R.client_numbers(
            served, measured)["attempted"],
        "failed": failed, "metrics": metrics, "device": dev}
    if trace and run.red is not None:
        dev["busy_s"] = run.red.busy_s
        dev["window_s"] = run.red.window_s
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in run.red.top_ops],
            "idle_gaps": [[n, s] for n, s in run.red.idle_gaps]}
    result["checks"] = checks
    return result, lines

