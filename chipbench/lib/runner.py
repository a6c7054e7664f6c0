"""One run of one cell: build the served system, warm it, drive it for the
window from the client side, and record what the metrics read.

The system under test is the repository's serving path as
``python -m repro.launch.serve --scheduler`` takes it:
``EngineConfig(**engine).build_server(get_config(model))`` and its
``ServingEngine``, with the engine's own defaults (prefill handoff, one
``block_until_ready`` per tick, mid-decode joins). The benchmark supplies
the weights (made on the device from the seed, see ``weights``), the
requests (``traffic``), and a recorder that notes each program call's
bucket and real row lengths in dispatch order, by wrapping three of the
engine's callables from outside.
"""

from __future__ import annotations

import gc
import importlib
import math
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import numpy as np

from chipbench.lib import traffic as T
from chipbench.lib import weights as W


def bucket(n: int, minimum: int = 16) -> int:
    """The engine's power-of-two bucket (``repro.core.plan_cache``)."""
    n = max(int(n), minimum, 1)
    return 1 << (n - 1).bit_length()


class CompileCounter:
    """Backend compiles and persistent-cache hits from ``jax.monitoring``."""

    def __init__(self, jax):
        self.monitoring = jax.monitoring
        self.compiles = 0
        self.seconds = 0.0
        self.cache_hits = 0

    def __enter__(self):
        self.monitoring.register_event_duration_secs_listener(self._duration)
        self.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc):
        self.monitoring.unregister_event_duration_listener(self._duration)
        self.monitoring.unregister_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


class Recorder:
    """What the timed path did, read from outside the engine.

    ``calls``: program calls in dispatch order, ``{"kind", "b", "s",
    "lengths" | "positions", "page"}``, from host state only. The recorder
    also hands the prefill each request's own prompt tokens (the engine's
    requests carry only a length and it would prefill ones), and keeps the
    program's logits for the rows of the requests in ``watch``: the
    reference for the check is compared with those. A kept row is sliced
    out on the device by one small jitted call, with no wait for the step;
    ``collect`` copies the kept rows to the host once the window has closed.
    ``host_s`` is the host time the recorder's own work took while ``on``.
    With ``keep_all`` every row is kept (and thrown away): the warm-up uses
    it to compile the slice for every logits shape the window can meet."""

    def __init__(self, jax, page: int, max_rows: int):
        self.page = page
        self.calls: List[Dict[str, Any]] = []
        self.on = False
        self.keep_all = False
        self.host_s = 0.0
        self.prompts: Dict[int, np.ndarray] = {}
        self.watch: Dict[int, "Served"] = {}
        self._queued: List[Any] = []
        self._tick: List[Any] = []
        self._kept: List[Any] = []
        self._slice = jax.jit(lambda x, r: x.reshape(x.shape[0], -1)[r])
        self._rows = [jax.device_put(np.int32(i)) for i in range(max_rows)]

    def _timed(self, t0: float) -> None:
        if self.on:
            self.host_s += time.perf_counter() - t0

    def _keep(self, served, index, logits, row) -> None:
        if served is not None or self.keep_all:
            self._kept.append((served, index, self._slice(logits, self._rows[row])))

    def install(self, eng, srv):
        admit, prefill, tick = eng._admit_members, srv.run_prefill, eng._phase_tick

        def admit_members(group, queued, join_step, now):
            self._queued = queued
            return admit(group, queued, join_step, now)

        def run_prefill(entry, tokens=None, lengths=None):
            t0 = time.perf_counter()
            b, s = entry.key.batch_bucket, entry.key.seq_bucket
            rows = [qr for qr in self._queued for _ in range(qr.req.batch)]
            if tokens is None and any(qr.rid in self.prompts for qr in rows):
                host = np.ones((b, s), np.int32)
                for i, qr in enumerate(rows):
                    p = self.prompts.get(qr.rid)
                    if p is not None:
                        host[i, :len(p)] = p
                tokens = host
            if self.on:
                self.calls.append({"kind": "prefill", "b": b, "s": s,
                                   "lengths": [qr.req.context for qr in rows]})
            self._timed(t0)
            logits, kv = prefill(entry, tokens, lengths)
            t0 = time.perf_counter()
            for i, qr in enumerate(rows):
                self._keep(self.watch.get(qr.rid), 0, logits, i)
            self._timed(t0)
            return logits, kv

        def phase_tick(group):
            t0 = time.perf_counter()
            live = [m for m in group.members if not m.done]
            if self.on:
                self.calls.append({
                    "kind": "decode", "b": group.entry.key.batch_bucket,
                    "s": group.entry.key.seq_bucket, "page": self.page,
                    "positions": [m.base_pos + (group.steps_done - m.join_step)
                                  for m in live for _ in range(m.req.batch)]})
            self._tick = [(self.watch.get(m.qr.rid), m.emitted, m.rows[0])
                          for m in live]
            entry = group.entry
            if not entry.extras.get("chipbench"):
                entry.extras["chipbench"] = entry.step_fn
                entry.step_fn = self._keeping(entry.step_fn)
            self._timed(t0)
            try:
                return tick(group)
            finally:
                self._tick = []

        eng._admit_members = admit_members
        srv.run_prefill = run_prefill
        eng._phase_tick = phase_tick

    def _keeping(self, step):
        def run(*args):
            logits, cache = step(*args)
            t0 = time.perf_counter()
            for served, index, row in self._tick:
                self._keep(served, index, logits, row)
            self._timed(t0)
            return logits, cache
        return run

    def collect(self) -> None:
        """Copy the kept rows to the host (after the window)."""
        for served, index, row in self._kept:
            if served is not None:
                served.logits[index] = np.asarray(row, np.float32)
        self._kept = []


@dataclass
class Served:
    """One request as the client saw it (times in seconds from the window
    open, on the host clock)."""

    due: float
    prompt: int
    output: int
    submit: float = math.nan
    tokens: List[float] = field(default_factory=list)
    handle: Any = None
    result: Optional[np.ndarray] = None     # served tokens, once finished
    prompt_ids: Optional[np.ndarray] = None
    logits: Dict[int, np.ndarray] = field(default_factory=dict)


@dataclass
class Run:
    """Everything the metric readers and the check see."""

    cell: Dict
    config: Dict
    sizes: Dict
    family: Any                     # the reference module
    peaks: Dict
    seconds: float                  # measured window length
    served: List[Served]
    calls: List[Dict]
    compiles_in_window: int
    pool_denials: int
    memory_peak_bytes: int
    red: Any = None                 # trace Reduction (traced runs)
    kernels: Dict[str, Any] = field(default_factory=dict)
    setup_s: float = 0.0


def load_family(name: str):
    return importlib.import_module(f"chipbench.reference.{name}")


def build(jax, config: Dict, seed: int):
    """(server, engine, recorder) with the benchmark's weights installed."""
    from repro.configs import get_config
    from repro.models import model as model_mod
    from repro.runtime.engine import WallClock
    from repro.runtime.engine_config import EngineConfig

    mc = get_config(config["model"])
    sizes = config["sizes"]
    wrong = {k: (getattr(mc, k, None), v) for k, v in sizes.items()
             if getattr(mc, k, None) != v}
    if wrong:
        raise SystemExit(f"chipbench: {config['model']} differs from its "
                         f"configuration file: {wrong}")
    ecfg = EngineConfig(**config["engine"])
    family = load_family(config["family"])
    params = W.make_all(family, sizes, W.seed_key(seed), ecfg.jnp_dtype())

    def init_params(self, key, shardings=None):
        specs = self.param_specs()
        got = {k: (v.shape, v.dtype) for k, v in params.items()}
        want = {k: (tuple(v.shape), v.dtype) for k, v in specs.items()}
        if got != want:
            raise SystemExit(f"chipbench: weight layout of {config['family']} "
                             f"does not match the program's: "
                             f"{sorted(set(got.items()) ^ set(want.items()))[:4]}")
        return params

    orig = model_mod.Model.init_params
    model_mod.Model.init_params = init_params
    try:
        srv = ecfg.build_server(mc)
    finally:
        model_mod.Model.init_params = orig
    del params
    eng = ecfg.build_engine(srv, clock=WallClock())
    rec = Recorder(jax, ecfg.page_size, bucket(ecfg.max_group_batch, 1))
    rec.install(eng, srv)
    return srv, eng, rec


def replace_weights(jax, srv, config: Dict, seed: int) -> None:
    """New seeded weights in place of the server's (same shapes)."""
    family = load_family(config["family"])
    srv.params = None
    gc.collect()
    srv.params = W.make_all(family, config["sizes"], W.seed_key(seed),
                            srv.dtype)
    jax.block_until_ready(srv.params)


def seq_buckets(traffic: Dict) -> List[int]:
    lo = bucket(traffic["prompt"]["min"] + traffic["output"]["min"])
    hi = bucket(traffic["prompt"]["max"] + traffic["output"]["max"])
    out, s = [], lo
    while s <= hi:
        out.append(s)
        s *= 2
    return out


def warm_up(jax, eng, rec: Recorder, config: Dict, traffic: Dict) -> None:
    """Call every program and every small host-side array operation the
    traffic can reach: for each sequence bucket, groups of every size up to
    ``max_group_batch`` and mid-decode joins of every count into every
    batch bucket, plus the per-request token concatenation for every
    output length, and the recorder's row slice for every logits shape."""
    import jax.numpy as jnp
    from repro.runtime.serve_loop import ServeRequest

    mgb = config["engine"].get("max_group_batch", 8)
    rec.keep_all = True

    def drain():
        while not eng.idle:
            eng.step()

    for s in seq_buckets(traffic):
        ctx = max(1, s - 5)           # every span of the scenarios stays in s
        for n in range(1, mgb + 1):
            b = bucket(n, 1)
            if n == b:
                continue
            for _ in range(n):
                eng.submit(ServeRequest(1, ctx, 2))
            drain()
        b = 1
        while b <= mgb:
            for k in range(0, b):
                # b members, k of them finish after one decode step and
                # free their rows; then k newcomers join those rows
                for i in range(b):
                    eng.submit(ServeRequest(1, ctx, 2 if i < k else 4))
                eng.step()
                for _ in range(k):
                    eng.submit(ServeRequest(1, ctx, 2))
                drain()
            b *= 2
    rec.keep_all = False
    rec.collect()
    tok = jnp.ones((1, 1), jnp.int32)
    for n in range(1, traffic["output"]["max"] + 1):
        jax.block_until_ready(jnp.concatenate([tok] * n, axis=1))


def prompt_ids(seed: int, index: int, length: int, vocab: int) -> np.ndarray:
    """The prompt of the ``index``-th request of a run: uniform token ids
    drawn from the seed."""
    return np.random.default_rng([int(seed), int(index)]).integers(
        0, vocab, length, dtype=np.int32)


def drive(jax, eng, rec: Recorder, sched: List[T.Arrival], seconds: float,
          seed: int, vocab: int, watch=(), on_open=None):
    """Offer the schedule open-loop for ``seconds``; returns the requests
    and the measured window length. ``watch``: indices of the requests
    whose logits the recorder keeps for the check."""
    from repro.runtime.serve_loop import ServeRequest

    served = [Served(a.due_s, a.prompt, a.output) for a in sched]
    watch = set(watch)
    by_rid: Dict[int, Served] = {}
    i = 0
    rec.on = True
    t0 = time.perf_counter()
    if on_open is not None:
        on_open()
    now = 0.0
    while now < seconds:
        while i < len(served) and served[i].due <= now:
            r = served[i]
            r.prompt_ids = prompt_ids(seed, i, r.prompt, vocab)
            req = ServeRequest(1, r.prompt, r.output)
            rec.prompts[req.rid] = r.prompt_ids
            if i in watch:
                rec.watch[req.rid] = r
            with jax.profiler.TraceAnnotation("chipbench.submit"):
                r.handle = eng.submit(req)
            r.submit = time.perf_counter() - t0
            by_rid[req.rid] = r
            i += 1
        if eng.idle:
            nxt = served[i].due if i < len(served) else seconds
            with jax.profiler.TraceAnnotation("chipbench.idle"):
                time.sleep(max(0.0, min(nxt, seconds) - now))
        else:
            with jax.profiler.TraceAnnotation("engine.step"):
                events = eng.step()
            t = time.perf_counter() - t0
            for ev in events:
                if ev.token is not None:
                    by_rid[ev.rid].tokens.append(t)
        now = time.perf_counter() - t0
    rec.on = False
    return served, now


def finish(eng, rec: Recorder, served: List[Served], watch=(),
           limit_s: float = 60.0) -> None:
    """After the window: cancel every request but the watched ones and step
    the engine until those have finished (for at most ``limit_s``), so that
    the check sees whole answers. Nothing here is timed or recorded."""
    keep = {id(served[i].handle) for i in watch}
    for h in list(eng.handles.values()):
        if id(h) not in keep:
            eng.cancel(h)
    t0 = time.perf_counter()
    while not eng.idle and time.perf_counter() - t0 < limit_s:
        eng.step()
    rec.collect()
    rec.prompts.clear()
    rec.watch.clear()


def client_numbers(served: List[Served], seconds: float) -> Dict[str, Any]:
    """What the client saw in the window."""
    due = [r for r in served if r.due < seconds]
    ttft = [((r.tokens[0] if r.tokens and r.tokens[0] <= seconds else seconds)
             - r.due) for r in due]
    gaps = [b - a for r in due for a, b in zip(r.tokens, r.tokens[1:])
            if b <= seconds]
    out_tokens = sum(1 for r in due for t in r.tokens if t <= seconds)
    lag = [r.submit - r.due for r in due if not math.isnan(r.submit)]
    return {"attempted": len(due), "ttft": ttft, "gaps": gaps,
            "out_tokens": out_tokens, "lag": lag}


def memory_peak(jax) -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))
