"""Reduction of a profiler trace to the numbers the per-layer metrics read.

A trace is kept as three interval lists on one clock (nanoseconds): the
device's operations ("XLA Ops" lines of each ``/device:TPU:N`` plane), the
device's program executions ("XLA Modules" lines), and the benchmark's own
host spans (``jax.profiler.TraceAnnotation`` names starting ``chipbench.``
or ``engine.``). ``load`` reads an ``.xplane.pb`` with
``jax.profiler.ProfileData``; ``from_dict`` reads the same lists from JSON,
which is how the tests feed a small recorded trace.

The window is the host span ``chipbench.window``. Everything is clipped to
it. The profiler stops recording device events once its buffer is full, so
a long window of many small programs can lose its end: when the device
holds fewer executions of the benchmark's step programs than the host
dispatched in the window, the traced window ends with the last one traced.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[int, int, str]          # (start_ns, end_ns, name)
HOST_PREFIXES = ("chipbench.", "engine.")
WINDOW = "chipbench.window"
# ops whose interval encloses other ops of the same program
ENCLOSING = ("while", "conditional", "call")


@dataclass
class Trace:
    ops: List[List[Interval]]            # per device
    modules: List[List[Interval]]        # per device
    host: List[Interval]


def from_dict(d: Dict) -> Trace:
    conv = lambda xs: [(int(a), int(b), str(n)) for a, b, n in xs]   # noqa: E731
    return Trace([conv(x) for x in d["ops"]], [conv(x) for x in d["modules"]],
                 conv(d["host"]))


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, modules, host = [], [], []
    for plane in pd.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            o, m = [], []
            for line in plane.lines:
                dest = {"XLA Ops": o, "XLA Modules": m}.get(line.name)
                if dest is None:
                    continue
                for ev in line.events:
                    s = int(ev.start_ns)
                    dest.append((s, s + int(ev.duration_ns), ev.name))
            ops.append(o)
            modules.append(m)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    name = ev.name
                    if name.startswith(HOST_PREFIXES):
                        s = int(ev.start_ns)
                        host.append((s, s + int(ev.duration_ns), name))
    return Trace(ops, modules, host)


def op_stem(name: str) -> str:
    """``%fusion.143 = bf16[..] fusion(..)`` -> ``fusion``."""
    head = name.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def module_stem(name: str) -> str:
    """``jit_decode_step(1627..)`` -> ``jit_decode_step``."""
    return name.split("(", 1)[0]


def union(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi), n) for s, e, n in iv if e > lo and s < hi]


@dataclass
class Reduction:
    window_s: float                                 # traced window
    busy_s: float                                   # mean over devices
    kernel_s: Dict[str, float] = field(default_factory=dict)
    # per device-0 program execution in the window, in order:
    # (module stem, seconds, {kernel: seconds})
    programs: List[Tuple[str, float, Dict[str, float]]] = field(default_factory=list)
    host_counts: Dict[str, int] = field(default_factory=dict)
    top_ops: List[Tuple[str, float]] = field(default_factory=list)
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)

    def module_seconds(self, stem: str) -> float:
        return sum(s for m, s, _ in self.programs if m == stem)

    def module_count(self, stem: str) -> int:
        return sum(1 for m, _s, _ in self.programs if m == stem)


def reduce(trace: Trace, kernels: Dict[str, Sequence[str]], top: int = 10,
           dispatched: Tuple[Sequence[str], int] = ((), 0)) -> Optional[Reduction]:
    """``kernels``: {kernel name: op stems it appears as in the trace}.
    ``dispatched``: (module stems of the step programs, how many the host
    dispatched in the window). Returns None when the trace holds no window
    span or no device."""
    wins = [h for h in trace.host if h[2] == WINDOW]
    if not wins or not trace.ops:
        return None
    lo, hi = wins[0][0], wins[0][1]
    stems, count = dispatched
    ends = sorted(e for s, e, n in (trace.modules[0] if trace.modules else [])
                  if lo <= s < hi and module_stem(n) in stems)
    if 0 < len(ends) < count:
        hi = min(hi, ends[-1])
    window_s = (hi - lo) / 1e9
    stem_to_kernel = {st: k for k, sts in kernels.items() for st in sts}

    busy = []
    for dev_ops in trace.ops:
        u = union([(s, e) for s, e, _ in _clip(dev_ops, lo, hi)])
        busy.append(sum(e - s for s, e in u) / 1e9)
    red = Reduction(window_s=window_s, busy_s=sum(busy) / len(busy))

    ops0 = sorted(_clip(trace.ops[0], lo, hi))
    mods0 = sorted(_clip(trace.modules[0], lo, hi)) if trace.modules else []
    kernel_s: Dict[str, float] = {}
    per_module: List[Dict[str, float]] = [dict() for _ in mods0]
    agg: Dict[str, float] = {}
    mi = 0
    for s, e, name in ops0:
        stem = op_stem(name)
        while mi < len(mods0) and mods0[mi][1] <= s:
            mi += 1
        inside = mi < len(mods0) and mods0[mi][0] <= s
        k = stem_to_kernel.get(stem)
        if k is not None:
            kernel_s[k] = kernel_s.get(k, 0.0) + (e - s) / 1e9
            if inside:
                per_module[mi][k] = per_module[mi].get(k, 0.0) + (e - s) / 1e9
        if stem not in ENCLOSING:
            owner = module_stem(mods0[mi][2]) if inside else "?"
            key = f"{owner}/{stem}"
            agg[key] = agg.get(key, 0.0) + (e - s) / 1e9
    red.kernel_s = kernel_s
    red.programs = [(module_stem(n), (e - s) / 1e9, per_module[i])
                    for i, (s, e, n) in enumerate(mods0)]
    red.top_ops = sorted(agg.items(), key=lambda kv: -kv[1])[:top]

    spans = _clip([h for h in trace.host if h[2] != WINDOW], lo, hi)
    for _s, _e, n in spans:
        red.host_counts[n] = red.host_counts.get(n, 0) + 1
    # idle gaps on device 0, named by the innermost host span open at the
    # gap's middle
    u = union([(s, e) for s, e, _ in ops0])
    gaps, t = [], lo
    for s, e in u:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    named = []
    for gs, ge in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = (gs + ge) // 2
        cover = [h for h in spans if h[0] <= mid < h[1]]
        name = min(cover, key=lambda h: h[1] - h[0])[2] if cover else "no span"
        named.append((name, (ge - gs) / 1e9))
    red.idle_gaps = named
    return red
