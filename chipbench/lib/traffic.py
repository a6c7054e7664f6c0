"""The one traffic generator: a traffic file's parameters -> a schedule.

A traffic file (``chipbench/traffic/<mix>.json``) gives an arrival process
and the distributions of prompt and output lengths. The schedule, arrival
times and lengths alike, is drawn from the file's own ``base_seed``, so
every run offers the same work in the same order; a run's ``--seed`` draws
only the weights and the prompts' token ids. (When the seed drew the work
itself, the set of lengths that fell into a window changed from seed to
seed, and with it the tails, by a factor of two.)

Arrival processes:

- ``poisson``: exponential gaps at ``rate_per_s``.
- ``switching_poisson``: a repeating ``period_s`` made of ``phases``, each
  ``{"seconds", "rate_scale"}``; inside a phase the rate is
  ``mean_rate_per_s * rate_scale``. The scales are normalised so that the
  time-averaged rate over a period is ``mean_rate_per_s``.

Lengths: ``{"dist": "lognormal", "median", "sigma", "min", "max"}``,
rounded to whole tokens and clipped to [min, max].
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, List

import numpy as np


@dataclass(frozen=True)
class Arrival:
    due_s: float
    prompt: int
    output: int


def _lengths(rng, spec: Dict, n: int) -> np.ndarray:
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    x = rng.lognormal(math.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def _segments(arrival: Dict, seconds: float):
    """[(start, end, rate)] covering [0, seconds)."""
    kind = arrival["process"]
    if kind == "poisson":
        return [(0.0, seconds, float(arrival["rate_per_s"]))]
    if kind == "switching_poisson":
        phases = arrival["phases"]
        period = float(arrival["period_s"])
        if abs(sum(p["seconds"] for p in phases) - period) > 1e-9:
            raise ValueError("phases must fill period_s")
        norm = sum(p["seconds"] * p["rate_scale"] for p in phases) / period
        out, t = [], 0.0
        while t < seconds:
            for p in phases:
                end = min(t + p["seconds"], seconds)
                if end > t:
                    out.append((t, end, arrival["mean_rate_per_s"]
                                * p["rate_scale"] / norm))
                t += p["seconds"]
        return out
    raise ValueError(f"unknown arrival process {kind!r}")


def mean_rate(arrival: Dict, seconds: float) -> float:
    """Expected arrivals per second over ``seconds``."""
    segs = _segments(arrival, seconds)
    return sum((e - s) * r for s, e, r in segs) / seconds


def schedule(traffic: Dict, seconds: float) -> List[Arrival]:
    """Arrivals due in [0, seconds), in due order."""
    base = np.random.default_rng(int(traffic.get("base_seed", 0)))
    arrival = traffic["arrival"]
    parts = []
    for start, end, rate in _segments(arrival, seconds):
        dues, t = [], start
        while True:
            t += base.exponential(1.0 / rate)
            if t >= end:
                break
            dues.append(t)
        parts.append(np.asarray(dues))
    dues = np.concatenate(parts) if parts else np.zeros(0)
    n = len(dues)
    prompts = _lengths(base, traffic["prompt"], n)
    outputs = _lengths(base, traffic["output"], n)
    return [Arrival(float(d), int(p), int(o))
            for d, p, o in zip(dues, prompts, outputs)]


def percentile(samples, q: float) -> float:
    """Nearest-rank percentile: the smallest sample with at least ``q``
    percent of the samples at or below it."""
    xs = sorted(samples)
    if not xs:
        raise ValueError("percentile of no samples")
    return xs[min(len(xs) - 1, max(0, math.ceil(q / 100.0 * len(xs)) - 1))]
