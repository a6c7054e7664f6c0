"""The serving engine's own profiler spans and program counters.

The tick's phases run under fixed-name ``jax.profiler.TraceAnnotation``
spans and ``SchedulerMetrics`` counts the programs they dispatch. A
profiler session records the spans on the host plane, on the same clock as
the device's operations. These tests drive a smoke-width engine under
``jax.profiler.start_trace`` in a child process (so the profiler can never
hang the suite: the child has its own time limit), read the host plane
back with ``ProfileData``, and check names, nesting and counts against
hand counts.
"""

import json
import os
import subprocess
import sys

import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.runtime.engine_config import EngineConfig
from repro.runtime.serve_loop import PlanServer

SPANS = {"engine.tick", "engine.joins", "engine.form", "engine.admit",
         "engine.prefill", "engine.wait", "engine.decode", "engine.emit",
         "engine.retire", "engine.plan"}
CHILD_TIMEOUT_S = 240

SCRIPT = r"""
import json, sys, tempfile
from pathlib import Path

import jax
from jax.profiler import ProfileData

from repro.configs import get_config
from repro.runtime.engine import ServingEngine
from repro.runtime.engine_config import EngineConfig
from repro.runtime.serve_loop import PlanServer, ServeRequest

srv = PlanServer(get_config("yi-6b-smoke"), config=EngineConfig())
slots = []
run_prefill = srv.run_prefill


def counted_prefill(entry, tokens=None, lengths=None):
    slots.append(entry.key.batch_bucket * entry.key.seq_bucket)
    return run_prefill(entry, tokens, lengths)


srv.run_prefill = counted_prefill


def traced(drive):
    # host events at level 1 (annotations), as the benchmark records them
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    tdir = tempfile.mkdtemp()
    jax.profiler.start_trace(tdir, profiler_options=opts)
    out = drive()
    jax.profiler.stop_trace()
    pd = ProfileData.from_file(str(next(Path(tdir).rglob("*.xplane.pb"))))
    spans = [(int(ev.start_ns), int(ev.start_ns) + int(ev.duration_ns),
              ev.name)
             for plane in pd.planes if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events
             if ev.name.startswith("engine.")]
    return spans, out


def counters(m):
    return {k: getattr(m, k) for k in (
        "ticks", "decode_dispatches", "prefill_dispatches", "prefill_tokens",
        "prefill_slots")}


def mixed():
    # a group of two requests, then a mid-decode joiner into its free row
    eng = ServingEngine(srv)
    reqs = [ServeRequest(1, 20, 6), ServeRequest(2, 24, 6)]
    for r in reqs:
        eng.submit(r)
    steps = 0
    eng.step()
    steps += 1
    joiner = ServeRequest(1, 22, 3)
    reqs.append(joiner)
    eng.submit(joiner)
    while not eng.idle:
        eng.step()
        steps += 1
    return {"steps": steps, "counters": counters(eng.metrics),
            "prompt_tokens": sum(r.batch * r.context for r in reqs),
            "joins": eng.metrics.joins}


def members(n):
    def drive():
        eng = ServingEngine(srv)
        for _ in range(n):
            eng.submit(ServeRequest(1, 20, 5))
        steps = 0
        while not eng.idle:
            eng.step()
            steps += 1
        return {"steps": steps, "groups": eng.metrics.groups}
    return drive


spans, out = traced(mixed)
out["spans"] = spans
out["slots"] = sum(slots)
res = {"mixed": out}
for n in (1, 4):
    spans, got = traced(members(n))
    got["spans"] = spans
    res[f"members{n}"] = got
print(json.dumps(res))
"""


@pytest.fixture(scope="module")
def run():
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.abspath(src))
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, timeout=CHILD_TIMEOUT_S, env=env)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return json.loads(r.stdout.strip().splitlines()[-1])


def _names(spans):
    return [n for _s, _e, n in spans]


def _count(spans):
    out = {}
    for n in _names(spans):
        out[n] = out.get(n, 0) + 1
    return out


def test_every_phase_span_is_emitted_and_no_other(run):
    assert set(_names(run["mixed"]["spans"])) == SPANS
    for key in ("members1", "members4"):
        assert set(_names(run[key]["spans"])) <= SPANS


def test_no_span_takes_the_benchmarks_tick_name(run):
    for key in ("mixed", "members1", "members4"):
        names = set(_names(run[key]["spans"]))
        assert "engine.step" not in names
        assert not any(n.startswith("chipbench.") for n in names)


def test_one_tick_span_per_step_call(run):
    for key in ("mixed", "members1", "members4"):
        got = run[key]
        assert _count(got["spans"])["engine.tick"] == got["steps"]
    assert run["mixed"]["counters"]["ticks"] == run["mixed"]["steps"]


@pytest.mark.parametrize("name", ["engine.wait", "engine.decode",
                                  "engine.emit"])
def test_device_phases_lie_inside_a_tick(run, name):
    spans = run["mixed"]["spans"]
    ticks = [(s, e) for s, e, n in spans if n == "engine.tick"]
    inner = [(s, e) for s, e, n in spans if n == name]
    assert inner
    for s, e in inner:
        assert any(ts <= s and e <= te for ts, te in ticks), (name, s, e)


def test_span_counts_per_tick_do_not_grow_with_members(run):
    one, four = run["members1"], run["members4"]
    assert one["groups"] == four["groups"] == 1
    assert one["steps"] == four["steps"]
    c1, c4 = _count(one["spans"]), _count(four["spans"])
    for name in SPANS - {"engine.plan"}:
        assert c1.get(name) == c4.get(name), name
    for name in ("engine.tick", "engine.decode", "engine.emit"):
        assert c4[name] == four["steps"], name
    # one wait per decode step and one for the group's prefill
    assert c4["engine.wait"] == four["steps"] + c4["engine.prefill"]
    # the group forms in the first tick; joins run for groups in flight
    assert c4["engine.joins"] == four["steps"] - 1


def test_counters_equal_hand_counts(run):
    got = run["mixed"]
    c = got["counters"]
    assert got["joins"] == 1
    assert c["ticks"] == got["steps"]
    assert c["prefill_tokens"] == got["prompt_tokens"]
    assert c["prefill_slots"] == got["slots"]
    counts = _count(got["spans"])
    assert c["prefill_dispatches"] == counts["engine.prefill"] == 2
    assert c["decode_dispatches"] == counts["engine.decode"]


def test_step_programs_lower_to_the_names_the_trace_matches():
    """The device trace names a program by its jitted function: the
    benchmark matches prefill and decode executions as ``jit_prefill`` and
    ``jit_decode_step``."""
    srv = PlanServer(get_config("yi-6b-smoke"), config=EngineConfig())
    pre = srv.prefill_entry(2, 24)
    b, s = pre.key.batch_bucket, pre.key.seq_bucket
    text = pre.step_fn.lower(
        srv.params, {"tokens": jnp.ones((b, s), jnp.int32),
                     "lengths": jnp.full((b,), s, jnp.int32)}).as_text()
    assert "module @jit_prefill" in text
    dec = srv.decode_entry(2, 24)
    b, s = dec.key.batch_bucket, dec.key.seq_bucket
    arena = srv.pool.acquire(b, s)
    text = dec.step_fn.lower(
        srv.params, arena.cache, jnp.ones((b, 1), jnp.int32),
        jnp.zeros((b,), jnp.int32), arena.tables).as_text()
    assert "module @jit_decode_step" in text
