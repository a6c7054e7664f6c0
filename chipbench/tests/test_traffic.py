"""The traffic generator and the client-side latency arithmetic."""

import json
import math
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from chipbench.lib import runner as R
from chipbench.lib import traffic as T

HERE = Path(__file__).resolve().parents[1]
MIXES = sorted(p.stem for p in (HERE / "traffic").glob("*.json"))


def mix(name):
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", MIXES)
def test_every_run_gets_the_same_schedule(name):
    a = T.schedule(mix(name), 30.0)
    b = T.schedule(mix(name), 30.0)
    assert a == b and a


@pytest.mark.parametrize("name", MIXES)
def test_the_files_base_seed_draws_the_schedule(name):
    t = mix(name)
    other = dict(t, base_seed=t.get("base_seed", 0) + 1)
    a, b = T.schedule(t, 30.0), T.schedule(other, 30.0)
    assert a != b


@pytest.mark.parametrize("name", MIXES)
def test_lengths_stay_inside_their_clips(name):
    t = mix(name)
    s = T.schedule(t, 30.0)
    assert all(t["prompt"]["min"] <= x.prompt <= t["prompt"]["max"] for x in s)
    assert all(t["output"]["min"] <= x.output <= t["output"]["max"] for x in s)
    dues = [x.due_s for x in s]
    assert dues == sorted(dues) and all(0.0 <= d < 30.0 for d in dues)


def test_burst_process_mean_is_the_configured_rate():
    t = mix("chat-burst")
    rate = t["arrival"]["mean_rate_per_s"]
    assert T.mean_rate(t["arrival"], 10.0) == pytest.approx(rate)
    n = len(T.schedule(t, 2000.0))
    # Poisson count over 2000 s: sd = sqrt(rate * 2000), allow 4 sd
    assert abs(n - rate * 2000.0) < 4 * math.sqrt(rate * 2000.0)
    # and the bursts carry 3x / 0.5x the mean
    first = [x for x in T.schedule(t, 2000.0) if x.due_s % 10.0 < 2.0]
    assert len(first) / (0.2 * 2000.0) == pytest.approx(
        rate * 3.0 / 1.0, rel=0.1)


def test_latencies_are_timed_from_due_time():
    done = R.Served(due=1.0, prompt=8, output=3, submit=1.5, tokens=[2.0, 2.5, 3.0])
    late = R.Served(due=4.0, prompt=8, output=3, submit=4.2, tokens=[])
    after = R.Served(due=11.0, prompt=8, output=3)
    c = R.client_numbers([done, late, after], seconds=10.0)
    assert c["attempted"] == 2
    assert c["ttft"] == [pytest.approx(1.0), pytest.approx(6.0)]
    assert c["gaps"] == [pytest.approx(0.5), pytest.approx(0.5)]
    assert c["lag"] == [pytest.approx(0.5), pytest.approx(0.2)]
    assert c["out_tokens"] == 3


def test_nearest_rank_percentile():
    xs = list(range(1, 101))
    assert T.percentile(xs, 90) == 90 and T.percentile(xs, 99) == 99
    assert T.percentile([5.0], 90) == 5.0


def _run(cwd, env):
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "mamba2-1.3b.chat-burst",
         "--seed", "1", "--seconds", "1"], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def test_exits_non_zero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = _run(HERE.parent, env)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout


def test_exits_non_zero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(HERE.parent / "BENCHMARK.json", tmp_path)
    shutil.copytree(HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
