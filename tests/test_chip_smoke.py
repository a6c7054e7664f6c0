"""chip_smoke.py rehearsed on the CPU: its phases run end to end at smoke
widths with the Pallas kernels in interpret mode, and the script itself
refuses to run, and prints no result, without a TPU."""

import json
import os
import subprocess
import sys

from conftest import run_multidev

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")

# smoke widths and a short request mix; the CPU stands in for the chip, and
# interpret-mode kernels lower to no tpu_custom_call, so that check is off
SMOKE_SETUP = """
import importlib.util, json, sys
spec = importlib.util.spec_from_file_location("chip_smoke", {script!r})
cs = importlib.util.module_from_spec(spec)
spec.loader.exec_module(cs)
cs._import_repo()
import jax
from repro import compile_cache
from repro.kernels import ops
ops.BACKEND = "pallas"
compile_cache.enable_compile_cache = lambda: "off"
cs.require_kernels = lambda where, names: None
cs.require_tpu = lambda jax: {{"platform": "cpu", "kind": "cpu",
                              "count": len(jax.devices())}}
cs.YI, cs.MAMBA = "yi-6b-smoke", "mamba2-1.3b-smoke"
cs.YI_CONTEXTS, cs.MAMBA_CONTEXTS, cs.NEW_TOKENS = (20, 24, 28, 100), (20, 40), 3
cs.FLASH = dict(b=1, h=4, hkv=2, s=64, d=32)
cs.PAGED = dict(b=2, hkv=2, g=2, d=32, page=16, sc=64)
cs.SSD = dict(b=1, s=64, h=2, p=16, n=16, chunk=16)
"""


def _smoke_script(argv) -> str:
    return SMOKE_SETUP.format(script=SCRIPT) + (
        f"rc = cs.main({argv!r})\n"
        "assert rc == 0, rc\n")


def _last_json(stdout: str) -> dict:
    return json.loads(stdout.strip().splitlines()[-1])


def test_chip_smoke_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, SCRIPT], capture_output=True,
                       text=True, timeout=120, env=env, cwd=ROOT)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "needs a TPU" in r.stderr


def test_chip_smoke_one_chip_phases_at_smoke_size():
    r = subprocess.run(
        [sys.executable, "-c", _smoke_script([])], capture_output=True,
        text=True, timeout=600, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        cwd=ROOT)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    out = r.stdout
    for phase in ("phase 1", "phase 2", "phase 3"):
        assert phase in out
    assert out.count(" ok") >= 3            # three kernels within tolerance
    assert "all finished" in out
    assert _last_json(out) == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 1}}


def test_chip_smoke_sharded_phase_on_four_host_devices():
    out = run_multidev(_smoke_script(["--chips", "4"]), n=4, timeout=600)
    assert "on a 4-chip mesh" in out
    assert "4 chips vs 1" in out and "FAIL" not in out
    assert _last_json(out)["device"]["count"] == 4

