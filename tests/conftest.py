import os
import subprocess
import sys

import pytest

# Tests run single-device (the dry-run owns the 512-device setup; see
# src/repro/launch/dryrun.py). Multi-device behaviours are tested through
# subprocesses that set XLA_FLAGS before importing jax.
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


@pytest.fixture(autouse=True)
def _fresh_legacy_kwarg_warnings():
    """fold_legacy_kwargs warns once per process per call site; reset the
    registry before every test so pytest.warns assertions hold regardless
    of test order (imported lazily: multidev subprocess helpers must not
    force jax in before they set XLA_FLAGS)."""
    from repro.runtime.engine_config import reset_legacy_kwarg_warnings
    reset_legacy_kwarg_warnings()
    yield

MULTIDEV_PRELUDE = """
import os
os.environ["JAX_PLATFORMS"] = "cpu"  # host devices only; never the chip
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n}"
import sys
sys.path.insert(0, {src!r})
"""


def multidev_script(body: str, n: int = 8) -> str:
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    return MULTIDEV_PRELUDE.format(n=n, src=os.path.abspath(src)) + body


def run_multidev(body: str, n: int = 8, timeout: int = 300) -> str:
    r = subprocess.run(
        [sys.executable, "-c", multidev_script(body, n)],
        capture_output=True, text=True, timeout=timeout,
    )
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout
