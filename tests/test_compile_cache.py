"""The persistent compilation cache sits where JAX_COMPILATION_CACHE_DIR
says, or else at one fixed directory inside the checkout."""

import os
import subprocess
import sys

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
PROBE = """
import jax, jax.numpy as jnp
from repro.compile_cache import DEFAULT_DIR, enable_compile_cache
print(enable_compile_cache())
print(jax.config.jax_compilation_cache_dir)
print(DEFAULT_DIR)
"""
# writes one compiled program to the cache (never into the checkout's own)
COMPILE = """
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones(8)).block_until_ready()
"""


def _probe(env_dir=None):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=SRC)
    body = PROBE
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
        body += COMPILE
    r = subprocess.run([sys.executable, "-c", body], capture_output=True,
                       text=True, timeout=120, env=env)
    assert r.returncode == 0, r.stderr
    return r.stdout.split()


def test_env_dir_is_used_and_nothing_else(tmp_path):
    used, configured, default = _probe(tmp_path)
    assert used == configured == str(tmp_path)
    assert os.listdir(tmp_path), "compiled program not written to the cache"
    assert default != str(tmp_path)


def test_fixed_in_checkout_dir_without_env():
    used, configured, default = _probe()
    root = os.path.dirname(SRC)
    assert used == configured == default == os.path.join(root, ".jax_cache")


def test_import_sets_nothing():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=SRC)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    r = subprocess.run(
        [sys.executable, "-c",
         "import jax, repro.compile_cache; "
         "print(jax.config.jax_compilation_cache_dir)"],
        capture_output=True, text=True, timeout=120, env=env)
    assert r.returncode == 0, r.stderr
    assert r.stdout.strip() == "None"
