"""Paper claim (§3): the row-partitioned remote-parfor scoring plan "avoids
shuffling and scales linearly with the number of cluster nodes". Verified
structurally (this container has 2 cores — wall-time scaling is not
meaningful): per-worker row count halves as workers double, and the lowered
plan contains zero collectives (subprocess with placeholder devices)."""

from __future__ import annotations

import subprocess
import sys
import os

_BODY = """
import os
os.environ["JAX_PLATFORMS"] = "cpu"  # host devices only; never the chip
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n}"
import sys; sys.path.insert(0, {src!r})
import time
import jax, jax.numpy as jnp
from repro.core.parfor import parfor, count_collectives
from repro.core.sharding import make_mesh
mesh = make_mesh(({n},), ("data",))
w = jax.random.normal(jax.random.PRNGKey(0), (64, 16))
x = jax.random.normal(jax.random.PRNGKey(1), (512, 64))
fn = lambda rows: parfor(lambda r: jax.nn.softmax(r @ w, -1), rows, mesh=mesh)[0]
jitted = jax.jit(fn)
compiled = jitted.lower(x).compile()
colls = count_collectives(compiled.as_text())
out = jitted(x); jax.block_until_ready(out)
t0 = time.perf_counter()
for _ in range(20): out = jitted(x)
jax.block_until_ready(out)
us = (time.perf_counter() - t0) / 20 * 1e6
print(f"RESULT,{{us:.1f}},{{colls}},{{512 // {n}}}")
"""


def run():
    rows = []
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
    for n in (1, 2, 4, 8):
        body = _BODY.format(n=n, src=src)
        r = subprocess.run([sys.executable, "-c", body],
                           capture_output=True, text=True, timeout=300)
        line = [ln for ln in r.stdout.splitlines() if ln.startswith("RESULT")]
        if not line:
            raise RuntimeError(f"parfor_scaling_w{n} child failed: "
                               f"{r.stderr[-400:]}")
        _, us, colls, rows_per = line[0].split(",")
        rows.append(
            f"parfor_scaling_w{n},{us},collectives={colls};rows_per_worker={rows_per}")
    return rows
