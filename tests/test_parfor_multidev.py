"""parfor task-parallel scoring (paper §3): remote plan == local plan
results, and the remote body contains ZERO collectives (the "avoids
shuffling" claim). Multi-device behaviour runs in a subprocess with 8
placeholder host devices."""

from conftest import run_multidev


def test_parfor_remote_equals_local_and_no_shuffle():
    out = run_multidev("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core.sharding import make_mesh
from repro.core.parfor import parfor, choose_parfor_plan, count_collectives

mesh = make_mesh((4, 2), ("data", "model"))
w = jax.random.normal(jax.random.PRNGKey(0), (16, 8))

def score(rows):
    return jax.nn.softmax(rows @ w, axis=-1)

x = jax.random.normal(jax.random.PRNGKey(1), (64, 16))

# local (no mesh)
local, plan_l = parfor(score, x)
assert plan_l == "local"

# remote (row-partitioned shard_map)
remote, plan_r = parfor(score, x, mesh=mesh)
assert plan_r == "remote", plan_r
np.testing.assert_allclose(np.asarray(remote), np.asarray(local), rtol=1e-5)

# the "avoids shuffling" property: zero collectives in the lowered plan
import functools
fn = lambda rows: parfor(score, rows, mesh=mesh)[0]
hlo = jax.jit(fn).lower(x).compile().as_text()
n = count_collectives(hlo)
assert n == 0, f"parfor body must be collective-free, found {n}"

# with reduce="mean": exactly the final allreduce appears
fn2 = lambda rows: parfor(lambda r: jnp.sum(r @ w, axis=-1, keepdims=True),
                          rows, mesh=mesh, reduce="mean")[0]
hlo2 = jax.jit(fn2).lower(x).compile().as_text()
assert count_collectives(hlo2) >= 1
print("PARFOR_OK")
""")
    assert "PARFOR_OK" in out


def test_parfor_optimizer_chooses_local_for_small_input():
    out = run_multidev("""
import jax
from repro.core.parfor import choose_parfor_plan
from repro.core.sharding import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))
assert choose_parfor_plan(2, mesh) == "local"      # too few rows
assert choose_parfor_plan(3, mesh) == "local"      # indivisible
assert choose_parfor_plan(64, mesh) == "remote"
assert choose_parfor_plan(64, None) == "local"
print("CHOOSE_OK")
""")
    assert "CHOOSE_OK" in out


def test_sharded_train_step_multidev():
    """A reduced arch trains under a real (4 data x 2 model) mesh with the
    planner's shardings — the end-to-end distributed path on 8 devices."""
    out = run_multidev("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.sharding import make_mesh
from repro.config import MeshConfig, InputShape, TrainConfig
from repro.configs import get_config
from repro.core.planner import compile_plan
from repro.core.sharding import tree_specs
from repro.models.model import build_model
from repro.runtime.train_loop import (make_train_step, init_opt_state,
                                      train_shardings, batch_specs)
from repro.data import make_batch

mesh_cfg = MeshConfig(shape=(4, 2), axis_names=("data", "model"))
mesh = make_mesh((4, 2), ("data", "model"))
cfg = get_config("yi-6b-smoke")
shape = InputShape("tiny", 32, 8, "train")
train = TrainConfig(optimizer="adam", learning_rate=1e-2, force_strategy="fsdp_tensor_parallel")
plan = compile_plan(cfg, shape, mesh_cfg, train)
model = build_model(cfg, dtype=jnp.float32)

with mesh:
    (pspecs, _, pshard), (ospecs, _, oshard) = train_shardings(model, plan.config, mesh_cfg, train, mesh)
    params = model.init_params(jax.random.PRNGKey(0))
    params = jax.device_put(params, pshard)
    opt = init_opt_state(train.optimizer, params, plan.config)
    step_fn = jax.jit(make_train_step(model, plan.config, mesh_cfg, train))
    losses = []
    for i in range(8):
        b = make_batch(cfg, shape, step=i, dtype=jnp.float32)
        params, opt, metrics = step_fn(params, opt, b, jnp.int32(i))
        losses.append(float(metrics["loss"]))
assert all(np.isfinite(losses)), losses
assert losses[-1] < losses[0], losses
print("TRAIN_MULTIDEV_OK", losses[0], "->", losses[-1])
""", timeout=560)
    assert "TRAIN_MULTIDEV_OK" in out
