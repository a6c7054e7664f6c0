"""The main-path Pallas kernels, and yi-6b's serving steps, compiled ahead of
time for a described TPU v5e by the installed TPU compiler.

Interpret mode runs a kernel's body on the CPU and accepts blocks that
Mosaic refuses; these compiles fail where the chip's compiler would. Nothing
runs: no result or time comes from here. The topology is described inside a
fixture, never at import, so that only the worker that runs this file loads
the TPU library.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.config import InputShape, MeshConfig
from repro.configs import get_config
from repro.core.planner import PlanCompiler
from repro.core.strategies import PlanConfig, Strategy
from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention
from repro.kernels.paged_attention import paged_decode_attention
from repro.kernels.ssd_scan import ssd_scan
from repro.models.common import ShardCtx
from repro.models.model import build_model
from repro.runtime.serve_loop import make_decode_step, make_prefill

BF16 = jnp.bfloat16
HBM_BYTES = 16 * 1024**3


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # programs compiled for a described chip cannot be read back from the
    # persistent cache without one: keep them out of it
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 (any failure means: no TPU compiler)
        jax.config.update("jax_enable_compilation_cache", was_on)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Steer the dispatcher to the compiled kernels, as on a chip."""
    monkeypatch.setattr(ops, "on_tpu", lambda: True)


def _kernels(lowered) -> list:
    return sorted(set(re.findall(r'kernel_name = "([^"]+)"',
                                 lowered.as_text())))


def _compile(fn, *args):
    lowered = jax.jit(fn).lower(*args)
    return lowered, lowered.compile()


def test_flash_prefill_compiles(one_chip):
    sds = lambda s: jax.ShapeDtypeStruct(s, BF16, sharding=one_chip)
    q, kv = sds((1, 32, 2048, 128)), sds((1, 4, 2048, 128))
    lowered, _ = _compile(lambda q, k, v: flash_attention(q, k, v), q, kv, kv)
    assert _kernels(lowered) == ["_flash_kernel"]


def test_flash_short_prompt_compiles(one_chip):
    """A 5-token prompt: blocks keep their 8-row floor, the prompt pads."""
    sds = lambda s: jax.ShapeDtypeStruct(s, BF16, sharding=one_chip)
    lowered, _ = _compile(lambda q, k, v: flash_attention(q, k, v),
                          sds((1, 32, 5, 128)), sds((1, 4, 5, 128)),
                          sds((1, 4, 5, 128)))
    assert _kernels(lowered) == ["_flash_kernel"]


def test_paged_decode_compiles(one_chip):
    b, hkv, g, d, page, sc = 8, 4, 8, 128, 64, 2048
    n_pages = sc // page
    sds = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    kv = sds((b * sc, hkv, d), BF16)
    lowered, _ = _compile(
        lambda q, k, v, t, p: paged_decode_attention(q, k, v, t, p,
                                                     page=page, sc=sc),
        sds((b, 1, hkv * g, d), BF16), kv, kv,
        sds((b, n_pages), jnp.int32), sds((b,), jnp.int32))
    assert _kernels(lowered) == ["_paged_decode_kernel"]


def test_ssd_scan_compiles_at_mamba2_widths(one_chip):
    b, s, h, p, n = 1, 2048, 64, 64, 128
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                 sharding=one_chip)
    lowered, _ = _compile(
        lambda *a: ssd_scan(*a, chunk=64),
        sds((b, s, h, p), BF16), sds((b, s, h), jnp.float32),
        sds((h,), jnp.float32), sds((b, s, n), BF16), sds((b, s, n), BF16),
        sds((h,), jnp.float32))
    assert _kernels(lowered) == ["_ssd_kernel"]


def test_flash_kernel_under_four_chip_mesh(topo, compiled_kernels):
    """XLA cannot partition a Mosaic kernel: on a mesh the call runs under
    shard_map, batch split over the data axis."""
    mesh = Mesh(np.array(topo.devices), ("data",),
                axis_types=(AxisType.Auto,))
    ctx = ShardCtx(PlanConfig(strategy=Strategy.DATA_PARALLEL,
                              batch_axes=("data",)),
                   MeshConfig((4,), ("data",)), mesh)
    sds = lambda s: jax.ShapeDtypeStruct(s, BF16,
                                         sharding=NamedSharding(mesh, P("data")))
    lowered, compiled = _compile(
        lambda q, k, v: ops.attention(q, k, v, partition=ctx.kernel_map),
        sds((4, 32, 1024, 128)), sds((4, 32, 1024, 128)),
        sds((4, 32, 1024, 128)))
    assert _kernels(lowered) == ["_flash_kernel"]
    # each chip attends over its own row: no collective is needed
    assert "all-gather" not in compiled.as_text()


@pytest.mark.parametrize("kind,batch,seq", [("prefill", 1, 1024),
                                            ("decode", 4, 1024)])
def test_yi6b_serving_step_compiles_and_fits(one_chip, compiled_kernels,
                                             kind, batch, seq):
    """One yi-6b step at its published config in bf16, from eval_shape-style
    shapes: the step carries its kernel, and params plus the step's
    arguments and temporaries fit one chip's HBM."""
    cfg = get_config("yi-6b")
    model = build_model(cfg, dtype=BF16)
    mesh_cfg = MeshConfig((1,), ("data",))
    plan = PlanCompiler(cache_pool_arenas=4, cache_page_size=64).compile(
        cfg, InputShape("step", seq, batch, kind), mesh_cfg)
    sds = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    params = {k: sds(v.shape, v.dtype) for k, v in model.param_specs().items()}
    if kind == "prefill":
        fn = make_prefill(model, plan.config, mesh_cfg)
        args = (params, {"tokens": sds((batch, seq), jnp.int32),
                         "lengths": sds((batch,), jnp.int32)})
        want = "_flash_kernel"
    else:
        fn = make_decode_step(model, plan.config, mesh_cfg, page=64,
                              seq_len=seq)
        ent, _n, sc = model.paged_cache_entries(batch, seq, 64)
        args = (params, {k: sds(s, dt) for k, (s, _a, dt) in ent.items()},
                sds((batch, 1), jnp.int32), sds((batch,), jnp.int32),
                sds((batch, sc // 64), jnp.int32))
        want = "_paged_decode_kernel"
    lowered, compiled = _compile(fn, *args)
    assert _kernels(lowered) == [want]
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 12e9 < mem.argument_size_in_bytes and used < 0.9 * HBM_BYTES
