"""Plan compiler behaviour + property tests (hypothesis).

The invariants mirror SystemML's optimizer contracts: never pick a plan
whose worst-case estimate exceeds the budget if a fitting plan exists;
escalate monotonically with model size; single-device -> single-node plan.
"""

import pytest

try:
    import hypothesis.strategies as st
    from hypothesis import given, settings
except ImportError:  # minimal images: seeded deterministic fallback
    from repro.testing.hypothesis_compat import given, settings, st

from repro.config import (INPUT_SHAPES, SINGLE_DEVICE_MESH, SINGLE_POD_MESH,
                          MULTI_POD_MESH, TPU_V5E, HardwareSpec, TrainConfig)
from repro.configs import ARCH_IDS, get_config
from repro.core.memory import estimate_memory
from repro.core.planner import PlanCompiler, compile_plan
from repro.core.sharding import spec_for
from repro.core.strategies import PlanConfig, Strategy
from repro.kernels import ops


def test_single_device_gets_local_plan():
    cfg = get_config("yi-6b-smoke")
    plan = compile_plan(cfg, INPUT_SHAPES["train_4k"], SINGLE_DEVICE_MESH)
    assert plan.config.strategy == Strategy.LOCAL


def test_small_model_stays_data_parallel():
    """Paper-faithful behaviour: when replicated weights fit, SystemML's
    data-parallel plan is chosen (cheapest in the lattice)."""
    cfg = get_config("whisper-medium")
    plan = compile_plan(cfg, INPUT_SHAPES["long_500k"], SINGLE_POD_MESH)
    assert plan.config.strategy in (Strategy.DATA_PARALLEL, Strategy.DP_TP)


def test_huge_model_escalates():
    cfg = get_config("llama3-405b")
    plan = compile_plan(cfg, INPUT_SHAPES["train_4k"], SINGLE_POD_MESH)
    assert plan.config.strategy == Strategy.FSDP_TP
    assert plan.config.params_over_data
    assert plan.config.opt_state_dtype == "bfloat16"  # plan-chosen compression


def test_force_strategy():
    cfg = get_config("llama3-405b")
    t = TrainConfig(force_strategy="data_parallel")
    plan = compile_plan(cfg, INPUT_SHAPES["train_4k"], SINGLE_POD_MESH, t)
    assert plan.config.strategy == Strategy.DATA_PARALLEL


def test_moe_gets_expert_parallel():
    cfg = get_config("qwen3-moe-235b-a22b")
    plan = compile_plan(cfg, INPUT_SHAPES["train_4k"], SINGLE_POD_MESH)
    assert plan.config.expert_parallel


def test_long_context_gets_window_variant():
    cfg = get_config("yi-6b")
    plan = compile_plan(cfg, INPUT_SHAPES["long_500k"], SINGLE_POD_MESH)
    assert plan.config.attention_variant == "window"


def test_ssm_has_no_attention_variant():
    cfg = get_config("mamba2-1.3b")
    plan = compile_plan(cfg, INPUT_SHAPES["long_500k"], SINGLE_POD_MESH)
    assert plan.config.attention_variant == "none"


def test_multi_pod_batch_axes_include_pod():
    cfg = get_config("granite-8b")
    plan = compile_plan(cfg, INPUT_SHAPES["train_4k"], MULTI_POD_MESH)
    assert "pod" in plan.config.batch_axes


@pytest.mark.parametrize("arch", ARCH_IDS)
@pytest.mark.parametrize("shape", list(INPUT_SHAPES))
def test_every_combo_produces_a_plan(arch, shape):
    cfg = get_config(arch)
    plan = compile_plan(cfg, INPUT_SHAPES[shape], SINGLE_POD_MESH)
    assert plan.memory is not None and plan.cost is not None
    assert plan.explain()  # EXPLAIN renders


# ---------------------------------------------------------------------------
# property tests
# ---------------------------------------------------------------------------


@given(budget_gib=st.integers(min_value=4, max_value=256))
@settings(max_examples=20, deadline=None)
def test_bigger_budget_never_picks_more_distributed_plan(budget_gib):
    """Monotonicity: growing the memory budget can only move the chosen
    strategy *earlier* in the lattice (SystemML: more driver memory ->
    more single-node plans)."""
    cfg = get_config("phi3-medium-14b")
    shape = INPUT_SHAPES["train_4k"]
    hw_small = HardwareSpec(hbm_bytes=budget_gib * 1024**3)
    hw_big = HardwareSpec(hbm_bytes=2 * budget_gib * 1024**3)
    p_small = PlanCompiler(hw_small).compile(cfg, shape, SINGLE_POD_MESH)
    p_big = PlanCompiler(hw_big).compile(cfg, shape, SINGLE_POD_MESH)
    assert p_big.config.strategy.order <= p_small.config.strategy.order


@given(st.sampled_from(ARCH_IDS), st.sampled_from(list(INPUT_SHAPES)))
@settings(max_examples=40, deadline=None)
def test_memory_estimate_positive_and_fsdp_smaller(arch, shape_name):
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    mesh = SINGLE_POD_MESH
    t = TrainConfig()
    dp = PlanConfig(strategy=Strategy.DATA_PARALLEL, batch_axes=("data",))
    fsdp = dp.replace(strategy=Strategy.FSDP_TP, tensor_parallel=True,
                      params_over_data=True,
                      expert_parallel=cfg.num_experts > 0)
    m_dp = estimate_memory(cfg, shape, mesh, dp, t, TPU_V5E)
    m_fsdp = estimate_memory(cfg, shape, mesh, fsdp, t, TPU_V5E)
    assert m_dp.total > 0 and m_fsdp.total > 0
    assert m_fsdp.per_device["params"] < m_dp.per_device["params"]


@given(
    shape=st.tuples(st.sampled_from([16, 64, 128, 4096]),
                    st.sampled_from([16, 32, 4096, 51865])),
    tp=st.booleans(), fsdp=st.booleans(),
)
@settings(max_examples=50, deadline=None)
def test_spec_for_valid(shape, tp, fsdp):
    """Sharding rules never assign one mesh axis twice and never produce a
    non-divisible split."""
    plan = PlanConfig(strategy=Strategy.DP_TP, batch_axes=("data",),
                      tensor_parallel=tp, params_over_data=fsdp)
    spec = spec_for(shape, ("ffn", "embed"), plan, SINGLE_POD_MESH, "param")
    used = []
    for i, entry in enumerate(spec):
        if entry is None:
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        for ax in axes:
            assert ax not in used, spec
            used.append(ax)
        size = 1
        for ax in axes:
            size *= dict(zip(SINGLE_POD_MESH.axis_names, SINGLE_POD_MESH.shape))[ax]
        assert shape[i] % size == 0, (shape, spec)


def test_server_on_tpu_refuses_over_budget_plan(monkeypatch):
    """The planner still emits an over-budget plan, with its warning note,
    for the analytic dry-run; a server on a TPU refuses to run it."""
    from repro.core.planner import OVER_HBM_BUDGET
    from repro.runtime import serve_loop
    from repro.runtime.engine_config import EngineConfig

    tiny = HardwareSpec(hbm_bytes=1024)
    srv = EngineConfig().build_server(get_config("yi-6b-smoke"), hw=tiny)
    assert OVER_HBM_BUDGET in srv.decode_entry(1, 32).plan.config.notes
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    with pytest.raises(serve_loop.PlanOverBudgetError, match="HBM budget"):
        srv.decode_entry(2, 64)


def test_server_hardware_comes_from_device_kind(monkeypatch):
    from repro.config import TPU_SPECS, hardware_for
    from repro.runtime import serve_loop

    assert serve_loop.device_hardware() is TPU_V5E       # off TPU
    assert hardware_for("TPU v5 lite") is TPU_SPECS["TPU v5 lite"]
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    with pytest.raises(ValueError, match="no HardwareSpec"):
        serve_loop.device_hardware()                     # kind "cpu"
