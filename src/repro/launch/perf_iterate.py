import os
os.environ["JAX_PLATFORMS"] = "cpu"  # placeholder host devices, never the chip
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""§Perf hillclimbing harness.

For a chosen (arch x shape) pair, lower+compile a *series* of plan variants
(paper-faithful baseline -> planner default -> manual hypotheses) and record
the three roofline terms for each, so EXPERIMENTS.md §Perf can show the
hypothesis -> change -> before -> after chain.

    PYTHONPATH=src python -m repro.launch.perf_iterate --pair llama_train
"""

import argparse
import json

from repro.config import INPUT_SHAPES, TPU_V5E, TrainConfig
from repro.configs import get_config
from repro.core.cost import analytic_cost
from repro.core.memory import estimate_memory
from repro.core.planner import compile_plan
from repro.core.strategies import ExecutionPlan
from repro.launch.dryrun import lower_combo
from repro.launch.mesh import mesh_cfg_for

OUT = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                   "experiments", "perf")


def variants_llama_train():
    """llama3-405b x train_4k: worst memory/roofline pair."""
    arch, shape = "llama3-405b", "train_4k"
    cfg = get_config(arch)
    mesh_cfg = mesh_cfg_for()
    base_plan = compile_plan(cfg, INPUT_SHAPES[shape], mesh_cfg).config
    out = [
        ("paper_faithful_dp", dict(force_strategy="data_parallel")),
        ("planner_default", dict()),
        ("micro8", dict(plan_override_cfg=base_plan.replace(microbatches=8))),
        ("micro32", dict(plan_override_cfg=base_plan.replace(microbatches=32))),
        ("no_seq_ckpt", dict(plan_override_cfg=base_plan.replace(
            seq_shard_checkpoints=False))),
        ("fp32_opt", dict(plan_override_cfg=base_plan.replace(
            opt_state_dtype="float32"))),
    ]
    return arch, shape, out


def variants_qwen3_train():
    """qwen3-moe x train_4k: most collective-bound (EP all-to-all)."""
    arch, shape = "qwen3-moe-235b-a22b", "train_4k"
    cfg = get_config(arch)
    mesh_cfg = mesh_cfg_for()
    base = compile_plan(cfg, INPUT_SHAPES[shape], mesh_cfg).config
    return arch, shape, [
        ("paper_faithful_dp", dict(force_strategy="data_parallel")),
        ("planner_default", dict()),
        ("no_expert_parallel", dict(plan_override_cfg=base.replace(
            expert_parallel=False))),
        ("micro4", dict(plan_override_cfg=base.replace(microbatches=4))),
        ("micro8", dict(plan_override_cfg=base.replace(microbatches=8))),
    ]


def variants_yi_prefill():
    """yi-6b x prefill_32k: the paper's batch-scoring scenario."""
    arch, shape = "yi-6b", "prefill_32k"
    cfg = get_config(arch)
    mesh_cfg = mesh_cfg_for()
    base = compile_plan(cfg, INPUT_SHAPES[shape], mesh_cfg).config
    return arch, shape, [
        ("paper_faithful_dp", dict(force_strategy="data_parallel")),
        ("planner_default", dict()),
        ("context_parallel", dict(plan_override_cfg=base.replace(
            seq_axes=("model",)))),
        ("no_tensor_parallel", dict(plan_override_cfg=base.replace(
            tensor_parallel=False))),
    ]


PAIRS = {
    "llama_train": variants_llama_train,
    "qwen3_train": variants_qwen3_train,
    "yi_prefill": variants_yi_prefill,
}


def run_pair(name: str):
    arch, shape, variants = PAIRS[name]()
    os.makedirs(OUT, exist_ok=True)
    results = []
    for label, kw in variants:
        plan_override = None
        if "plan_override_cfg" in kw:
            cfg = get_config(arch)
            shp = INPUT_SHAPES[shape]
            mesh_cfg = mesh_cfg_for()
            pcfg = kw["plan_override_cfg"]
            plan_override = ExecutionPlan(
                model=cfg, shape=shp, mesh=mesh_cfg, config=pcfg,
                memory=estimate_memory(cfg, shp, mesh_cfg, pcfg, TrainConfig(), TPU_V5E),
                cost=analytic_cost(cfg, shp, mesh_cfg, pcfg, TPU_V5E),
            )
        try:
            rec, _, _ = lower_combo(
                arch, shape,
                force_strategy=kw.get("force_strategy"),
                plan_override=plan_override)
            rf, mem = rec["roofline"], rec["memory"]
            row = {
                "label": label,
                "compute_s": rf["compute_s"],
                "memory_s": rf["memory_s"],
                "collective_s": rf["collective_s"],
                "dominant": rf["dominant"],
                "step_lower_bound_s": rf["step_time_lower_bound_s"],
                "useful_flops": rf["useful_flops_ratio"],
                "peak_gib": mem["peak_estimate_bytes"] / 2**30,
                "collectives_gib": {k: v / 2**30 for k, v in
                                    rec["hlo_cost"]["collectives"].items()},
            }
        except Exception as e:  # noqa: BLE001
            row = {"label": label, "error": f"{type(e).__name__}: {e}"}
        results.append(row)
        print(json.dumps(row), flush=True)
    with open(os.path.join(OUT, f"{name}.json"), "w") as f:
        json.dump({"arch": arch, "shape": shape, "results": results}, f, indent=1)
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--pair", choices=list(PAIRS) + ["all"], default="all")
    args = ap.parse_args()
    pairs = list(PAIRS) if args.pair == "all" else [args.pair]
    for p in pairs:
        print(f"== {p}")
        run_pair(p)


if __name__ == "__main__":
    main()
