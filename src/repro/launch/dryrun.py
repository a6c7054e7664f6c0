import os
os.environ["JAX_PLATFORMS"] = "cpu"  # placeholder host devices, never the chip
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run (deliverable e).

For every (architecture x input shape x mesh) combination: compile the
planner-chosen execution plan via ``jax.jit(...).lower(...).compile()`` on
the production mesh built from 512 placeholder host devices, then extract

  * ``compiled.memory_analysis()``  — proves the plan fits / how close
  * ``compiled.cost_analysis()``    — XLA's raw (loop-body-once) numbers
  * call-graph-weighted HLO cost    — flops / HBM bytes / collective bytes
                                      per chip per step (launch.hlo_analysis)

and writes one JSON record per combo under ``experiments/dryrun/``.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-6b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--jobs N]
  PYTHONPATH=src python -m repro.launch.dryrun --arch X --shape Y \
      --force-strategy data_parallel        # paper-faithful baseline
"""

import argparse
import dataclasses
import json
import sys
import time
import traceback
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.config import (INPUT_SHAPES, TPU_V5E, InputShape, MeshConfig,
                          ModelConfig, TrainConfig)
from repro.configs import ARCH_IDS, get_config
from repro.core.cost import model_flops_per_step, roofline_terms
from repro.core.planner import compile_plan
from repro.core.sharding import spec_for, tree_specs
from repro.launch import hlo_analysis
from repro.launch.mesh import make_production_mesh, mesh_cfg_for
from repro.models.model import build_model
from repro.runtime.serve_loop import cache_shardings, make_decode_step, make_prefill
from repro.runtime.train_loop import (make_train_step, opt_state_specs,
                                      train_shardings, batch_specs)

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


def batch_input_specs(cfg: ModelConfig, shape: InputShape, dtype=jnp.bfloat16):
    b, s = shape.global_batch, shape.seq_len
    if shape.kind == "decode":
        specs = {"tokens": jax.ShapeDtypeStruct((b, 1), jnp.int32)}
        return specs
    specs = {
        "tokens": jax.ShapeDtypeStruct((b, s), jnp.int32),
    }
    if shape.kind == "train":
        specs["targets"] = jax.ShapeDtypeStruct((b, s), jnp.int32)
    if cfg.frontend == "vision":
        specs["patch_embeds"] = jax.ShapeDtypeStruct(
            (b, cfg.num_frontend_tokens, cfg.d_model), dtype)
    if cfg.is_encdec:
        specs["frames"] = jax.ShapeDtypeStruct((b, cfg.encoder_seq, cfg.d_model), dtype)
    return specs


def lower_combo(arch: str, shape_name: str, *, multi_pod: bool = False,
                force_strategy: Optional[str] = None,
                train_cfg: TrainConfig = TrainConfig(),
                plan_override=None):
    """Lower + compile one combination; returns (record, compiled, plan)."""
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    mesh_cfg = mesh_cfg_for(multi_pod=multi_pod)
    mesh = make_production_mesh(multi_pod=multi_pod)
    if force_strategy:
        train_cfg = dataclasses.replace(train_cfg, force_strategy=force_strategy)
    plan = plan_override or compile_plan(cfg, shape, mesh_cfg, train_cfg)
    model = build_model(cfg, dtype=jnp.bfloat16)

    t0 = time.time()
    with mesh:
        if shape.kind == "train":
            lowered = _lower_train(model, plan, mesh, mesh_cfg, shape, train_cfg)
        elif shape.kind == "prefill":
            lowered = _lower_prefill(model, plan, mesh, mesh_cfg, shape)
        else:
            lowered = _lower_decode(model, plan, mesh, mesh_cfg, shape)
        compiled = lowered.compile()
    compile_s = time.time() - t0

    mem = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    hlo = hlo_analysis.analyze(compiled.as_text())
    chips = mesh_cfg.num_devices
    mf = model_flops_per_step(cfg, shape)
    terms = roofline_terms(hlo.flops, hlo.hbm_bytes, hlo.collective_bytes,
                           chips, TPU_V5E, model_flops=mf, per_chip=True)
    record = {
        "arch": arch,
        "shape": shape_name,
        "mesh": list(mesh_cfg.shape),
        "multi_pod": multi_pod,
        "strategy": plan.config.strategy.value,
        "plan_notes": list(plan.config.notes),
        "plan": {
            "batch_axes": list(plan.config.batch_axes),
            "seq_axes": list(plan.config.seq_axes),
            "tensor_parallel": plan.config.tensor_parallel,
            "params_over_data": plan.config.params_over_data,
            "expert_parallel": plan.config.expert_parallel,
            "opt_state_dtype": plan.config.opt_state_dtype,
            "microbatches": plan.config.microbatches,
            "seq_shard_checkpoints": plan.config.seq_shard_checkpoints,
            "attention_variant": plan.config.attention_variant,
            "cache_batch_axes": list(plan.config.cache_batch_axes),
            "cache_heads_over_model": plan.config.cache_heads_over_model,
            "cache_seq_axes": list(plan.config.cache_seq_axes),
        },
        "compile_seconds": compile_s,
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_estimate_bytes": mem.argument_size_in_bytes
            + mem.temp_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes,
            "hbm_budget": TPU_V5E.hbm_bytes,
        },
        "xla_cost_analysis": {k: float(v) for k, v in ca.items()
                              if isinstance(v, (int, float))
                              and ("flops" in k or "bytes accessed" == k)},
        "hlo_cost": hlo.to_dict(),
        "roofline": {
            "compute_s": terms.compute_s,
            "memory_s": terms.memory_s,
            "collective_s": terms.collective_s,
            "dominant": terms.dominant,
            "model_flops_global": mf,
            "model_flops_per_chip": mf / chips,
            "useful_flops_ratio": (mf / chips) / hlo.flops if hlo.flops else 0.0,
            "step_time_lower_bound_s": terms.step_time_s,
        },
        "planner_estimate": dict(plan.memory.per_device),
        "planner_cost": {
            "compute_s": plan.cost.compute_s,
            "memory_s": plan.cost.memory_s,
            "collective_s": plan.cost.collective_s,
        },
    }
    return record, compiled, plan


def _scalar_shard(mesh):
    return NamedSharding(mesh, P())


def _lower_train(model, plan, mesh, mesh_cfg, shape, train_cfg):
    (pspecs, _, pshard), (ospecs, _, oshard) = train_shardings(
        model, plan.config, mesh_cfg, train_cfg, mesh)
    bspecs = batch_input_specs(model.cfg, shape, model.dtype)
    bparts = batch_specs(bspecs, plan.config, mesh_cfg)
    bshard = {k: NamedSharding(mesh, v) for k, v in bparts.items()}
    step_fn = make_train_step(model, plan.config, mesh_cfg, train_cfg)
    metric_shard = {"xent": _scalar_shard(mesh), "aux": _scalar_shard(mesh),
                    "loss": _scalar_shard(mesh), "grad_norm": _scalar_shard(mesh)}
    jitted = jax.jit(
        step_fn,
        in_shardings=(pshard, oshard, bshard, _scalar_shard(mesh)),
        out_shardings=(pshard, oshard, metric_shard),
        donate_argnums=(0, 1),
    )
    step_spec = jax.ShapeDtypeStruct((), jnp.int32)
    return jitted.lower(pspecs, ospecs, bspecs, step_spec)


def _lower_prefill(model, plan, mesh, mesh_cfg, shape):
    pspecs = model.param_specs()
    pparts = tree_specs(pspecs, model.param_axes(), plan.config, mesh_cfg, "param")
    pshard = jax.tree.map(lambda sp: NamedSharding(mesh, sp), pparts,
                          is_leaf=lambda x: isinstance(x, P))
    bspecs = batch_input_specs(model.cfg, shape, model.dtype)
    bparts = batch_specs(bspecs, plan.config, mesh_cfg)
    bshard = {k: NamedSharding(mesh, v) for k, v in bparts.items()}
    fn = make_prefill(model, plan.config, mesh_cfg)
    jitted = jax.jit(fn, in_shardings=(pshard, bshard))
    return jitted.lower(pspecs, bspecs)


def _lower_decode(model, plan, mesh, mesh_cfg, shape):
    pspecs = model.param_specs()
    pparts = tree_specs(pspecs, model.param_axes(), plan.config, mesh_cfg, "param")
    pshard = jax.tree.map(lambda sp: NamedSharding(mesh, sp), pparts,
                          is_leaf=lambda x: isinstance(x, P))
    cspecs, _, cshard = cache_shardings(
        model, shape.global_batch, shape.seq_len, plan.config, mesh_cfg, mesh)
    tspec = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
    tshard = NamedSharding(
        mesh, spec_for((shape.global_batch, 1), ("batch", None),
                       plan.config, mesh_cfg, "act"))
    fn = make_decode_step(model, plan.config, mesh_cfg)
    jitted = jax.jit(
        fn,
        in_shardings=(pshard, cshard, tshard, _scalar_shard(mesh)),
        out_shardings=(None, cshard),
        donate_argnums=(1,),
    )
    pos_spec = jax.ShapeDtypeStruct((), jnp.int32)
    return jitted.lower(pspecs, cspecs, tspec, pos_spec)


# ---------------------------------------------------------------------------


def run_one(arch, shape_name, multi_pod, force_strategy=None, out_dir=OUT_DIR):
    tag = f"{arch}_{shape_name}_{'2pod' if multi_pod else '1pod'}"
    if force_strategy:
        tag += f"_{force_strategy}"
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, tag + ".json")
    try:
        record, compiled, plan = lower_combo(
            arch, shape_name, multi_pod=multi_pod,
            force_strategy=force_strategy)
        record["ok"] = True
    except Exception as e:  # noqa: BLE001 — recorded as a dry-run failure
        record = {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
                  "ok": False, "error": f"{type(e).__name__}: {e}",
                  "traceback": traceback.format_exc()[-4000:]}
    with open(path, "w") as f:
        json.dump(record, f, indent=1)
    status = "OK " if record.get("ok") else "FAIL"
    peak = record.get("memory", {}).get("peak_estimate_bytes", 0) / 2**30
    dom = record.get("roofline", {}).get("dominant", "?")
    print(f"[{status}] {tag:60s} peak={peak:7.2f}GiB dominant={dom} "
          f"strategy={record.get('strategy', '?')}", flush=True)
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS + ["all"], default=None)
    ap.add_argument("--shape", choices=list(INPUT_SHAPES) + ["all"], default="all")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true", help="all archs, shapes, meshes")
    ap.add_argument("--force-strategy", default=None)
    ap.add_argument("--out", default=OUT_DIR)
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    archs = ARCH_IDS if (args.all or args.arch in (None, "all")) else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or args.shape == "all") else [args.shape]
    meshes = [False, True] if (args.all or args.both_meshes) else [args.multi_pod]

    failures = 0
    for mp in meshes:
        for arch in archs:
            for shape_name in shapes:
                tag = f"{arch}_{shape_name}_{'2pod' if mp else '1pod'}"
                if args.force_strategy:
                    tag += f"_{args.force_strategy}"
                path = os.path.join(args.out, tag + ".json")
                if args.skip_existing and os.path.exists(path):
                    with open(path) as f:
                        if json.load(f).get("ok"):
                            print(f"[SKIP] {tag}", flush=True)
                            continue
                rec = run_one(arch, shape_name, mp, args.force_strategy, args.out)
                failures += 0 if rec.get("ok") else 1
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
