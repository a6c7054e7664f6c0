"""Kernel micro-benchmarks (paper §3 "Native BLAS Exploitation"/"GPU
Backend") plus the PR-8 ``paged_decode`` scenario: end-to-end decode-step
time with the plan-selectable paged-attention operator vs the legacy
gather materialization, across context lengths and page sizes.

On this CPU container the Pallas path runs interpreted (not timed); we
time the XLA fallback operator — for ``paged`` that is
:func:`repro.kernels.paged_attention.paged_attention_xla`, which reads the
flat slot stack once and contracts grouped GQA einsums directly, where the
gather path materializes gathered K/V *and* their ``q_per_kv``-repeated
expansions every step (≈ ``(2 + 2g)x`` cache traffic). The same traffic
asymmetry is what the analytic cost model banks on when the plan compiler
picks the kernel per bucket, so the measured ratio doubles as a check on
the selection rule.

Acceptance targets (CI-enforced under ``--smoke``):

- paged decode step >= 1.5x faster than gather at the long-context cells;
- logits equivalence paged == gather == ref at every measured cell;
- zero recompiles: each jitted step traces exactly once (trace counter).

    PYTHONPATH=src python benchmarks/bench_kernels.py [--smoke]

Prints ``name,us_per_call,derived`` CSV rows (harness contract), writes the
full result set to ``BENCH_kernels.json`` (the perf-trajectory artifact CI
uploads), and exits non-zero below the gate.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.config import VMEM_LIMIT_BYTES
from repro.configs import get_config
from repro.kernels import ref
from repro.models.model import build_model
from repro.runtime.kv_cache import KVCachePool

try:
    from benchmarks.bench_meta import scenario_meta
except ImportError:  # run as a script from the benchmarks/ directory
    from bench_meta import scenario_meta

TARGET_SPEEDUP = 1.5
RESULTS_JSON = "BENCH_kernels.json"
KEY = jax.random.PRNGKey(0)


def _time(fn, *args, reps=10):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6


# ---------------------------------------------------------------------------
# micro-kernels (paper §3): structural roofline of the Pallas blocks
# ---------------------------------------------------------------------------


def _micro_rows():
    rows = []
    key = jax.random.PRNGKey(0)

    # matmul 1024^3, MXU tile 128: per-block VMEM = bm*bk + bk*bn + bm*bn(f32)
    a = jax.random.normal(key, (1024, 1024), jnp.bfloat16)
    b = jax.random.normal(key, (1024, 1024), jnp.bfloat16)
    us = _time(jax.jit(ref.matmul_ref), a, b)
    vmem = (128 * 128 * 2) * 2 + 128 * 128 * 4
    ai = (2 * 1024**3) / (2 * 2 * 1024 * 1024)
    rows.append(f"kernel_matmul_1024,{us:.1f},vmem_block={vmem};intensity={ai:.0f};"
                f"vmem_ok={vmem <= VMEM_LIMIT_BYTES}")

    # flash attention 2x8x1024x64
    q = jax.random.normal(key, (2, 8, 1024, 64), jnp.bfloat16)
    us = _time(jax.jit(lambda q: ref.attention_ref(q, q, q)), q)
    vmem = (128 * 64 * 2) * 3 + 128 * 128 * 4 + 128 * 64 * 4
    rows.append(f"kernel_flash_attn_1k,{us:.1f},vmem_block={vmem};"
                f"vmem_ok={vmem <= VMEM_LIMIT_BYTES}")

    # ssd scan: mamba2-like (chunked BLAS-3 form)
    B, S, H, P, N = 2, 512, 8, 64, 128
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (B, S, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    av = -jnp.exp(jax.random.normal(ks[2], (H,)))
    bm = jax.random.normal(ks[3], (B, S, N))
    cm = jax.random.normal(ks[4], (B, S, N))
    d = jnp.ones((H,))
    seq = jax.jit(lambda *a: ref.ssd_ref(*a)[0])
    chk = jax.jit(lambda *a: ref.ssd_chunked_ref(*a, chunk=64)[0])
    us_seq = _time(seq, x, dt, av, bm, cm, d, reps=3)
    us_chk = _time(chk, x, dt, av, bm, cm, d, reps=3)
    rows.append(f"kernel_ssd_sequential,{us_seq:.1f},form=scan")
    rows.append(f"kernel_ssd_chunked,{us_chk:.1f},form=blas3;"
                f"speedup={us_seq / us_chk:.2f}x")

    # conv2d im2col (the paper's lowering)
    x = jax.random.normal(key, (8, 16, 32, 32), jnp.float32)
    w = jax.random.normal(key, (32, 16, 3, 3), jnp.float32)
    us = _time(jax.jit(lambda x, w: ref.conv2d_ref(x, w, 1, 1)), x, w)
    rows.append(f"kernel_conv2d_im2col,{us:.1f},lowering=im2col")
    return rows


# ---------------------------------------------------------------------------
# paged_decode scenario (PR 8): plan-selectable operator vs legacy gather
# ---------------------------------------------------------------------------


def _counted_step(model, page, seq, kernel):
    """Jitted decode step with the kernel baked in (exactly what
    ``serve_loop.make_decode_step`` produces) plus a trace counter: the
    closure body runs once per XLA trace, so ``traces["n"]`` past the
    warmup call counts spurious recompiles."""
    traces = {"n": 0}

    def step(params, cache, tok, pos, tables):
        traces["n"] += 1
        return model.decode_step(params, cache, tok, pos, tables=tables,
                                 page=page, seq_len=seq,
                                 decode_kernel=kernel)

    return jax.jit(step), traces


def _paged_cell(cfg, b, ctx, page, reps):
    """One (batch, context, page) cell: identical paged arena, per-kernel
    jitted steps, timed back-to-back with a logits-equivalence check."""
    model = build_model(cfg, dtype=jnp.float32)
    params = model.init_params(KEY)
    prompt = 8  # timing is depth-independent: both operators walk all slots
    toks = jax.random.randint(jax.random.PRNGKey(1), (b, prompt), 0,
                              cfg.vocab_size)
    lengths = jnp.full((b,), prompt, jnp.int32)
    logits, dense = model.prefill(params, toks, lengths=lengths,
                                  cache_len=ctx)
    pool = KVCachePool(model, page_size=page)
    arena = pool.acquire(b, ctx)
    rows = pool.admit_request_rows(arena, b, prompt=prompt, span=prompt + 4)
    pool.write_rows(arena, rows, dense)
    for r in rows:
        pool.ensure_decode_slots(arena, [r], prompt)
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
    pos = lengths

    out, us, traces = {}, {}, {}
    for kern in ("gather", "paged", "ref"):
        step, tr = _counted_step(model, page, ctx, kern)
        out[kern], _ = step(params, arena.cache, tok, pos, arena.tables)
        jax.block_until_ready(out[kern])
        if kern != "ref":  # ref is the oracle, not a production operator
            us[kern] = _time(lambda *a: step(*a)[0], params, arena.cache,
                             tok, pos, arena.tables, reps=reps)
        traces[kern] = tr

    equal = all(
        np.allclose(np.asarray(out[k]), np.asarray(out["gather"]),
                    rtol=1e-5, atol=1e-5) for k in ("paged", "ref"))
    recompiles = sum(t["n"] - 1 for t in traces.values())
    return {
        "batch": b, "ctx": ctx, "page": page,
        "paged_us": us["paged"], "gather_us": us["gather"],
        "speedup": us["gather"] / us["paged"],
        "logits_equal": bool(equal), "recompiles": recompiles,
    }


def _paged_cells(smoke: bool):
    """(batch, ctx, page, reps, gated) sweep. The gated rows are the
    long-context cells — where the gather path's materialized expansions
    dominate the step and the plan compiler flips to ``paged``."""
    if smoke:
        return [(2, 256, 64, 20, False),
                (4, 2048, 64, 10, True),
                (4, 2048, 16, 10, True)]
    return [(2, 256, 64, 30, False),
            (4, 1024, 64, 20, False),
            (4, 4096, 64, 10, True),
            (4, 4096, 16, 10, True),
            (8, 4096, 64, 5, True)]


def _paged_rows(smoke: bool, arch: str):
    cfg = get_config(arch)
    cells, rows = [], []
    for b, ctx, page, reps, gated in _paged_cells(smoke):
        cell = _paged_cell(cfg, b, ctx, page, reps)
        cell["gated"] = gated
        cells.append(cell)
        rows.append(
            f"kernel_paged_decode_b{b}_c{ctx}_p{page},{cell['paged_us']:.1f},"
            f"gather_us={cell['gather_us']:.1f};"
            f"speedup={cell['speedup']:.2f}x;"
            f"logits_equal={int(cell['logits_equal'])};"
            f"recompiles={cell['recompiles']};gated={int(gated)}")
    return rows, cells


def run(smoke: bool = True, arch: str = "yi-6b-smoke"):
    """Harness entry point (benchmarks/run.py contract): CSV rows only."""
    return _micro_rows() + _paged_rows(smoke, arch)[0]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="small sweep for CI (seconds, not minutes)")
    ap.add_argument("--arch", default="yi-6b-smoke")
    args = ap.parse_args(argv)

    print("name,us_per_call,derived")
    for row in _micro_rows():
        print(row, flush=True)
    rows, cells = _paged_rows(args.smoke, args.arch)
    for row in rows:
        print(row, flush=True)

    gated = [c for c in cells if c["gated"]]
    worst = min(c["speedup"] for c in gated)
    equal = all(c["logits_equal"] for c in cells)
    recompiles = sum(c["recompiles"] for c in cells)
    ok = True
    if worst < TARGET_SPEEDUP:
        print(f"FAIL: paged decode speedup {worst:.2f}x < "
              f"{TARGET_SPEEDUP}x target at long-context cells",
              file=sys.stderr)
        ok = False
    if not equal:
        print("FAIL: paged/ref logits diverged from the gather path",
              file=sys.stderr)
        ok = False
    if recompiles:
        print(f"FAIL: decode steps burned {recompiles} extra traces "
              f"(kernel choice is static per plan; steps must trace once)",
              file=sys.stderr)
        ok = False
    with open(RESULTS_JSON, "w") as f:
        json.dump({
            "bench": "kernels", "smoke": args.smoke, "arch": args.arch,
            "meta": scenario_meta(args.arch),
            "rows": rows, "ok": ok,
            "gates": {
                "paged_decode_speedup": {"value": worst,
                                         "target": TARGET_SPEEDUP},
                "logits_equal": {"value": bool(equal), "target": True},
                "recompiles": {"value": recompiles, "target": 0},
            },
            "cells": cells,
        }, f, indent=2)
        f.write("\n")
    print(f"# results -> {RESULTS_JSON}", file=sys.stderr)
    return 0 if ok else 1


if __name__ == "__main__":
    enable_compile_cache()
    sys.exit(main())
