#!/usr/bin/env python3
"""Chip smoke run: the serving stack's main path, end to end, on a TPU.

    python chip_smoke.py              # one chip: phases 1-3
    python chip_smoke.py --chips 4    # four chips: the sharded phase only

Phase 1 runs the three main-path Pallas kernels (flash prefill attention,
paged decode attention, SSD scan) at published widths through the
``repro.kernels.ops`` dispatcher and compares each with its ``ref.py``
oracle. Phase 2 serves yi-6b at its published config (32 layers, d_model
4096, bf16, random weights from a seed) through
``EngineConfig(...).build_server`` and its engine, the path
``python -m repro.launch.serve --scheduler`` takes. Phase 3 serves
mamba2-1.3b the same way, so the SSD kernel runs inside the model.
``--chips 4`` serves the same yi-6b requests on one chip and then through a
``PlanServer`` over every visible chip, in one process, and compares the
two.

Every phase asserts what it checks; any failure exits non-zero. The timings
printed are those of a smoke run and include compilation: they are not a
benchmark. The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``. Without a TPU,
or outside a checkout of the repository, the script exits non-zero and
prints no such line.
"""

from __future__ import annotations

import argparse
import gc
import json
import re
import sys
import time
from pathlib import Path

# What each phase runs. Widths are the published ones; only the request
# count and lengths are chosen for a short run.
YI, MAMBA = "yi-6b", "mamba2-1.3b"
# two context buckets (256, 1024) of three requests each
YI_CONTEXTS = (128, 160, 200, 600, 800, 1000)
MAMBA_CONTEXTS = (128, 500, 1000)
NEW_TOKENS = 16
FLASH = dict(b=1, h=32, hkv=4, s=2048, d=128)
PAGED = dict(b=8, hkv=4, g=8, d=128, page=64, sc=2048)
SSD = dict(b=1, s=2048, h=64, p=64, n=128, chunk=64)
# max |kernel - oracle| / max |oracle|: bf16 inputs and outputs, f32
# accumulation in both (a bf16 ulp is 2^-8 = 0.0039 relative)
KERNEL_TOL = 2e-2
# max |sharded - one chip| / max |one chip| over the prefill logits
SHARDED_TOL = 2e-2
SEED = 0


def _import_repo():
    src = Path(__file__).resolve().parent / "src"
    if not (src / "repro").is_dir():
        raise SystemExit(f"chip_smoke: no repro package under {src}; run "
                         "this script from a checkout of the repository")
    sys.path.insert(0, str(src))


def require_tpu(jax):
    """Print the device; refuse to run anywhere but a TPU."""
    devs = jax.devices()
    dev = devs[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devs)}", flush=True)
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found "
                         f"{dev.platform!r}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}


def kernels_in(lowered_text: str):
    """Names of the Pallas TPU kernels in a lowered program."""
    return sorted({m for line in lowered_text.splitlines()
                   if "tpu_custom_call" in line
                   for m in re.findall(r'kernel_name = "([^"]+)"', line)})


def require_kernels(where: str, names) -> None:
    if not names:
        raise AssertionError(f"{where}: no tpu_custom_call in the lowered "
                             "program")


class CompileLog:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events, while the ``with`` block runs."""

    def __init__(self, jax):
        self.monitoring = jax.monitoring
        self.seconds = 0.0
        self.cache_hits = 0

    def __enter__(self):
        self.monitoring.register_event_duration_secs_listener(self._duration)
        self.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc):
        self.monitoring.unregister_event_duration_listener(self._duration)
        self.monitoring.unregister_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def line(self) -> str:
        return (f"compile_s={self.seconds:.1f} "
                f"persistent_cache_hits={self.cache_hits}")


def _rel_err(got, want):
    import numpy as np
    a = np.asarray(got, np.float32)
    b = np.asarray(want, np.float32)
    if not (np.isfinite(a).all() and np.isfinite(b).all()):
        return float("inf"), float("inf")
    err = float(np.max(np.abs(a - b)))
    return err, err / max(float(np.max(np.abs(b))), 1e-30)


def _check_close(name, got, want, tol):
    err, rel = _rel_err(got, want)
    ok = rel <= tol
    print(f"  {name}: max_abs_err={err:.3e} rel_err={rel:.3e} "
          f"tol={tol:.0e} {'ok' if ok else 'FAIL'}", flush=True)
    if not ok:
        raise AssertionError(f"{name}: rel_err {rel:.3e} > {tol:.0e}")


# ---------------------------------------------------------------------------
# phase 1: kernels against their oracles
# ---------------------------------------------------------------------------


def kernel_phase(jax, log: CompileLog) -> None:
    import jax.numpy as jnp
    from repro.kernels import ops, ref

    print("phase 1: kernels vs ref.py oracles (bf16 in, f32 oracle)",
          flush=True)
    bf = jnp.bfloat16
    keys = iter(jax.random.split(jax.random.PRNGKey(SEED), 16))

    def normal(shape, dtype=bf):
        return jax.random.normal(next(keys), shape, jnp.float32).astype(dtype)

    def check(name, fn, oracle, args, tol=KERNEL_TOL):
        jitted = jax.jit(fn)
        require_kernels(name, kernels_in(jitted.lower(*args).as_text()))
        got = jitted(*args)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(oracle)(*args)
        _check_close(name, got, want, tol)

    f = FLASH
    q = normal((f["b"], f["h"], f["s"], f["d"]))
    k = normal((f["b"], f["hkv"], f["s"], f["d"]))
    v = normal((f["b"], f["hkv"], f["s"], f["d"]))
    check(f"flash_attention {f['b']}x{f['h']}x{f['s']}x{f['d']} causal",
          lambda q, k, v: ops.attention(q, k, v, causal=True),
          lambda q, k, v: ref.attention_ref(q, k, v, causal=True), (q, k, v))

    p = PAGED
    n_pages = p["sc"] // p["page"]
    n_phys = p["b"] * n_pages
    q = normal((p["b"], 1, p["hkv"] * p["g"], p["d"]))
    kc = normal((n_phys * p["page"], p["hkv"], p["d"]))
    vc = normal((n_phys * p["page"], p["hkv"], p["d"]))
    tables = jax.random.permutation(next(keys), n_phys).reshape(
        p["b"], n_pages).astype(jnp.int32)
    pos = jax.random.randint(next(keys), (p["b"],), 0, p["sc"], jnp.int32)
    check(f"paged_decode b={p['b']} hkv={p['hkv']} g={p['g']} "
          f"page={p['page']} slots={p['sc']}",
          lambda *a: ops.paged_attention(*a, page=p["page"], sc=p["sc"]),
          lambda *a: ref.paged_decode_ref(*a, page=p["page"], sc=p["sc"]),
          (q, kc, vc, tables, pos))

    s = SSD
    x = normal((s["b"], s["s"], s["h"], s["p"]))
    dt = jax.nn.softplus(normal((s["b"], s["s"], s["h"]), jnp.float32) - 2.0)
    a = -jnp.exp(normal((s["h"],), jnp.float32))
    bm = normal((s["b"], s["s"], s["n"]))
    cm = normal((s["b"], s["s"], s["n"]))
    d = jnp.ones((s["h"],), jnp.float32)
    check(f"ssd_scan b={s['b']} s={s['s']} h={s['h']} p={s['p']} "
          f"n={s['n']} chunk={s['chunk']}",
          lambda *args: ops.ssd(*args, chunk=s["chunk"]),
          lambda *args: ref.ssd_ref(*args)[0], (x, dt, a, bm, cm, d))
    print(f"  {log.line()}", flush=True)


# ---------------------------------------------------------------------------
# phases 2-3 (and the sharded phase): serving through the engine
# ---------------------------------------------------------------------------


def _step_kernels(jax, srv):
    """{(kind, batch, seq): kernel names} for every step the server
    compiled, read off each step lowered again at its bucket shape."""
    import jax.numpy as jnp
    sds = jax.ShapeDtypeStruct
    out = {}
    for key in srv.cache.keys():
        entry = srv.cache.get(key)
        b, s = key.batch_bucket, key.seq_bucket
        if key.kind == "prefill":
            args = (srv.params, {"tokens": sds((b, s), jnp.int32),
                                 "lengths": sds((b,), jnp.int32)})
        else:
            ent, _n, sc = srv.model.paged_cache_entries(b, s, srv.page_size)
            cache = {k: sds(sh, dt) for k, (sh, _ax, dt) in ent.items()}
            args = (srv.params, cache, sds((b, 1), jnp.int32),
                    sds((b,), jnp.int32),
                    sds((b, -(-sc // srv.page_size)), jnp.int32))
        out[(key.kind, b, s)] = kernels_in(
            entry.step_fn.lower(*args).as_text())
    return out


def serve_phase(jax, log: CompileLog, arch: str, contexts, *, mesh_cfg=None,
                want_kernels=("prefill", "decode")):
    """Serve one request per context (``NEW_TOKENS`` each) through the
    engine; returns the prefill logits and output tokens on the host."""
    import numpy as np
    from repro.configs import get_config
    from repro.runtime.engine import WallClock
    from repro.runtime.engine_config import EngineConfig
    from repro.runtime.serve_loop import ServeRequest

    cfg = get_config(arch)
    dev = jax.devices()[0]
    ecfg = EngineConfig(dtype="bfloat16", seed=SEED)
    t0 = time.perf_counter()
    srv = ecfg.build_server(cfg, mesh_cfg)
    jax.block_until_ready(srv.params)
    n_params = sum(x.size for x in jax.tree.leaves(srv.params))
    print(f"  {arch}: {cfg.num_layers} layers d_model={cfg.d_model} "
          f"{n_params / 1e9:.3f} B params bf16 on mesh "
          f"{dict(zip(srv.mesh_cfg.axis_names, srv.mesh_cfg.shape))} "
          f"init_s={time.perf_counter() - t0:.1f}", flush=True)

    prefill_logits = []
    run_prefill = srv.run_prefill

    def recording_prefill(entry, tokens=None, lengths=None):
        logits, kv = run_prefill(entry, tokens, lengths)
        prefill_logits.append(np.asarray(logits, np.float32))
        return logits, kv

    srv.run_prefill = recording_prefill
    eng = ecfg.build_engine(srv, clock=WallClock())
    handles = [eng.submit(ServeRequest(1, c, NEW_TOKENS)) for c in contexts]
    # time to first token on the host clock: until the tick that returned
    # the request's first token (its prefill included) had finished
    t0 = time.perf_counter()
    ttft = {}
    while not eng.idle:
        for ev in eng.step():
            if ev.index == 0 and ev.token is not None:
                ttft[ev.rid] = time.perf_counter() - t0
    wall = time.perf_counter() - t0

    tokens = []
    for c, h in zip(contexts, handles):
        rec = h.result
        got = np.asarray(rec["tokens"])
        if rec["finish_reason"] != "length" or got.shape != (1, NEW_TOKENS):
            raise AssertionError(
                f"{arch} ctx={c}: finished {rec['finish_reason']!r} with "
                f"tokens {got.shape}, want (1, {NEW_TOKENS})")
        tokens.append(got)
    bad = [i for i, lg in enumerate(prefill_logits)
           if not np.isfinite(lg).all()]
    if not prefill_logits or bad:
        raise AssertionError(f"{arch}: non-finite prefill logits in "
                             f"prefill calls {bad}")
    firsts = sorted(ttft.values())
    print(f"  {arch}: {len(handles)} requests, contexts {list(contexts)}, "
          f"{NEW_TOKENS} new tokens each: all finished, "
          f"{len(prefill_logits)} prefill calls with finite logits",
          flush=True)
    print(f"  {arch} smoke timings (include compilation; not a benchmark): "
          f"wall_s={wall:.2f} tokens_per_s={len(handles) * NEW_TOKENS / wall:.1f} "
          f"ttft_p50_s={firsts[len(firsts) // 2]:.3f} "
          f"ttft_max_s={firsts[-1]:.3f} {log.line()}",
          flush=True)
    print(f"  {srv.summary().split('  |')[0]}", flush=True)

    for (kind, b, s), names in sorted(_step_kernels(jax, srv).items()):
        print(f"  {kind} step {b}x{s}: kernels={names}", flush=True)
        if kind in want_kernels:
            require_kernels(f"{arch} {kind} step {b}x{s}", names)
    stats = dev.memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        print(f"  hbm peak_bytes_in_use={stats['peak_bytes_in_use'] / 1e9:.2f} "
              f"GB of {stats.get('bytes_limit', 0) / 1e9:.2f} GB "
              f"(device 0)", flush=True)
    return prefill_logits, tokens


def _free(jax):
    gc.collect()
    stats = jax.devices()[0].memory_stats() or {}
    if "bytes_in_use" in stats:
        print(f"  freed: bytes_in_use={stats['bytes_in_use'] / 1e9:.2f} GB "
              f"(device 0)", flush=True)


def sharded_phase(jax, log: CompileLog) -> None:
    """yi-6b on one chip, then over every visible chip; same requests."""
    import numpy as np
    from repro.config import MeshConfig

    n = len(jax.devices())
    print(f"sharded phase: {YI} on 1 chip, then on a {n}-chip mesh",
          flush=True)
    one_logits, one_tokens = serve_phase(
        jax, log, YI, YI_CONTEXTS, mesh_cfg=MeshConfig((1,), ("data",)))
    _free(jax)
    many_logits, many_tokens = serve_phase(jax, log, YI, YI_CONTEXTS)
    if len(one_logits) != len(many_logits):
        raise AssertionError(f"prefill calls differ: {len(one_logits)} on "
                             f"one chip, {len(many_logits)} on {n}")
    for i, (a, b) in enumerate(zip(many_logits, one_logits)):
        _check_close(f"prefill call {i} logits {a.shape}, {n} chips vs 1",
                     a, b, SHARDED_TOL)
    same = sum(int((a == b).sum()) for a, b in zip(many_tokens, one_tokens))
    total = sum(a.size for a in one_tokens)
    print(f"  greedy tokens equal on {same}/{total} positions "
          f"(random weights; near-ties may flip)", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded phase, over four chips")
    args = ap.parse_args(argv)

    import jax
    device = require_tpu(jax)
    if device["count"] != args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} but JAX sees "
                         f"{device['count']} device(s)")
    _import_repo()
    from repro.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    t0 = time.perf_counter()
    with CompileLog(jax) as log:
        if args.chips == 4:
            sharded_phase(jax, log)
        else:
            kernel_phase(jax, log)
            print(f"phase 2: {YI} served through EngineConfig.build_server "
                  "+ engine", flush=True)
            serve_phase(jax, log, YI, YI_CONTEXTS)
            _free(jax)
            print(f"phase 3: {MAMBA} served (SSD kernel in prefill)",
                  flush=True)
            serve_phase(jax, log, MAMBA, MAMBA_CONTEXTS,
                        want_kernels=("prefill",))
        print(f"total_s={time.perf_counter() - t0:.1f} {log.line()}",
              flush=True)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
