"""repro.analysis (PR 7): every lint rule vs. a seeded violation fixture
plus the clean-tree gate, the plan auditor's planted-violation self-test
and memory-statistics sandwich, EngineConfig validation errors, and the
runtime sanitizer — planted-corruption detection plus full engine/router
scenarios (submit / stream / cancel / EOS / drain / failover) run with
``sanitize=True`` across the serving families."""

import pytest

try:
    import hypothesis.strategies as st
    from hypothesis import given, settings
except ImportError:  # minimal images: seeded deterministic fallback
    from repro.testing.hypothesis_compat import given, settings, st

from repro.analysis.cost_audit import (audit_cell, check_explain_axes,
                                       check_selection_monotonic,
                                       trace_closure_certificate)
from repro.analysis.lint import DEFAULT_ROOTS, lint_paths, lint_source
from repro.analysis.matrix import merge_report, smoke_cells
from repro.analysis.sanitize import (SanitizeError, check_engine, check_pool,
                                     recount_live_bytes)
from repro.config import InputShape, MeshConfig
from repro.configs import get_config
from repro.core.plan_cache import BucketPolicy, bucket_pow2
from repro.core.planner import PlanCompiler
from repro.core.strategies import PLAN_AXES
from repro.runtime.engine_config import EngineConfig
from repro.runtime.serve_loop import ServeRequest

FAMILIES = ["yi-6b-smoke", "mamba2-1.3b-smoke", "recurrentgemma-2b-smoke"]


# ---------------------------------------------------------------------------
# invariant linter: each rule detects its seeded violation
# ---------------------------------------------------------------------------


def _rules(findings):
    return {f.rule for f in findings}


def test_lint_local_import_seeded():
    src = "def f():\n    import os\n    return os.getpid()\n"
    assert _rules(lint_source(src, "src/repro/core/x.py")) == {"local-import"}


def test_lint_init_cache_outside_pool_seeded():
    src = "def f(model):\n    return model.init_cache(4, 128)\n"
    found = lint_source(src, "src/repro/runtime/rogue.py")
    assert _rules(found) == {"init-cache-outside-pool"}
    # the module that defines the pool is blessed
    assert lint_source(src, "src/repro/runtime/kv_cache.py") == []


def test_lint_admission_outside_pool_seeded():
    src = "def f(pool, arena):\n    return pool.alloc_rows(arena, 2)\n"
    found = lint_source(src, "src/repro/runtime/rogue.py")
    assert _rules(found) == {"admission-outside-pool"}


def test_lint_rid_mint_seeded():
    src = ("def f(req):\n"
           "    req.rid = 7\n"
           "def g():\n"
           "    global _NEXT_RID\n"
           "    _NEXT_RID += 1\n")
    found = lint_source(src, "src/repro/runtime/rogue.py")
    assert _rules(found) == {"rid-mint"}
    assert len(found) >= 2  # both the .rid assign and the counter touch
    # serve_loop itself constructs rids
    assert lint_source(src, "src/repro/runtime/serve_loop.py") == []


def test_lint_tracer_host_sync_seeded():
    src = ("import numpy as np\n"
           "def step(x):\n"
           "    a = x.item()\n"
           "    b = float(x)\n"
           "    c = np.asarray(x)\n"
           "    return a, b, c\n")
    found = lint_source(src, "src/repro/models/rogue.py")
    assert _rules(found) == {"tracer-host-sync"}
    assert len(found) == 3
    # only tick-path modules are in scope: host-side code may materialize
    assert lint_source(src, "src/repro/runtime/metrics.py") == []


def test_lint_plan_cache_mutation_seeded():
    src = "def f(cache, key, plan):\n    cache._entries[key] = plan\n"
    found = lint_source(src, "src/repro/runtime/rogue.py")
    assert _rules(found) == {"plan-cache-mutation"}
    assert lint_source(src, "src/repro/core/plan_cache.py") == []


def test_lint_waiver_suppresses_finding():
    src = ("def f():\n"
           "    import os  # lint: allow-local-import\n"
           "    return os.getpid()\n")
    assert lint_source(src, "src/repro/core/x.py") == []


def test_lint_clean_tree_is_green():
    """The CI gate: zero findings over the shipped tree (satellite: every
    pre-existing violation was fixed or explicitly waived)."""
    found = lint_paths(DEFAULT_ROOTS)
    assert found == [], "\n".join(str(f) for f in found)


# ---------------------------------------------------------------------------
# plan auditor: planted violations + memory sandwich (slow: traces plans)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", FAMILIES)
def test_plan_audit_clean_cell_with_memory_bound(arch):
    """Zero findings per family on the clean tree, and the compile-time
    estimate sits inside [certified floor, reuse-free ceiling]."""
    from repro.analysis.plan_audit import audit_cell

    rec, findings = audit_cell(arch, "bfloat16", "decode", 1, 64)
    assert findings == [], "\n".join(str(f) for f in findings)
    mem = rec["memory"]
    assert mem["covered"], mem
    assert mem["floor_bytes"] <= mem["estimate_bytes"] <= mem["ceiling_bytes"]


def test_plan_audit_flags_planted_violations():
    """The acceptance fixtures: an injected fp32 constant in a bf16 decode
    step and an injected host callback are both flagged; the un-tampered
    control cell stays clean."""
    from repro.analysis.plan_audit import selftest

    st = selftest()
    assert st["clean_control"], st
    assert st["fp32_const_flagged"], st
    assert st["host_callback_flagged"], st


# ---------------------------------------------------------------------------
# EngineConfig validation
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kw,match", [
    ({"dtype": "float16"}, "dtype"),
    ({"bucket_select": "lifo"}, "bucket_select"),
    ({"placement": "random"}, "placement"),
    ({"replicas": 0}, "replicas"),
    ({"cache_capacity": 0}, "cache_capacity"),
    ({"recompile_margin": -0.1}, "recompile_margin"),
    ({"page_size": -1}, "page_size"),
    ({"pool_arenas": 0}, "pool_arenas"),
    ({"pool_max_arenas": -1}, "pool caps"),
    ({"pool_max_bytes": -1.0}, "pool caps"),
    ({"max_group_batch": 0}, "max_group_batch"),
    ({"slo_ms": -5.0}, "slo_ms"),
])
def test_engine_config_rejects_invalid(kw, match):
    with pytest.raises(ValueError, match=match):
        EngineConfig(**kw)


def test_engine_config_sanitize_field_defaults_off():
    assert EngineConfig().sanitize is False
    assert EngineConfig(sanitize=True).sanitize is True


# ---------------------------------------------------------------------------
# runtime sanitizer: planted corruption is detected
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def live_engine():
    """A sanitized engine with one group mid-decode (module-scoped: the
    corruption tests below tamper and restore around it)."""
    cfg = get_config("yi-6b-smoke")
    ecfg = EngineConfig(sanitize=True, cache_capacity=8)
    eng = ecfg.build_engine(ecfg.build_server(cfg))
    eng.submit(ServeRequest(1, 60, 64))
    eng.step()
    eng.step()
    assert eng.active, "expected an in-flight group"
    return eng


def test_sanitizer_clean_mid_flight(live_engine):
    assert check_engine(live_engine) == []


def test_sanitizer_catches_page_double_lease(live_engine):
    arena = live_engine.active[0].arena
    row = next(iter(arena._row_pages))
    page = arena._row_pages[row][0]
    arena._row_pages[row].append(page)  # same page leased twice
    try:
        found = check_pool(live_engine.server.pool)
        assert "page-double-lease" in _rules(found)
        assert "page-leak" in _rules(found)  # conservation breaks too
        with pytest.raises(SanitizeError):
            live_engine._sanitize()
    finally:
        arena._row_pages[row].pop()
    assert check_engine(live_engine) == []


def test_sanitizer_catches_orphaned_page_lease(live_engine):
    arena = live_engine.active[0].arena
    row = next(iter(arena._row_pages))
    arena._free.append(row)  # row "freed" while still holding pages
    try:
        found = check_engine(live_engine)
        assert "page-orphan" in _rules(found)
        assert "row-lease-drift" in _rules(found)
    finally:
        arena._free.remove(row)
    assert check_engine(live_engine) == []


def test_sanitizer_catches_live_bytes_drift(live_engine):
    arena = live_engine.active[0].arena
    arena.allocator.reserved += 1  # incremental counter drifts from rows
    try:
        found = check_pool(live_engine.server.pool)
        assert "reserve-drift" in _rules(found)
        assert "live-bytes-drift" in _rules(found)
    finally:
        arena.allocator.reserved -= 1


def test_sanitizer_catches_pooled_bytes_drift(live_engine):
    pool = live_engine.server.pool
    pool.metrics.pooled_bytes += 4096  # gauge drifts from the free list
    try:
        assert "pooled-bytes-drift" in _rules(check_pool(pool))
    finally:
        pool.metrics.pooled_bytes -= 4096
    assert check_engine(live_engine) == []


def test_sanitizer_catches_handle_leak(live_engine):
    live_engine.handles[999_999] = object()  # retired-but-tracked handle
    try:
        found = check_engine(live_engine)
        assert "handle-leak" in _rules(found)
    finally:
        del live_engine.handles[999_999]
    assert check_engine(live_engine) == []


def test_sanitizer_recount_matches_live_bytes(live_engine):
    pool = live_engine.server.pool
    assert recount_live_bytes(pool) == pytest.approx(pool.live_bytes())


# ---------------------------------------------------------------------------
# sanitized scenarios: the existing engine/router flows, sanitize=True
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", FAMILIES)
def test_sanitized_engine_scenario(arch):
    """Submit / stream / cancel / EOS / drain with per-tick sanitizer
    assertions enabled: every transition must keep the invariants."""
    cfg = get_config(arch)
    ecfg = EngineConfig(sanitize=True, cache_capacity=8)
    eng = ecfg.build_engine(ecfg.build_server(cfg))
    reqs = [ServeRequest(1, 24, 6),
            ServeRequest(2, 28, 6),
            ServeRequest(1, 24, 6, eos_id=0),  # may stop early on EOS
            ServeRequest(1, 30, 8)]
    handles = [eng.submit(r) for r in reqs]
    seen = 0
    for ev in eng.events():
        if ev.token is not None:
            seen += 1
            if ev.rid == handles[3].rid and ev.index >= 1:
                eng.cancel(handles[3])  # client hangs up mid-decode
    recs = eng.drain()
    assert seen > 0
    assert len(recs) == len(reqs)
    by_rid = {r["rid"]: r for r in recs}
    assert by_rid[handles[3].rid]["finish_reason"] == "cancelled"
    assert eng.idle and not eng.handles  # nothing leaked past retirement
    assert eng.server.pool.live_bytes() == 0.0


@pytest.mark.parametrize("arch", ["yi-6b-smoke", "mamba2-1.3b-smoke"])
def test_sanitized_router_scenario_with_failover(arch):
    """Two sanitized replicas: placement, work stealing, a mid-run
    drain_replica failover, and fleet drain — per-tick assertions at both
    the replica and router levels."""
    cfg = get_config(arch)
    ecfg = EngineConfig(sanitize=True, replicas=2, cache_capacity=8)
    client = ecfg.build_client(cfg)
    handles = [client.submit(ServeRequest(1, 24, 5)) for _ in range(6)]
    client.step()
    client.step()
    moved = client.drain_replica(0)
    recs = client.drain()
    assert len(recs) == len(handles)
    assert all(r["tokens"].shape[1] > 0 for r in recs)
    # drained replica's live work moved, nobody was dropped
    assert {r["rid"] for r in recs} == {h.rid for h in handles}
    assert all(h.replica.idx == 1 for h in moved)
    for r in client.replicas:
        assert r.engine.server.pool.live_bytes() == 0.0


def test_serve_launcher_accepts_sanitize_flag():
    """--sanitize folds into EngineConfig.from_args (field-name match)."""
    import argparse

    ns = argparse.Namespace(sanitize=True, dtype="float32")
    assert EngineConfig.from_args(ns).sanitize is True


# ---------------------------------------------------------------------------
# PR 9: use-after-donation lint rule
# ---------------------------------------------------------------------------


_DONATION_BAD = """
def tick(group, srv):
    logits, new_cache = group.entry.step_fn(
        srv.params, group.arena.cache, group.toks, group.pos)
    stale = group.arena.cache["layer0.k"]
    group.arena.cache = new_cache
    return logits, stale
"""


def test_lint_use_after_donation_seeded():
    """A cache reference read after being passed to a donating step and
    before rebinding is flagged — but only in tick-path modules."""
    found = lint_source(_DONATION_BAD, "src/repro/runtime/engine_x.py")
    assert _rules(found) == {"use-after-donation"}
    # non-tick modules (analysis tooling, tests) are out of scope
    assert lint_source(_DONATION_BAD, "src/repro/analysis/fixture.py") == []


def test_lint_use_after_donation_clean_idioms():
    """The sanctioned shapes stay clean: rebind through the call's own
    assignment, rebind before any read, and untrackable (consumed at the
    call site) arguments."""
    rebind = ("def tick(entry, params, cache, toks, pos):\n"
              "    logits, cache = entry.step_fn(params, cache, toks, pos)\n"
              "    return logits, cache\n")
    assert lint_source(rebind, "src/repro/runtime/engine_x.py") == []
    consumed = ("def tick(group, srv):\n"
                "    logits, out = group.entry.step_fn(\n"
                "        srv.params, group.arena.relinquish(), group.toks,\n"
                "        group.pos)\n"
                "    group.arena.adopt(out)\n"
                "    return logits\n")
    assert lint_source(consumed, "src/repro/runtime/engine_x.py") == []
    rebound_first = ("def tick(group, srv, fresh):\n"
                     "    logits, out = group.entry.step_fn(\n"
                     "        srv.params, group.cache, group.toks, group.pos)\n"
                     "    group.cache = out\n"
                     "    return logits, group.cache\n")
    assert lint_source(rebound_first, "src/repro/runtime/engine_x.py") == []


def test_lint_use_after_donation_tracks_through_branch_join():
    """A donation inside an ``if`` branch is tracked past the join point
    into the parent block (the engine's paged/dense split)."""
    src = ("def tick(group, srv, paged):\n"
           "    if paged:\n"
           "        logits, out = group.entry.step_fn(\n"
           "            srv.params, group.cache, group.toks, group.pos,\n"
           "            group.tables)\n"
           "    else:\n"
           "        logits, out = group.entry.step_fn(\n"
           "            srv.params, group.cache, group.toks, group.pos)\n"
           "    leak = group.cache\n"
           "    group.cache = out\n"
           "    return logits, leak\n")
    found = lint_source(src, "src/repro/runtime/engine_x.py")
    assert _rules(found) == {"use-after-donation"}
    assert len(found) == 2  # both branches' donations reach the read


def test_lint_use_after_donation_waiver():
    """The explicit waiver suppresses the finding (host-side metadata
    probes like .is_deleted() are the sanctioned exception)."""
    waived = _DONATION_BAD.replace(
        'stale = group.arena.cache["layer0.k"]',
        'stale = group.arena.cache["layer0.k"]'
        '  # lint: allow-use-after-donation')
    assert lint_source(waived, "src/repro/runtime/engine_x.py") == []


# ---------------------------------------------------------------------------
# PR 9: donation-conditioned memory sandwich + memory auditor
# ---------------------------------------------------------------------------


def test_plan_audit_donated_ceiling_conditioned():
    """The reuse-free ceiling conditions on the plan's donation flags: an
    estimate that still carries the double-buffer term must overflow the
    donated (tighter) ceiling while fitting the un-donated one."""
    from repro.analysis.plan_audit import audit_memory
    import jax
    import jax.numpy as jnp

    def step(cache, x):
        return cache + x, cache * 2.0

    cache_spec = jax.ShapeDtypeStruct((1024,), jnp.float32)
    closed = jax.make_jaxpr(step)(cache_spec, cache_spec)
    donated = 1024 * 4
    # an estimate sitting just above the donated ceiling but under the
    # un-donated one (the two differ by exactly the donated bytes)
    _, under = audit_memory(closed, 4.0 * donated, 0.0, "t")
    _, over = audit_memory(closed, 4.0 * donated, 0.0, "t",
                           donated_bytes=donated)
    assert not any(f.rule == "memory-uncovered" for f in under)
    assert any(f.rule == "memory-uncovered" for f in over)
    # and the floor drops by the donated bytes too
    rec_d, _ = audit_memory(closed, 4.0 * donated, 0.0, "t",
                            donated_bytes=donated)
    rec_u, _ = audit_memory(closed, 4.0 * donated, 0.0, "t")
    assert rec_u["floor_bytes"] - rec_d["floor_bytes"] == donated


@pytest.mark.parametrize("arch", FAMILIES)
def test_memory_audit_certifies_aliasing(arch):
    """Tentpole acceptance: for each family the lowered decode executable
    aliases every cache leaf (slot stacks and/or recurrent state) onto
    its output, and the certified peak credits exactly those bytes."""
    from repro.analysis.memory_audit import DONATED_CLASSES, audit_cell

    rec, findings = audit_cell(arch, "bfloat16", 1, 64,
                               decode_kernel="paged")
    assert findings == [], "\n".join(str(f) for f in findings)
    assert rec["donate_cache"] is True
    cache_classes = [c for c in rec["classes"] if c in DONATED_CLASSES]
    assert cache_classes, rec["classes"]
    for c in cache_classes:
        assert rec["classes"][c]["lifetime"] == "aliased-in-place", rec
    cache_bytes = sum(rec["classes"][c]["bytes"] for c in cache_classes)
    assert rec["aliased_bytes"] == cache_bytes
    assert (rec["certified_peak_bytes"]
            == rec["input_bytes"] + rec["output_bytes"] - cache_bytes)


def test_memory_audit_flags_undonated_plan():
    """The planted fixture: a compiler forced to donate_cache=False
    produces a plan every cell of which is flagged cache-not-donated."""
    from repro.analysis.memory_audit import audit_cell

    rec, findings = audit_cell("yi-6b-smoke", "bfloat16", 1, 64,
                               decode_kernel="paged", donate=False)
    assert rec["donate_cache"] is False
    assert any(f.rule == "cache-not-donated" for f in findings)
    # nothing aliases without donation
    assert rec["aliased_bytes"] == 0


# ---------------------------------------------------------------------------
# PR 9: sanitized serving on donated buffers
# ---------------------------------------------------------------------------


def _run_scenario(arch, donate):
    """The full engine scenario (submit / stream / EOS / cancel / drain)
    under sanitize=True, returning records sorted by rid."""
    cfg = get_config(arch)
    ecfg = EngineConfig(sanitize=True, cache_capacity=8, donate=donate)
    eng = ecfg.build_engine(ecfg.build_server(cfg))
    reqs = [ServeRequest(1, 24, 6),
            ServeRequest(2, 28, 6),
            ServeRequest(1, 24, 6, eos_id=0),  # may stop early on EOS
            ServeRequest(1, 30, 8)]
    handles = [eng.submit(r) for r in reqs]
    for ev in eng.events():
        if (ev.token is not None and ev.rid == handles[3].rid
                and ev.index >= 1):
            eng.cancel(handles[3])  # same-tick reclamation of live rows
    recs = eng.drain()
    assert eng.idle and not eng.handles
    assert eng.server.pool.live_bytes() == 0.0
    return sorted(recs, key=lambda r: r["rid"])


@pytest.mark.parametrize("arch", FAMILIES)
def test_sanitized_donation_token_parity(arch):
    """Donated serving is byte-identical to the double-buffered path under
    the sanitizer, across the attention / SSD / hybrid families — incl.
    cancel and EOS reclaiming rows the same tick the step consumed the
    cache. XLA writing in place must not change a single logit."""
    import numpy as np

    donated = _run_scenario(arch, donate=True)
    plain = _run_scenario(arch, donate=False)
    assert len(donated) == len(plain)
    # rids are process-global mints — compare positionally (sorted order
    # is submission order in both runs)
    for d, p in zip(donated, plain):
        assert d["finish_reason"] == p["finish_reason"]
        assert (np.asarray(d["tokens"]).tobytes()
                == np.asarray(p["tokens"]).tobytes())
        # the donated run never holds the second arena copy
        assert d["watermark_bytes"] <= p["watermark_bytes"]


def test_bench_meta_artifact_revision_status(tmp_path):
    """The staleness checker: an artifact stamped with the current
    revision reads current, a different hash reads stale, a missing or
    unstamped file reads unknown (never an exception)."""
    import json
    import pathlib
    import sys

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]
                           / "benchmarks"))
    try:
        import bench_meta
    finally:
        sys.path.pop(0)

    head = bench_meta.git_describe()
    current = tmp_path / "BENCH_current.json"
    current.write_text(json.dumps({"meta": {"git": head}}))
    stale = tmp_path / "BENCH_stale.json"
    stale.write_text(json.dumps({"meta": {"git": "deadbee-dirty"}}))
    unstamped = tmp_path / "BENCH_unstamped.json"
    unstamped.write_text(json.dumps({"rows": []}))

    assert bench_meta.artifact_revision_status(str(current))["status"] \
        in ("current", "unknown")  # unknown only outside a git checkout
    if head != "unknown":
        assert (bench_meta.artifact_revision_status(str(stale))["status"]
                == "stale")
        # -dirty suffixes are ignored: regenerating from the working tree
        # that becomes the next commit must not read as stale
        dirty = tmp_path / "BENCH_dirty.json"
        dirty.write_text(json.dumps(
            {"meta": {"git": bench_meta._base_rev(head) + "-dirty"}}))
        assert (bench_meta.artifact_revision_status(str(dirty))["status"]
                == "current")
    assert (bench_meta.artifact_revision_status(str(unstamped))["status"]
            == "unknown")
    assert (bench_meta.artifact_revision_status(str(tmp_path / "nope.json"))
            ["status"] == "unknown")


def test_serve_launcher_accepts_no_donate_flag():
    """--no-donate inverts into EngineConfig.donate (A/B escape hatch)."""
    import argparse

    ns = argparse.Namespace(no_donate=True, dtype="float32")
    assert EngineConfig.from_args(ns).donate is False
    ns = argparse.Namespace(no_donate=False, dtype="float32")
    assert EngineConfig.from_args(ns).donate is True


# ---------------------------------------------------------------------------
# PR 10: shared smoke matrix, cost certifier, selection-decision audits
# ---------------------------------------------------------------------------

_MESH1 = MeshConfig(shape=(1,), axis_names=("data",))


def test_matrix_smoke_cells_enumeration():
    """One authoritative cell enumeration: decode cells appear under both
    forced kernels, prefill cells only for handoff-capable archs (kernel
    pinned to auto), and ``where`` renders the canonical cell id."""
    cells = list(smoke_cells(archs=("yi-6b-smoke",), dtypes=("bfloat16",),
                             buckets=((1, 64),)))
    decode = [c for c in cells if c.kind == "decode"]
    assert sorted(c.forced_kernel for c in decode) == ["gather", "paged"]
    assert all(c.where == f"yi-6b-smoke/bfloat16/decode/b1s64/"
               f"{c.forced_kernel}" for c in decode)
    prefill = [c for c in cells if c.kind == "prefill"]
    assert [c.forced_kernel for c in prefill] == ["auto"]
    assert prefill[0].where == "yi-6b-smoke/bfloat16/prefill/b1s64"


def test_matrix_merge_report_preserves_foreign_sections(tmp_path):
    """The report is shared by three auditors: merging one section must
    not clobber the others (the historical memory_audit bug), and a
    non-dict or corrupt prior file is replaced, never crashed on."""
    import json

    path = str(tmp_path / "R.json")
    with open(path, "w") as f:
        json.dump({"memory": {"cells": 3}, "findings": []}, f)
    merged = merge_report(path, {"cost": {"ok": True}})
    with open(path) as f:
        on_disk = json.load(f)
    assert on_disk["memory"] == {"cells": 3}
    assert on_disk["findings"] == []
    assert on_disk["cost"] == {"ok": True}
    assert merged == on_disk
    # non-dict prior JSON (a bare list) is replaced wholesale
    with open(path, "w") as f:
        json.dump([1, 2], f)
    merge_report(path, {"cost": 1})
    with open(path) as f:
        assert json.load(f) == {"cost": 1}
    # corrupt JSON likewise
    with open(path, "w") as f:
        f.write("{not json")
    merge_report(path, {"memory": 2})
    with open(path) as f:
        assert json.load(f) == {"memory": 2}


def test_cost_audit_cell_sandwich_and_planted_flops():
    """A clean cell certifies (floor <= analytic <= ceiling on both the
    FLOP and traffic statistics); a drifted cost-model constant — FLOPs
    inflated past the jaxpr-derived ceiling, or deflated under the
    certified MAC floor — is flagged."""
    rec, findings = audit_cell("yi-6b-smoke", "bfloat16", "decode", 1, 64,
                               decode_kernel="gather")
    assert findings == []
    fl, tr = rec["flops"], rec["traffic"]
    assert fl["floor"] <= fl["analytic"] <= fl["ceiling"]
    assert fl["traced_macs"] > 0
    assert tr["floor_bytes"] <= tr["analytic_bytes"] <= tr["ceiling_bytes"]
    _, inflated = audit_cell("yi-6b-smoke", "bfloat16", "decode", 1, 64,
                             decode_kernel="gather", flop_scale=64.0)
    assert _rules(inflated) == {"flop-over-estimate"}
    _, deflated = audit_cell("yi-6b-smoke", "bfloat16", "decode", 1, 64,
                             decode_kernel="gather", flop_scale=1 / 64.0)
    assert _rules(deflated) == {"flop-under-estimate"}
    _, bloated = audit_cell("yi-6b-smoke", "bfloat16", "decode", 1, 64,
                            decode_kernel="gather", traffic_scale=64.0)
    assert _rules(bloated) == {"traffic-over-estimate"}


def test_cost_audit_monotonicity_checker():
    """At most one paged/gather flip along a swept statistic; the
    committed-frac axis additionally admits only paged -> gather."""
    assert check_selection_monotonic(
        [(16, "gather"), (32, "paged"), (64, "paged")], "t") == []
    doctored = [(16, "gather"), (32, "paged"), (64, "gather"),
                (128, "paged")]
    found = check_selection_monotonic(doctored, "t")
    assert _rules(found) == {"crossover-inversion"}
    # directional: raising committed pages only raises the paged cost
    wrong_way = [(0.1, "gather"), (0.9, "paged")]
    assert _rules(check_selection_monotonic(
        wrong_way, "t", axis="committed_frac")) == {"crossover-inversion"}
    right_way = [(0.1, "paged"), (0.9, "gather")]
    assert check_selection_monotonic(
        right_way, "t", axis="committed_frac") == []


def test_cost_audit_explain_completeness():
    """explain_axes() must record every PLAN_AXES entry; dropping one is
    exactly the planted violation the checker flags."""
    plan = PlanCompiler(cache_page_size=64, cache_pool_arenas=4).compile(
        get_config("yi-6b-smoke"), InputShape("t", 64, 1, "decode"),
        _MESH1, dtype="bfloat16")
    axes = plan.explain_axes()
    assert set(axes) == set(PLAN_AXES)
    assert check_explain_axes(axes, "t") == []
    dropped = dict(axes)
    dropped.pop("decode_kernel")
    found = check_explain_axes(dropped, "t")
    assert _rules(found) == {"explain-axis-missing"}
    assert "decode_kernel" in found[0].detail


def test_planner_selection_trace_matches_choice():
    """The introspection hook reproduces the compiler's actual kernel
    choice and records the statistics it was made from."""
    cfg = get_config("yi-6b-smoke")
    compiler = PlanCompiler(cache_page_size=64, cache_pool_arenas=4)
    shape = InputShape("t", 256, 4, "decode")
    trace = compiler.selection_trace(cfg, shape)
    assert trace["kernel"] in ("paged", "gather", "ref", "none")
    assert trace["reason"]
    plan = compiler.compile(cfg, shape, _MESH1, dtype="bfloat16")
    assert plan.config.decode_kernel == trace["kernel"]
    # forced compilers report the forced operator with costs untouched
    forced = PlanCompiler(cache_page_size=64, cache_pool_arenas=4,
                          decode_kernel="ref").selection_trace(cfg, shape)
    assert forced["kernel"] == "ref" and forced["forced"]


def test_cost_audit_trace_closure_certificate():
    """The jit-signature set reachable from an EngineConfig is finite:
    pow2 bucket ladders closed under re-bucketing, signature count within
    the log-product bound."""
    rec, findings = trace_closure_certificate()
    assert findings == []
    assert rec["finite"]
    assert rec["signatures"] <= rec["bound"]
    policy = BucketPolicy()
    for b in rec["batch_buckets"]:
        assert bucket_pow2(b, policy.min_batch) == b
    for s in rec["seq_buckets"]:
        assert bucket_pow2(s, policy.min_seq) == s


@settings(max_examples=60, deadline=None)
@given(st.lists(st.tuples(st.integers(min_value=1, max_value=4096),
                          st.integers(min_value=1, max_value=8192)),
                min_size=1, max_size=64))
def test_bucket_policy_finite_for_bounded_streams(stream):
    """Any bounded request stream collapses onto a finite, idempotent
    bucket set: each bucket is a fixed point of re-bucketing (so
    recompiles mint no new jit signatures), no bucket overshoots 2x the
    request dimension (or the policy minimum), and the distinct-bucket
    count stays within the log2 product bound."""
    policy = BucketPolicy()
    buckets = {(bucket_pow2(b, policy.min_batch),
                bucket_pow2(s, policy.min_seq)) for b, s in stream}
    for bb, sb in buckets:
        assert bucket_pow2(bb, policy.min_batch) == bb
        assert bucket_pow2(sb, policy.min_seq) == sb
    for b, s in stream:
        assert bucket_pow2(b, policy.min_batch) <= 2 * max(
            b, policy.min_batch)
        assert bucket_pow2(s, policy.min_seq) <= 2 * max(s, policy.min_seq)
    # 13 batch ladder rungs (1..4096) x 10 seq rungs (16..8192)
    assert len(buckets) <= 13 * 10


def test_lint_plan_axis_in_explain_seeded():
    """The lint rule flags a PlanConfig field no explain renderer reads,
    a PlanConfig module with no renderer at all, and stays quiet when
    every axis is rendered (``notes`` exempt)."""
    dropped = (
        "class PlanConfig:\n"
        "    strategy: str = 'local'\n"
        "    decode_kernel: str = 'gather'\n"
        "    notes: tuple = ()\n"
        "class ExecutionPlan:\n"
        "    def explain_axes(self):\n"
        "        return {'strategy': self.config.strategy}\n")
    found = [f for f in lint_source(dropped)
             if f.rule == "plan-axis-in-explain"]
    assert len(found) == 1 and "decode_kernel" in found[0].detail
    no_renderer = "class PlanConfig:\n    strategy: str = 'local'\n"
    assert "plan-axis-in-explain" in _rules(lint_source(no_renderer))
    clean = (
        "class PlanConfig:\n"
        "    strategy: str = 'local'\n"
        "    decode_kernel: str = 'gather'\n"
        "    notes: tuple = ()\n"
        "class ExecutionPlan:\n"
        "    def explain_axes(self):\n"
        "        c = self.config\n"
        "        return {'strategy': c.strategy,\n"
        "                'decode_kernel': c.decode_kernel}\n")
    assert "plan-axis-in-explain" not in _rules(lint_source(clean))


def test_bench_meta_parent_revision_is_current(tmp_path):
    """An artifact stamped with HEAD's parent (the usual
    ``<parent>-dirty`` regeneration stamp — that working tree became this
    commit) reads current; anything older stays stale."""
    import json
    import pathlib
    import sys

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]
                           / "benchmarks"))
    try:
        import bench_meta
    finally:
        sys.path.pop(0)

    parent = bench_meta._parent_rev()
    if not parent or bench_meta.git_describe() == "unknown":
        pytest.skip("needs a git checkout with a parent commit")
    fresh = tmp_path / "BENCH_fresh.json"
    fresh.write_text(json.dumps({"meta": {"git": parent + "-dirty"}}))
    assert (bench_meta.artifact_revision_status(str(fresh))["status"]
            == "current")
    old = tmp_path / "BENCH_old.json"
    old.write_text(json.dumps({"meta": {"git": "0000bad-dirty"}}))
    assert (bench_meta.artifact_revision_status(str(old))["status"]
            == "stale")
