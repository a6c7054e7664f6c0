"""The correctness check at smoke widths on the CPU: the references agree
with the program's prefill-then-paged-decode path, and the harness's whole
run, with the timed path broken underneath, reads ``correct`` false."""

from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.lib import check as C
from chipbench.lib import runner as R
from chipbench.lib import weights as W
from chipbench.tests import smoke

CELLS = ["mamba2-1.3b.chat-burst"]
HERE = Path(__file__).resolve().parents[1]


@pytest.mark.parametrize("name", CELLS)
def test_reference_matches_prefill_logits(name):
    """Float32 program against the float32 reference: they differ only in
    the order of sums, so the logits (of order 1) agree to 1e-4 of their
    largest magnitude."""
    bench = smoke.SmokeBench()
    cfg = bench.config(bench.cell(name))
    srv, eng, _rec = R.build(jax, cfg, seed=5)
    entry = srv.prefill_entry(1, 24)
    logits, _kv = srv.run_prefill(entry, lengths=jnp.asarray([21], jnp.int32))
    got = np.asarray(logits)[0]
    family = R.load_family(cfg["family"])
    want = C.reference_logits(jax, family, cfg["sizes"], 5, "float32",
                              [(np.ones(21, np.int32), np.asarray([3]))])[0][0][0]
    assert np.max(np.abs(got - want)) <= 1e-4 * np.max(np.abs(want))


@pytest.mark.parametrize("name", CELLS)
def test_served_tokens_agree_with_the_reference(name):
    """Through the engine (prefill, handoff into the pool, decode steps):
    float32 serving picks the reference's best token, so the widest gap is
    zero up to reassociation (1e-4 of logits of order 1)."""
    res, _ = smoke.run(jax, name, seed=4)
    assert res["correct"]
    assert res["checks"]["max_logit_gap"]["value"] <= 1e-4
    assert res["checks"]["tokens_checked"]["value"] >= 4


def test_weights_layer_by_layer_equal_the_whole_model():
    family = R.load_family("ssd")
    sizes = smoke.SIZES["mamba2-1.3b"]
    key = W.seed_key(2**31 + 77)
    full = W.make_all(family, sizes, key, jnp.bfloat16)
    for l in range(sizes["num_layers"]):
        one = W.layer_f32(family, sizes, key, jnp.bfloat16, l)
        for name, v in one.items():
            np.testing.assert_array_equal(
                np.asarray(full["l." + name][l], np.float32), np.asarray(v))
    g = W.globals_f32(family, sizes, key, jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(full["embed"], np.float32),
                                  np.asarray(g["embed"]))


def _decode_entries(srv, change):
    """Wrap every decode program the server hands out with ``change``."""
    orig = srv.decode_entry

    def decode_entry(batch, context):
        entry = orig(batch, context)
        if not entry.extras.get("fault"):
            entry.step_fn = change(entry.step_fn)
            entry.extras["fault"] = True
        return entry

    srv.decode_entry = decode_entry


def state_unchanged(srv, eng):
    def change(step):
        def run(params, cache, *rest):
            kept = jax.tree.map(jnp.copy, cache)     # the step donates its input
            logits, _new = step(params, cache, *rest)
            return logits, kept
        return run
    _decode_entries(srv, change)


def half_batch_left_out(srv, eng):
    def change(step):
        def run(params, cache, toks, *rest):
            logits, new = step(params, cache, toks, *rest)
            half = max(1, logits.shape[0] // 2)
            return logits.at[half:].set(logits[:1]) if logits.shape[0] > 1 \
                else logits.at[:].set(0.0), new
        return run
    _decode_entries(srv, change)


def token_altered(srv, eng):
    prefill = srv.run_prefill

    def run_prefill(entry, tokens=None, lengths=None):
        logits, kv = prefill(entry, tokens, lengths)
        return logits.at[:, 7].add(1e4), kv
    srv.run_prefill = run_prefill


@pytest.mark.parametrize("fault", [state_unchanged, half_batch_left_out,
                                   token_altered])
@pytest.mark.parametrize("name", CELLS)
def test_a_broken_timed_path_is_not_correct(name, fault):
    """In the served dtype, against limits for smoke widths: there the sound
    bf16 program reads a logit error of about 0.02 and a gap of 0 (seeds 6
    and 7), a tenth of the limits; a decode step that keeps its old cache
    reads 0.37-0.75 on the dense model."""
    res, lines = smoke.run(jax, name, seed=6, fault=fault, dtype="bfloat16",
                           gap_limit=0.2, error_limit=0.2)
    assert res["correct"] is False
    c = res["checks"]
    assert any(c[k]["value"] > c[k]["limit"]
               for k in ("max_logit_gap", "max_logit_error"))
    assert lines[0].startswith("check max_logit_gap=")
