"""Whether what the timed path served is what the model computes.

Before the window opens, a few requests are drawn from the seed to be
watched (the longest of those due early enough to finish, then others);
every request is prefilled with its own prompt ids drawn from the seed.
While the window runs, the recorder keeps the program's logits of the
watched rows, prefill and decode steps alike. After the window, the
finished watched requests are run through the family's float32 reference,
layer by layer, on their prompt followed by the served tokens. Two numbers
are compared, each against its limit in the configuration file:

- ``max_logit_gap``: at each served position, the reference's best logit
  minus the reference's logit of the served token, widest over the sample.
  A correct bf16 server picks another token than float32 only on
  near-ties; a wrong token anywhere reads as a large gap.
- ``max_logit_error``: at each served position, the largest difference
  between the program's logits and the reference's over the vocabulary, in
  units of the reference logits' standard deviation there, widest over the
  sample. A stale cache or state moves every logit even where the best
  token happens not to change.

The control puts the reference in the program's place at the precision
below the configured bf16 (float8 e4m3 operands in every contraction,
scaled per tensor), at the same prompts and served tokens: its first
token at each position stands for the served token and its logits for the
kept ones, and ``verdict`` judges the two numbers it reads against the same
limits. A calibration also reads the planted fault of one served token
altered where it is produced (``fault_gap``): the gap of the next token id
at one position drawn from the seed.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from chipbench.lib import weights as W

F8_MAX = 448.0


def make_mm(jax, control: bool):
    import jax.numpy as jnp

    def q(x):
        if not control:
            return x
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
        return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale

    def mm(spec, a, b):
        return jnp.einsum(spec, q(a), q(b), precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)

    return mm


def watch_list(sched, seed: int, seconds: float, ck: Dict) -> List[int]:
    """The requests whose logits the run keeps, drawn from the seed before
    the window opens: the longest of those due early enough to finish, then
    others until ``tokens`` output tokens or ``max_requests`` requests."""
    pool = [i for i, a in enumerate(sched) if a.due_s < seconds * ck["due_share"]]
    if not pool:
        return []
    longest = max(pool, key=lambda i: (sched[i].prompt + sched[i].output, -i))
    rng = np.random.default_rng(int(seed) + 1)
    pick, total = [longest], sched[longest].output
    for i in rng.permutation(pool):
        if total >= ck["tokens"] or len(pick) >= ck["max_requests"]:
            break
        if int(i) != longest:
            pick.append(int(i))
            total += sched[int(i)].output
    return pick


def _pow2(n: int, minimum: int) -> int:
    return max(minimum, 1 << (int(n) - 1).bit_length())


def reference_logits(jax, family, sizes: Dict, seed: int, dtype,
                     seqs: Sequence[Tuple[np.ndarray, np.ndarray]],
                     controls: Sequence[bool] = (False,)) -> List[List[np.ndarray]]:
    """For each (prompt ids, served tokens): the logits at each served
    position, (n_served, vocab) float32, once per entry of ``controls``."""
    import jax.numpy as jnp

    key = W.seed_key(seed)
    # shapes rounded up to powers of two, so that the reference compiles
    # once per size class and later runs find it in the compile cache; the
    # padding lies after every compared position and the models are causal
    length = _pow2(max(len(p) + len(t) - 1 for p, t in seqs), 128)
    toks = np.zeros((_pow2(len(seqs), 1), length), np.int32)
    n_out = _pow2(max(len(t) for _p, t in seqs), 16)
    at = np.zeros((len(toks), n_out), np.int32)
    for i, (p, t) in enumerate(seqs):
        toks[i, :len(p)] = p
        toks[i, len(p):len(p) + len(t) - 1] = t[:-1]
        at[i, :len(t)] = np.arange(len(p) - 1, len(p) - 1 + len(t))
        at[i, len(t):] = len(p) - 1
    out = []
    with jax.default_matmul_precision("highest"):
        for control in controls:
            mm = make_mm(jax, control)
            layer = jax.jit(lambda p, x: family.layer(p, x, sizes, mm))
            head = jax.jit(lambda g, x, at: family.logits(
                g, jnp.take_along_axis(x, at[:, :, None], axis=1), sizes, mm))
            g = W.globals_f32(family, sizes, key, dtype)
            x = jax.jit(lambda g, t: family.embed(g, t, sizes))(g, jnp.asarray(toks))
            for l in range(sizes["num_layers"]):
                x = layer(W.layer_f32(family, sizes, key, dtype, l), x)
            lg = np.asarray(head(g, x, jnp.asarray(at)))
            out.append([lg[i, :len(t)] for i, (_p, t) in enumerate(seqs)])
            del x, g
    return out


def gaps(ref: np.ndarray, chosen: np.ndarray) -> np.ndarray:
    """Best reference logit minus the reference logit of ``chosen``."""
    return ref.max(axis=-1) - np.take_along_axis(ref, chosen[:, None], axis=-1)[:, 0]


def errors(ref: np.ndarray, got: np.ndarray) -> np.ndarray:
    """Per position: the largest logit error over the vocabulary, in units
    of the reference logits' standard deviation at that position."""
    return np.abs(got - ref).max(axis=-1) / ref.std(axis=-1)


def check(jax, family, sizes, seed, dtype, picked, with_control: bool = False
          ) -> Dict[str, Optional[float]]:
    """The compared numbers of the finished watched requests:
    ``max_logit_gap`` (served tokens against the reference's best),
    ``max_logit_error`` (the program's kept logits against the
    reference's), ``tokens_checked``; with the control, its gap, error and
    positions (``control_*``) and the planted fault's ``fault_gap``."""
    seqs = [(r.prompt_ids, r.result) for r in picked]
    if not seqs:
        return {"max_logit_gap": None, "max_logit_error": None, "tokens_checked": 0}
    controls = (False, True) if with_control else (False,)
    logits = reference_logits(jax, family, sizes, seed, dtype, seqs, controls)
    ref = logits[0]
    gap = max(float(gaps(lg, t).max()) for lg, (_p, t) in zip(ref, seqs))
    errs, n = [], 0
    for lg, r in zip(ref, picked):
        have = [i for i in range(len(r.result)) if i in r.logits]
        n += len(have)
        if have:
            errs.append(float(errors(lg[have], np.stack([r.logits[i] for i in have])).max()))
    out = {"max_logit_gap": gap, "max_logit_error": max(errs) if errs else None,
           "tokens_checked": n}
    if with_control:
        out["control_gap"] = max(float(gaps(lg, c.argmax(axis=-1)).max())
                                 for lg, c in zip(ref, logits[1]))
        out["control_error"] = max(float(errors(lg, c).max())
                                   for lg, c in zip(ref, logits[1]))
        out["control_tokens"] = sum(len(t) for _p, t in seqs)
        where = np.random.default_rng(int(seed) + 2).integers(out["control_tokens"])
        for lg, (_p, t) in zip(ref, seqs):
            if where < len(t):
                wrong = (t[where:where + 1] + 1) % lg.shape[-1]
                out["fault_gap"] = max(gap, float(gaps(lg[where:where + 1], wrong)[0]))
                break
            where -= len(t)
    return out


def as_control(nums: Dict) -> Dict:
    """The control's numbers in the program's place."""
    return {"max_logit_gap": nums["control_gap"],
            "max_logit_error": nums["control_error"],
            "tokens_checked": nums["control_tokens"]}


def verdict(nums: Dict, ck: Dict) -> Tuple[bool, Dict, List[str]]:
    """(correct, {number: {"value", "limit"}}, lines to print): each
    compared number against its limit in the configuration's ``check``."""
    limits = {"max_logit_gap": ck["max_logit_gap"],
              "max_logit_error": ck["max_logit_error"]}
    checks = {k: {"value": nums[k], "limit": v} for k, v in limits.items()}
    checks["tokens_checked"] = {"value": nums["tokens_checked"],
                                "limit": ck["min_tokens"]}
    correct = (all(nums[k] is not None and nums[k] <= v
                   for k, v in limits.items())
               and nums["tokens_checked"] >= ck["min_tokens"])
    lines = [f"check {k}={nums[k]!r} limit={v!r} (at most)"
             for k, v in limits.items()]
    lines.append(f"check tokens_checked={nums['tokens_checked']} "
                 f"limit={ck['min_tokens']} (at least)")
    return bool(correct), checks, lines
