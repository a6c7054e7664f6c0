"""Arithmetic the metric readers share: rooflines and model FLOP shares."""

from __future__ import annotations

from chipbench.lib import runner as R
from chipbench.lib.traffic import percentile

PROGRAM = {"prefill": "jit_prefill", "decode": "jit_decode_step"}


def client(run):
    return R.client_numbers(run.served, run.seconds)


def pct(samples, q, scale=1.0):
    return percentile(samples, q) * scale if samples else None


def roofline(run, kernel):
    """Least time of the kernel's calls (the larger of FLOPs over peak and
    bytes over bandwidth) over their device time, in percent. Calls are the
    recorder's, matched in dispatch order to the traced executions of the
    program that holds the kernel."""
    mod = run.kernels.get(kernel)
    if mod is None or run.red is None:
        return None
    calls = [c for c in run.calls if c["kind"] == mod.KIND]
    progs = [p for p in run.red.programs if p[0] == PROGRAM[mod.KIND]]
    least = spent = 0.0
    for call, (_stem, _secs, ks) in zip(calls, progs):
        t = ks.get(kernel, 0.0)
        if t <= 0:
            continue
        flops, nbytes = mod.cost(call, run.sizes)
        least += max(flops / run.peaks["bf16_flops_per_s"],
                     nbytes / run.peaks["hbm_bytes_per_s"])
        spent += t
    return 100.0 * least / spent if spent > 0 else None


def model_flops(run, kind, calls=None):
    fam, s = run.family, run.sizes
    total = 0.0
    for c in run.calls if calls is None else calls:
        if c["kind"] != kind:
            continue
        if kind == "prefill":
            total += sum(fam.prefill_flops(s, n) for n in c["lengths"])
        else:
            total += sum(fam.token_flops(s, p) for p in c["positions"])
    return total


def phase_mfu(run, kind):
    """Model FLOPs of the tokens a phase served, at their real lengths, over
    that phase's programs' device time times the peak, in percent."""
    if run.red is None:
        return None
    secs = run.red.module_seconds(PROGRAM[kind])
    if secs <= 0:
        return None
    # the calls the traced window covers (see ``trace``)
    traced = run.calls[:sum(run.red.module_count(p) for p in PROGRAM.values())]
    return 100.0 * model_flops(run, kind, traced) / (
        secs * run.peaks["bf16_flops_per_s"])
