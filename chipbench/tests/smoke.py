"""A cell at smoke widths on the CPU: the harness's whole run past its
look for a chip, for tests."""

from __future__ import annotations

import copy
import time

from chipbench.lib import cell as CELL

SIZES = {
    "mamba2-1.3b": {"num_layers": 2, "d_model": 128, "vocab_size": 512,
                    "ssm_state": 16, "ssm_expand": 2, "ssm_head_dim": 16,
                    "ssm_conv_width": 4, "tie_embeddings": True},
}
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e9}
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}


class SmokeBench(CELL.Bench):
    """The real BENCHMARK.json, with the cell's model cut to smoke widths
    and its traffic cut to a few short requests."""

    def __init__(self, rate=20.0, max_group_batch=4, gap_limit=None,
                 error_limit=None, dtype="float32"):
        super().__init__()
        self.rate, self.mgb = rate, max_group_batch
        self.gap_limit, self.error_limit, self.dtype = gap_limit, error_limit, dtype

    def config(self, cell):
        cfg = copy.deepcopy(super().config(cell))
        cfg["sizes"] = dict(SIZES[cfg["model"]])
        cfg["model"] += "-smoke"
        cfg["engine"]["max_group_batch"] = self.mgb
        cfg["engine"]["dtype"] = self.dtype
        cfg["check"] = dict(cfg["check"], tokens=24, max_requests=3, min_tokens=4)
        if self.gap_limit is not None:
            cfg["check"]["max_logit_gap"] = self.gap_limit
        if self.error_limit is not None:
            cfg["check"]["max_logit_error"] = self.error_limit
        return cfg

    def traffic(self, cell):
        t = copy.deepcopy(super().traffic(cell))
        t["prompt"] = dict(t["prompt"], median=20, min=8, max=40)
        t["output"] = dict(t["output"], median=5, min=3, max=8)
        if t["arrival"]["process"] == "poisson":
            t["arrival"]["rate_per_s"] = self.rate
        else:
            t["arrival"]["mean_rate_per_s"] = self.rate
        return t


def run(jax, name, seed=3, seconds=2.0, trace=False, fault=None,
        control=False, **kw):
    bench = SmokeBench(**kw)
    return CELL.run_cell(jax, bench, name, seed, seconds, trace,
                         time.perf_counter(), dict(DEVICE), PEAKS,
                         control=control, fault=fault)
