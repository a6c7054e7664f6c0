"""Kernel and model cost functions against counts made by hand at one
small shape."""

import importlib.util
from pathlib import Path

import pytest

from chipbench.reference import ssd

HERE = Path(__file__).resolve().parents[1]


def kernel(name):
    spec = importlib.util.spec_from_file_location(name, HERE / "kernels" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


SSD = {"num_layers": 1, "d_model": 2, "vocab_size": 3, "ssm_state": 3,
       "ssm_expand": 2, "ssm_head_dim": 2, "ssm_conv_width": 4}


def test_ssd_scan_counts_the_recurrence():
    flops, nbytes = kernel("ssd_scan").cost({"b": 1, "s": 2}, SSD)
    # heads = 2*2/2 = 2, head dim 2, state 3: 12 state elements, 5 each, 2 tokens
    assert flops == 2 * 12 * 5
    # per token: x and y (2 heads x 2, bf16), dt (2 heads f32), B and C (3, bf16)
    assert nbytes == 2 * (2 * 4 * 2 + 2 * 4 + 2 * 3 * 2)


def test_ssd_model_flops():
    # projections: z, x (2x4 each), B, C (2x3 each), dt (2x2), out (4x2) = 40 MACs
    # conv: width 4 over 4 + 3 + 3 channels = 40 MACs; scan 5 x 2 x 2 x 3 = 60
    per = 2 * 40 + 2 * 40 + 60
    assert ssd.token_flops(SSD, 5) == pytest.approx(per + 2 * 2 * 3)
    assert ssd.prefill_flops(SSD, 3) == pytest.approx(3 * per + 2 * 2 * 3)
