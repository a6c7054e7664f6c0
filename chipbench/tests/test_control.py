"""The lower-precision control.

On the chip, at each cell's own size, ``python3 chipbench/tune.py
--workload <cell> --calibrate <12 seeds> --control-seeds 3`` reads the
program's numbers on a dozen seeds and, on three, the control's (the
reference in the program's place with float8 operands, judged by the same
limits) and a planted wrong token's gap; the configuration file keeps
those readings beside the limits set between them. The first test holds
the limits to the readings. The second runs the control through the
harness's whole run at smoke widths on the CPU, against limits for smoke
widths (where the sound bf16 program reads about a tenth of them), and
sees ``correct`` come out false."""

import json
from pathlib import Path

import jax
import pytest

from chipbench.tests import smoke

HERE = Path(__file__).resolve().parents[1]
CELLS = {"mamba2-1.3b.chat-burst": "mamba2-1.3b.json"}
NUMBERS = ("max_logit_gap", "max_logit_error")


@pytest.mark.parametrize("config", sorted(set(CELLS.values())))
def test_limits_sit_between_the_chip_readings(config):
    ck = json.loads((HERE / "configs" / config).read_text())["check"]
    failed_by_control = []
    for k in NUMBERS:
        r = ck["readings"][k]
        lower = r["program_max"]
        # an upper reading is one at three times the lower or more: the
        # control's, else the planted fault's
        upper = [v for n, v in r.items() if n.endswith("_min") and v >= 3 * lower]
        if upper:
            assert lower < ck[k] < min(upper)
        else:
            assert r["kept_without_upper"] and lower < ck[k]
        failed_by_control.append(r["control_min"] > ck[k])
    assert any(failed_by_control)


@pytest.mark.parametrize("name", sorted(CELLS))
def test_control_reads_several_times_the_program(name):
    for seed in (11, 12, 13):
        res, lines = smoke.run(jax, name, seed=seed, dtype="bfloat16",
                               control=True, gap_limit=0.2, error_limit=0.2)
        assert res["correct"] is False, lines
        c = res["checks"]
        assert any(c[k]["value"] > c[k]["limit"] for k in NUMBERS)
