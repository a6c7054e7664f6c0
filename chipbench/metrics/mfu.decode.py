"""Model FLOPs of the tokens decoded in the window, at their real
positions, over the decode programs' device time times the bf16 peak."""

from chipbench.lib.readers import phase_mfu


def read(run):
    return phase_mfu(run, "decode")
