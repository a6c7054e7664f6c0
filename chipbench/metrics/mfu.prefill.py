"""Model FLOPs of the prompts prefilled in the window, at their real
lengths, over the prefill programs' device time times the bf16 peak."""

from chipbench.lib.readers import phase_mfu


def read(run):
    return phase_mfu(run, "prefill")
