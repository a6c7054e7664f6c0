"""The SSD scan in the prefill program (``repro.kernels.ssd_scan``).

One kernel call per layer on the prefill bucket: x (batch, seq, heads,
head_dim) in bf16, dt (batch, seq, heads) in f32, B and C (batch, seq,
state) in bf16, out y like x. The least work is the recurrence itself,
five operations per state element and token (decay, input product, add,
and the output's multiply-add); the least traffic reads the inputs once
and writes y once.
"""

KIND = "prefill"
FAMILIES = ("ssd",)
TRACE_OPS = ("ssd_scan",)


def cost(call, s):
    """(FLOPs, bytes) of one prefill program's SSD calls, all layers."""
    b, n = call["b"], call["s"]
    p, st = s["ssm_head_dim"], s["ssm_state"]
    h = s["ssm_expand"] * s["d_model"] // p
    flops = 5 * b * n * h * p * st
    nbytes = b * n * (2 * h * p * 2 + h * 4 + 2 * st * 2)
    return s["num_layers"] * flops, s["num_layers"] * nbytes
