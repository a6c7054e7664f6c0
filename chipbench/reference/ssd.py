"""Plain float32 reference of an attention-free Mamba-2 (SSD) stack.

Mamba-2 as in arXiv:2405.21060 (section 7, one group of B/C shared by all
heads): per layer RMSNorm -> input projections z, x, B, C and dt -> a
depthwise causal convolution (width ``ssm_conv_width``) and SiLU on x, B
and C -> the selective state-space recurrence

    h_t = exp(dt_t * A) h_{t-1} + dt_t * x_t (outer) B_t,   y_t = h_t C_t + D x_t

per head, with A = -exp(a_log) and dt = softplus(dt_proj + dt_bias) ->
y * SiLU(z), RMSNorm with a gain -> output projection -> residual add. The
embedding is tied to the output head and scaled by sqrt(d_model) on input.
The recurrence is evaluated in the chunked form of the paper's minimal
SSD listing (quadratic inside a chunk, state passing between chunks),
which equals the step-by-step recurrence in exact arithmetic.

Nothing here imports the system under test; ``layout`` only names the
weights the way the served program stores them.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

EPS = 1e-5
CHUNK = 128


def _dims(s):
    d = s["d_model"]
    di = s["ssm_expand"] * d
    return d, di, s["ssm_state"], di // s["ssm_head_dim"], s["ssm_head_dim"]


def layout(s):
    """(global leaves, per-layer leaves): name -> (shape, init)."""
    d, di, n, h, _p = _dims(s)
    wc = s["ssm_conv_width"]
    glob = {
        "embed": ((s["vocab_size"], d), ("normal", 1.0 / math.sqrt(d))),
        "final_ln": ((d,), ("ones",)),
    }
    layer = {
        "ln": ((d,), ("ones",)),
        "wz": ((d, di), ("normal", 1.0 / math.sqrt(d))),
        "wx": ((d, di), ("normal", 1.0 / math.sqrt(d))),
        "wb": ((d, n), ("normal", 1.0 / math.sqrt(d))),
        "wc": ((d, n), ("normal", 1.0 / math.sqrt(d))),
        "wdt": ((d, h), ("normal", 1.0 / math.sqrt(d))),
        # dt = softplus(bias) drawn log-uniform in [1e-3, 1e-1] (Mamba init)
        "dt_bias": ((h,), ("dt_bias", 1e-3, 1e-1)),
        "conv_x": ((wc, di), ("normal", 1.0 / math.sqrt(wc))),
        "conv_b": ((wc, n), ("normal", 1.0 / math.sqrt(wc))),
        "conv_c": ((wc, n), ("normal", 1.0 / math.sqrt(wc))),
        # A = -exp(a_log) with exp(a_log) uniform in [1, 16]
        "a_log": ((h,), ("a_log", 1.0, 16.0)),
        "d_skip": ((h,), ("ones",)),
        "gate_ln": ((di,), ("ones",)),
        "w_out": ((di, d), ("normal", 1.0 / math.sqrt(di))),
    }
    return glob, layer


def rms_norm(x, gain):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS) * gain


def causal_conv(x, w):
    """Depthwise causal convolution: y_t = sum_i w[i] x_{t - (W-1) + i}."""
    wd = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (wd - 1, 0), (0, 0)))
    return sum(xp[:, i:i + x.shape[1]] * w[i] for i in range(wd))


def ssd_scan(x, dt, a, b, c, mm, chunk=CHUNK):
    """One sequence: x (S, H, P), dt (S, H), a (H,), b/c (S, N) -> y (S, H, P)
    without the D skip."""
    n = x.shape[0]
    pad = -n % chunk
    x, dt = jnp.pad(x, ((0, pad), (0, 0), (0, 0))), jnp.pad(dt, ((0, pad), (0, 0)))
    b, c = jnp.pad(b, ((0, pad), (0, 0))), jnp.pad(c, ((0, pad), (0, 0)))
    nc = x.shape[0] // chunk
    xdt = (x * dt[..., None]).reshape(nc, chunk, *x.shape[1:])
    la = (dt * a[None]).reshape(nc, chunk, -1)                      # log decay
    b, c = b.reshape(nc, chunk, -1), c.reshape(nc, chunk, -1)
    cum = jnp.cumsum(la, axis=1)                                   # (nc, L, H)
    i = jnp.arange(chunk)
    seg = cum[:, :, None, :] - cum[:, None, :, :]                  # (nc, L, L, H)
    lower = (i[:, None] >= i[None, :])[None, :, :, None]
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, seg, 0.0)), 0.0)
    scores = mm("cln,csn->cls", c, b)
    y_in = mm("clsh,cshp->clhp", decay * scores[..., None], xdt)
    to_end = jnp.exp(cum[:, -1:, :] - cum)                         # (nc, L, H)
    states = mm("clhp,cln->chpn", xdt * to_end[..., None], b)

    def carry_state(h, inp):
        st, total = inp
        return h * jnp.exp(total)[:, None, None] + st, h

    _, before = jax.lax.scan(carry_state, jnp.zeros_like(states[0]),
                             (states, cum[:, -1, :]))              # state entering chunk
    y_out = mm("cln,chpn->clhp", c, before) * jnp.exp(cum)[..., None]
    return (y_in + y_out).reshape(nc * chunk, *x.shape[1:])[:n]


def embed(g, tokens, s):
    return g["embed"][tokens] * math.sqrt(s["d_model"])


def layer(p, x, s, mm):
    b_, n, _ = x.shape
    _d, di, _n, nh, hp = _dims(s)
    h = rms_norm(x, p["ln"])
    z = mm("bsd,de->bse", h, p["wz"])
    xr = mm("bsd,de->bse", h, p["wx"])
    br = mm("bsd,dn->bsn", h, p["wb"])
    cr = mm("bsd,dn->bsn", h, p["wc"])
    dt = jax.nn.softplus(mm("bsd,dh->bsh", h, p["wdt"]) + p["dt_bias"])
    xc = jax.nn.silu(causal_conv(xr, p["conv_x"])).reshape(b_, n, nh, hp)
    bc = jax.nn.silu(causal_conv(br, p["conv_b"]))
    cc = jax.nn.silu(causal_conv(cr, p["conv_c"]))
    a = -jnp.exp(p["a_log"])
    y = jax.lax.map(lambda r: ssd_scan(r[0], r[1], a, r[2], r[3], mm),
                    (xc, dt, bc, cc))
    y = (y + p["d_skip"][None, None, :, None] * xc).reshape(b_, n, di)
    y = rms_norm(y * jax.nn.silu(z), p["gate_ln"])
    return x + mm("bse,ed->bsd", y, p["w_out"])


def logits(g, x, s, mm):
    return mm("bsd,vd->bsv", rms_norm(x, g["final_ln"]), g["embed"])


def _token_flops_body(s) -> float:
    d, di, n, h, p = _dims(s)
    proj = d * (2 * di + 2 * n + h) + di * d
    conv = s["ssm_conv_width"] * (di + 2 * n)
    # recurrence per state element: decay, input product, add; output dot
    scan = 5 * h * p * n
    return s["num_layers"] * (2 * proj + 2 * conv + scan)


def token_flops(s, position: int) -> float:
    """Model FLOPs of one decoded token (independent of position)."""
    return _token_flops_body(s) + 2 * s["d_model"] * s["vocab_size"]


def prefill_flops(s, length: int) -> float:
    """Model FLOPs of a prompt of ``length`` tokens, logits for its last
    position only."""
    return _token_flops_body(s) * length + 2 * s["d_model"] * s["vocab_size"]
