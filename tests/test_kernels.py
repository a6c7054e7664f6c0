"""Per-kernel allclose sweeps against the pure-jnp oracles (ref.py),
executed in Pallas interpret mode (TPU is the deploy target; interpret
runs the kernel body on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.conv2d_im2col import conv2d_im2col
from repro.kernels.flash_attention import flash_attention
from repro.kernels.matmul import matmul
from repro.kernels.ssd_scan import ssd_scan
from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("m,k,n", [
    (128, 128, 128), (256, 384, 128), (100, 70, 50), (17, 33, 9),
    (512, 128, 256), (8, 8, 8),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul(m, k, n, dtype):
    a = jax.random.normal(KEY, (m, k), dtype)
    b = jax.random.normal(jax.random.PRNGKey(1), (k, n), dtype)
    got = matmul(a, b, interpret=True)
    want = ref.matmul_ref(a, b)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("n,c,h,w,f,kern,stride,pad", [
    (2, 3, 8, 8, 4, 3, 1, 1),
    (1, 1, 12, 12, 8, 5, 2, 2),
    (3, 4, 16, 16, 16, 3, 1, 0),
    (2, 2, 10, 10, 6, 3, 2, 1),
])
def test_conv2d_im2col(n, c, h, w, f, kern, stride, pad):
    x = jax.random.normal(KEY, (n, c, h, w), jnp.float32)
    wt = jax.random.normal(jax.random.PRNGKey(1), (f, c, kern, kern), jnp.float32)
    got = conv2d_im2col(x, wt, stride=stride, pad=pad, interpret=True)
    want = ref.conv2d_ref(x, wt, stride=stride, pad=pad)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("b,hq,hkv,sq,sk,d,causal,window", [
    (2, 4, 2, 64, 64, 32, True, 0),
    (1, 8, 2, 128, 128, 64, True, 0),
    (2, 4, 4, 64, 64, 32, False, 0),
    (2, 4, 2, 64, 64, 32, True, 16),   # sliding window
    (1, 2, 1, 1, 96, 32, True, 0),     # decode: single query
    (1, 2, 1, 100, 100, 32, True, 0),  # non-tile-aligned
    (2, 4, 1, 64, 64, 32, True, 0),    # MQA
    (1, 4, 2, 5, 5, 32, True, 0),      # short prompt: one padded 8-row block
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(b, hq, hkv, sq, sk, d, causal, window, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, hq, sq, d), dtype)
    k = jax.random.normal(ks[1], (b, hkv, sk, d), dtype)
    v = jax.random.normal(ks[2], (b, hkv, sk, d), dtype)
    got = flash_attention(q, k, v, causal=causal, window=window,
                          bq=32, bk=32, interpret=True)
    want = ref.attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("b,s,h,p,n,chunk", [
    (2, 64, 3, 8, 16, 16),
    (1, 32, 2, 16, 8, 8),
    (2, 128, 4, 8, 32, 32),
    (1, 64, 1, 32, 64, 16),
    (1, 128, 2, 64, 128, 64),   # mamba2 head/state widths, published chunk
])
def test_ssd_scan(b, s, h, p, n, chunk):
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (b, s, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    a = -jnp.exp(jax.random.normal(ks[2], (h,)))
    bm = jax.random.normal(ks[3], (b, s, n), jnp.float32)
    cm = jax.random.normal(ks[4], (b, s, n), jnp.float32)
    d = jnp.full((h,), 0.5)
    got = ssd_scan(x, dt, a, bm, cm, d, chunk=chunk, interpret=True)
    want, _ = ref.ssd_ref(x, dt, a, bm, cm, d)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


def test_ssd_chunked_ref_matches_sequential():
    ks = jax.random.split(KEY, 5)
    B, S, H, P, N = 2, 64, 3, 8, 16
    x = jax.random.normal(ks[0], (B, S, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    a = -jnp.exp(jax.random.normal(ks[2], (H,)))
    bm = jax.random.normal(ks[3], (B, S, N))
    cm = jax.random.normal(ks[4], (B, S, N))
    d = jnp.ones((H,))
    y1, s1 = ref.ssd_ref(x, dt, a, bm, cm, d)
    y2, s2 = ref.ssd_chunked_ref(x, dt, a, bm, cm, d, chunk=16)
    np.testing.assert_allclose(y1, y2, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(s1, s2, rtol=1e-4, atol=1e-4)


def test_ops_dispatch_fallback():
    """On CPU (auto backend) ops fall back to XLA; forcing pallas uses
    interpret mode — both match the oracle (the C7 dispatch contract)."""
    a = jax.random.normal(KEY, (64, 64))
    b = jax.random.normal(jax.random.PRNGKey(1), (64, 64))
    want = ref.matmul_ref(a, b)
    old = ops.BACKEND
    try:
        ops.BACKEND = "xla"
        np.testing.assert_allclose(ops.matmul(a, b), want, rtol=1e-5)
        ops.BACKEND = "pallas"
        np.testing.assert_allclose(ops.matmul(a, b), want, rtol=1e-5)
    finally:
        ops.BACKEND = old


def test_ops_raises_past_vmem_budget_on_tpu(monkeypatch):
    """On a TPU a kernel whose block set does not fit the scoped VMEM
    limit raises; it never falls back to XLA. Off TPU a forced pallas
    still falls back (interpret mode has no VMEM)."""
    q = jnp.zeros((1, 1, 16, 32), jnp.float32)
    big = 64 * 1024                     # a (64k x 64k) f32 score block
    monkeypatch.setattr(ops, "BACKEND", "pallas")
    want = ref.attention_ref(q, q, q)
    np.testing.assert_allclose(ops.attention(q, q, q, bq=big, bk=big), want)
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    with pytest.raises(ops.VmemBudgetError, match="flash_attention"):
        ops.attention(q, q, q, bq=big, bk=big)
