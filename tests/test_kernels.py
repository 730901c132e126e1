"""Per-kernel shape/dtype sweeps: Pallas (interpret) and XLA paths vs jnp oracles."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.mamba2_ssd.ops import ssd
from repro.kernels.mamba2_ssd.mamba2_ssd import ssd_pallas
from repro.kernels.mamba2_ssd.ref import ssd_ref
from repro.kernels.paged_attention.ops import paged_attention
from repro.kernels.paged_attention.ref import paged_attention_ref
from repro.kernels.rwkv6_scan.ops import wkv6, wkv6_decode_step
from repro.kernels.rwkv6_scan.rwkv6_scan import wkv6_pallas
from repro.kernels.rwkv6_scan.ref import wkv6_ref

RNG = np.random.default_rng(7)


def _rand(shape, dtype, scale=0.5):
    return jnp.asarray(RNG.standard_normal(shape) * scale, dtype)


# ------------------------------------------------------------------ flash attention
@pytest.mark.parametrize("B,S,N,K,hd", [
    (1, 128, 4, 4, 64),
    (2, 256, 8, 2, 64),
    (1, 384, 4, 1, 128),     # S not a block multiple
    (2, 200, 2, 2, 32),
])
@pytest.mark.parametrize("window", [1 << 30, 64])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, S, N, K, hd, window, dtype):
    q, k, v = (_rand((B, S, h, hd), dtype) for h in (N, K, K))
    w = jnp.int32(window)
    out = flash_attention(q, k, v, window=w, scale=hd ** -0.5, interpret=True)
    ref = attention_ref(q, k, v, w, scale=hd ** -0.5)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


def test_flash_attention_non_causal():
    q, k, v = (_rand((2, 128, 4, 64), jnp.float32) for _ in range(3))
    out = flash_attention(q, k, v, window=jnp.int32(1 << 30), scale=0.125,
                          causal=False, interpret=True)
    ref = attention_ref(q, k, v, jnp.int32(1 << 30), causal=False, scale=0.125)
    np.testing.assert_allclose(out, ref, atol=2e-5)


# ------------------------------------------------------------------ paged attention
@pytest.mark.parametrize("B,N,K,hd,page,maxp,q_dtype", [
    (2, 4, 2, 64, 16, 4, jnp.float32),
    (3, 8, 8, 32, 8, 6, jnp.float32),
    (1, 8, 1, 128, 32, 2, jnp.float32),
    # DeepSeek-Coder-33B's head layout as the serving engine passes it:
    # bf16 queries against float32 pages.
    (2, 56, 8, 128, 16, 4, jnp.bfloat16),
], ids=["2-4-2-64-16-4", "3-8-8-32-8-6", "1-8-1-128-32-2", "2-56-8-128-16-4-bf16"])
@pytest.mark.parametrize("window", [1 << 30, 24])
def test_paged_attention_sweep(B, N, K, hd, page, maxp, q_dtype, window):
    P = B * maxp + 2
    q = _rand((B, N, hd), q_dtype)
    kp = _rand((P, page, K, hd), jnp.float32)
    vp = _rand((P, page, K, hd), jnp.float32)
    table = jnp.asarray(
        RNG.permutation(P)[: B * maxp].reshape(B, maxp), jnp.int32
    )
    lengths = jnp.asarray(RNG.integers(1, page * maxp, (B,)), jnp.int32)
    out = paged_attention(q, kp, vp, table, lengths, jnp.int32(window),
                          scale=hd ** -0.5, interpret=True)
    ref = paged_attention_ref(q.astype(jnp.float32), kp, vp, table, lengths,
                              jnp.int32(window), scale=hd ** -0.5)
    assert out.dtype == q_dtype
    # A bf16 output carries its own rounding, up to 2^-9 relative.
    tol = dict(atol=2e-5) if q_dtype == jnp.float32 else dict(rtol=2.0 ** -8, atol=1e-5)
    np.testing.assert_allclose(out.astype(jnp.float32), ref, **tol)


# ------------------------------------------------------------------ wkv6
@pytest.mark.parametrize("B,T,H,K,V", [(2, 64, 2, 16, 16), (1, 50, 4, 32, 32)])
@pytest.mark.parametrize("impl", ["chunked", "pallas"])
def test_wkv6_sweep(B, T, H, K, V, impl):
    r, k = _rand((B, T, H, K), jnp.float32), _rand((B, T, H, K), jnp.float32)
    v = _rand((B, T, H, V), jnp.float32)
    w = jnp.asarray(RNG.uniform(1e-5, 0.999, (B, T, H, K)), jnp.float32)
    u = _rand((H, K), jnp.float32, 0.1)
    s0 = _rand((B, H, K, V), jnp.float32, 0.1)
    y_ref, S_ref = wkv6_ref(r, k, v, w, u, s0)
    if impl == "pallas":
        y, S = wkv6_pallas(r, k, v, w, u, s0, chunk=16, interpret=True)
    else:
        y, S = wkv6(r, k, v, w, u, s0, impl="chunked")
    np.testing.assert_allclose(y, y_ref, atol=3e-4)
    np.testing.assert_allclose(S, S_ref, atol=3e-4)


def test_wkv6_decode_matches_scan():
    B, T, H, K = 2, 8, 2, 8
    r, k, v = (_rand((B, T, H, K), jnp.float32) for _ in range(3))
    w = jnp.asarray(RNG.uniform(0.3, 0.99, (B, T, H, K)), jnp.float32)
    u = _rand((H, K), jnp.float32, 0.1)
    s = jnp.zeros((B, H, K, K))
    y_ref, _ = wkv6_ref(r, k, v, w, u, s)
    ys = []
    for t in range(T):
        y, s = wkv6_decode_step(r[:, t], k[:, t], v[:, t], w[:, t], u, s)
        ys.append(y)
    np.testing.assert_allclose(jnp.stack(ys, 1), y_ref, atol=1e-5)


# ------------------------------------------------------------------ mamba2 ssd
@pytest.mark.parametrize("B,T,H,P,N", [(2, 64, 2, 16, 8), (1, 45, 4, 8, 16)])
@pytest.mark.parametrize("impl", ["chunked", "pallas"])
def test_ssd_sweep(B, T, H, P, N, impl):
    x = _rand((B, T, H, P), jnp.float32)
    dt = jnp.asarray(RNG.uniform(1e-3, 2.0, (B, T, H)), jnp.float32)
    A = jnp.asarray(-RNG.uniform(0.5, 8.0, (H,)), jnp.float32)
    Bm, C = _rand((B, T, N), jnp.float32), _rand((B, T, N), jnp.float32)
    D = _rand((H,), jnp.float32, 0.1)
    h0 = _rand((B, H, P, N), jnp.float32, 0.1)
    y_ref, H_ref = ssd_ref(x, dt, A, Bm, C, D, h0)
    if impl == "pallas":
        y, Hf = ssd_pallas(x, dt, A, Bm, C, D, h0, chunk=16, interpret=True)
    else:
        y, Hf = ssd(x, dt, A, Bm, C, D, h0, impl="chunked", chunk=16)
    np.testing.assert_allclose(y, y_ref, atol=5e-4)
    np.testing.assert_allclose(Hf, H_ref, atol=5e-4)


def test_kernels_differentiate():
    """Training path: grads flow through the chunked impls without NaN."""
    B, T, H, K = 1, 32, 2, 8
    r, k, v = (_rand((B, T, H, K), jnp.float32) for _ in range(3))
    w = jnp.asarray(RNG.uniform(0.5, 0.99, (B, T, H, K)), jnp.float32)
    u = _rand((H, K), jnp.float32, 0.1)
    s0 = jnp.zeros((B, H, K, K))

    def loss(r, k, v, w):
        y, _ = wkv6(r, k, v, w, u, s0, impl="chunked")
        return jnp.sum(y ** 2)

    grads = jax.grad(loss, argnums=(0, 1, 2, 3))(r, k, v, w)
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in grads)
