"""WKV6 chunked Pallas TPU kernel (RWKV6 data-dependent per-channel decay).

Grid = (B, H, T/chunk); the chunk axis is innermost/sequential with the WKV state
S in R^{K x V} held in VMEM scratch across chunks. Per chunk the recurrence is the
same masked-matmul form as the XLA path (kernels/rwkv6_scan/ops.py), all exponents
clamped <= 0 so fp32 never overflows regardless of how hard the learned decay
resets. Chunk=16 keeps the (c, c, K) pairwise-decay tile at 64 KiB in VMEM while
the three matmuls per chunk hit the MXU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv6_kernel(
    r_ref, k_ref, v_ref, w_ref,    # (1, 1, c, K/V)
    u_ref,                          # (1, K)
    s0_ref,                         # (1, 1, K, V) initial state
    y_ref,                          # (1, 1, c, V)
    sout_ref,                       # (1, 1, K, V) final state
    s_scr,                          # VMEM (K, V) carried state
    *,
    chunk: int,
):
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        s_scr[...] = s0_ref[0, 0].astype(jnp.float32)

    r = r_ref[0, 0].astype(jnp.float32)            # (c, K)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)            # (c, V)
    w = w_ref[0, 0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)               # (K,)

    logw = jnp.log(jnp.maximum(w, 1e-38))
    cum = jnp.cumsum(logw, axis=0)                 # (c, K), <= 0
    cum_prev = cum - logw
    a_prev = jnp.exp(cum_prev)
    a_last = jnp.exp(cum[-1])                      # (K,)
    a_to_end = jnp.exp(cum[-1][None, :] - cum)     # (c, K), exponent <= 0

    S = s_scr[...]
    y_cross = jax.lax.dot_general(
        r * a_prev, S, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )                                              # (c, V)

    # pairwise per-channel decay, strict lower triangle
    dmat = jnp.exp(jnp.minimum(cum_prev[:, None, :] - cum[None, :, :], 0.0))
    scores = jnp.sum(r[:, None, :] * k[None, :, :] * dmat, axis=-1)  # (c, c)
    tri = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) > \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    scores = jnp.where(tri, scores, 0.0)
    y_intra = jax.lax.dot_general(
        scores, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    u_scores = jnp.sum(r * u[None, :] * k, axis=-1)            # (c,)
    y_ref[0, 0] = (y_cross + y_intra + u_scores[:, None] * v).astype(y_ref.dtype)

    s_scr[...] = a_last[:, None] * S + jax.lax.dot_general(
        k * a_to_end, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(ci == nc - 1)
    def _finish():
        sout_ref[0, 0] = s_scr[...].astype(sout_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6_pallas(r, k, v, w, u, state, *, chunk: int = 16, interpret: bool = False):
    """r,k,w: (B,T,H,K); v: (B,T,H,V); u: (H,K); state: (B,H,K,V)."""
    B, T, H, K = r.shape
    V = v.shape[-1]
    pad = (-T) % chunk
    if pad:
        zf = lambda x: jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        r, k, v = zf(r), zf(k), zf(v)
        w = jnp.pad(w, ((0, 0), (0, pad), (0, 0), (0, 0)), constant_values=1.0)
    Tp = r.shape[1]
    nc = Tp // chunk
    # (B, H, T, *) layout so the chunk axis tiles cleanly
    rt, kt, wt = (jnp.moveaxis(x, 1, 2) for x in (r, k, w))
    vt = jnp.moveaxis(v, 1, 2)

    kernel = functools.partial(_wkv6_kernel, chunk=chunk)
    y, s_out = pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, K), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, chunk, K), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, chunk, V), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, chunk, K), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, K), lambda b, h, i: (h, 0)),
            pl.BlockSpec((1, 1, K, V), lambda b, h, i: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, V), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, K, V), lambda b, h, i: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Tp, V), jnp.float32),
            jax.ShapeDtypeStruct((B, H, K, V), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((K, V), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(rt, kt, vt, wt, u, state)
    return jnp.moveaxis(y, 2, 1)[:, :T], s_out
