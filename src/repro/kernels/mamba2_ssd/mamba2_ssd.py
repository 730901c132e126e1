"""Mamba2 SSD chunked Pallas TPU kernel (scalar-per-head decay).

Grid = (B, H, T/chunk), chunk axis sequential with the state H in R^{P x N} in VMEM
scratch. Same math as the XLA chunked path (kernels/mamba2_ssd/ops.py): within a
chunk the recurrence is two (c x c)/(c x N) matmuls plus decay weightings, with all
exponents <= 0 (A < 0, dt > 0). A and D arrive as scalar-prefetch operands (SMEM).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(
    A_ref, D_ref,                 # scalar prefetch: (H,) each
    x_ref,                        # (1, 1, c, P)
    dt_ref,                       # (1, 1, c)
    b_ref,                        # (1, c, N)
    c_ref,                        # (1, c, N)
    h0_ref,                       # (1, 1, P, N)
    y_ref,                        # (1, 1, c, P)
    hout_ref,                     # (1, 1, P, N)
    h_scr,                        # VMEM (P, N)
    *,
    chunk: int,
):
    h = pl.program_id(1)
    ci = pl.program_id(2)
    nc = pl.num_programs(2)
    A = A_ref[h]
    Dh = D_ref[h]

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = h0_ref[0, 0].astype(jnp.float32)

    x = x_ref[0, 0].astype(jnp.float32)          # (c, P)
    dt = dt_ref[0, 0].astype(jnp.float32)        # (c,)
    Bm = b_ref[0].astype(jnp.float32)            # (c, N)
    C = c_ref[0].astype(jnp.float32)

    L = A * jnp.cumsum(dt)                       # (c,), <= 0
    ai = jnp.exp(L)
    al = jnp.exp(L[-1])
    ae = jnp.exp(L[-1] - L)                      # <= 1

    Hst = h_scr[...]                             # (P, N)
    cb = jax.lax.dot_general(
        C, Bm, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                            # (c, c)
    decay = jnp.exp(jnp.minimum(L[:, None] - L[None, :], 0.0))
    tri = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    scores = jnp.where(tri, cb * decay * dt[None, :], 0.0)
    y_intra = jax.lax.dot_general(
        scores, x, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )                                            # (c, P)
    y_cross = ai[:, None] * jax.lax.dot_general(
        C, Hst, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                            # (c, P)
    y_ref[0, 0] = (y_intra + y_cross + Dh * x).astype(y_ref.dtype)

    upd = jax.lax.dot_general(
        x * (dt * ae)[:, None], Bm, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                            # (P, N)
    h_scr[...] = al * Hst + upd

    @pl.when(ci == nc - 1)
    def _finish():
        hout_ref[0, 0] = h_scr[...].astype(hout_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_pallas(x, dt, A, Bm, C, D, state, *, chunk: int = 32, interpret: bool = False):
    """x: (B,T,H,P); dt: (B,T,H); A,D: (H,); Bm,C: (B,T,N); state: (B,H,P,N)."""
    B, T, H, P = x.shape
    N = Bm.shape[-1]
    pad = (-T) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    Tp = x.shape[1]
    nc = Tp // chunk
    xt = jnp.moveaxis(x, 1, 2)                   # (B, H, T, P)
    dtt = jnp.moveaxis(dt, 1, 2)                 # (B, H, T)

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    y, h_out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, H, nc),
            in_specs=[
                pl.BlockSpec((1, 1, chunk, P), lambda b, h, i, *_: (b, h, i, 0)),
                pl.BlockSpec((1, 1, chunk), lambda b, h, i, *_: (b, h, i)),
                pl.BlockSpec((1, chunk, N), lambda b, h, i, *_: (b, i, 0)),
                pl.BlockSpec((1, chunk, N), lambda b, h, i, *_: (b, i, 0)),
                pl.BlockSpec((1, 1, P, N), lambda b, h, i, *_: (b, h, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, chunk, P), lambda b, h, i, *_: (b, h, i, 0)),
                pl.BlockSpec((1, 1, P, N), lambda b, h, i, *_: (b, h, 0, 0)),
            ],
            scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Tp, P), jnp.float32),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(A.astype(jnp.float32), D.astype(jnp.float32), xt, dtt, Bm, C, state)
    return jnp.moveaxis(y, 2, 1)[:, :T], h_out
