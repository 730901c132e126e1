"""Flash attention Pallas TPU kernel (causal / sliding-window, GQA-aware).

Tiling: grid = (batch*q_heads, num_q_blocks, num_k_blocks); the k-block axis is the
innermost (sequential on TPU), with the running max / normalizer / accumulator held
in VMEM scratch across k steps — the classic flash recurrence:

    m' = max(m, rowmax(S));  l' = l*e^{m-m'} + rowsum(e^{S-m'});  acc' = acc*e^{m-m'} + e^{S-m'} V

Block shapes are (BLOCK_Q, head_dim) x (BLOCK_K, head_dim) — multiples of 128 on the
contracting/lane dims so the MXU tiles cleanly. The sliding window arrives as a
scalar-prefetch operand (it is *data*: per-layer windows ride through lax.scan).
Fully-masked k blocks are skipped via @pl.when, which is what makes sliding-window
layers O(S*window) rather than O(S^2).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1.0e30
DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128


def _flash_kernel(
    window_ref,            # scalar prefetch: (1,) int32
    q_ref,                 # (1, block_q, hd)
    k_ref,                 # (1, block_k, hd)
    v_ref,                 # (1, block_k, hd)
    o_ref,                 # (1, block_q, hd)
    m_scr,                 # VMEM (block_q,)
    l_scr,                 # VMEM (block_q,)
    acc_scr,               # VMEM (block_q, hd)
    *,
    block_q: int,
    block_k: int,
    seq_len: int,
    causal: bool,
    scale: float,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    window = window_ref[0]

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = ki * block_k

    # block-level reachability: causal => k_start <= q_end; window => k covers
    # [q_start - window + 1, q_end]
    q_end = q_start + block_q - 1
    reachable = jnp.logical_and(
        k_start <= q_end if causal else True,
        k_start + block_k - 1 >= q_start - window + 1,
    )

    @pl.when(reachable)
    def _compute():
        q = q_ref[0].astype(jnp.float32)           # (bq, hd)
        k = k_ref[0].astype(jnp.float32)           # (bk, hd)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                   # (bq, bk)

        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = (kpos < seq_len) & (qpos - kpos < window)
        if causal:
            mask = mask & (qpos >= kpos)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_scr[...] * alpha + jnp.sum(p, axis=1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_scr[...]
        o_ref[0] = (acc_scr[...] / jnp.maximum(l, 1e-20)[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "scale", "block_q", "block_k", "interpret"),
)
def flash_attention_bnh(
    q: jax.Array,            # (BN, S, hd)  batch*heads flattened
    k: jax.Array,            # (BN, T, hd)  kv heads already broadcast to q heads
    v: jax.Array,
    window: jax.Array,       # () or (1,) int32
    *,
    causal: bool = True,
    scale: float = 1.0,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
) -> jax.Array:
    BN, S, hd = q.shape
    T = k.shape[1]
    bq = min(block_q, S)
    bk = min(block_k, T)
    pad_q = (-S) % bq
    pad_k = (-T) % bk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0)))
    Sp, Tp = q.shape[1], k.shape[1]
    grid = (BN, Sp // bq, Tp // bk)

    kernel = functools.partial(
        _flash_kernel, block_q=bq, block_k=bk, seq_len=T, causal=causal, scale=scale
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                # index maps receive the scalar-prefetch ref as a trailing arg
                pl.BlockSpec((1, bq, hd), lambda b, i, j, *_: (b, i, 0)),
                pl.BlockSpec((1, bk, hd), lambda b, i, j, *_: (b, j, 0)),
                pl.BlockSpec((1, bk, hd), lambda b, i, j, *_: (b, j, 0)),
            ],
            out_specs=pl.BlockSpec((1, bq, hd), lambda b, i, j, *_: (b, i, 0)),
            scratch_shapes=[
                pltpu.VMEM((bq,), jnp.float32),
                pltpu.VMEM((bq,), jnp.float32),
                pltpu.VMEM((bq, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((BN, Sp, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(jnp.asarray(window, jnp.int32).reshape(1), q, k, v)
    return out[:, :S]
