"""Paged-attention decode kernel: one new token against a slab-allocated KV pool.

This is the compute side of the paper's slab-allocator middleware (core/slab.py):
KV pages are fixed-size chunks handed out by the slab allocator; hot pages live in
HBM, cold pages are demoted to the host tier by the KV-cache manager
(serving/kv_manager.py) using the paper's Policy1/Policy2. The kernel consumes the
HBM-resident pool + a per-sequence block table.

Layout: q (B, N, hd); pages (P, page_size, K, hd). Grid = (B, max_pages): one
step fetches every kv head of one page, so each block spans the array's two minor
dims, which Mosaic requires of a block below (8, 128). Query head n belongs to kv
head n // (N // K); the scores are computed against all page_size*K rows of the
page and the other heads' rows masked out. The page axis is innermost with
flash-style running max/normalizer in VMEM scratch. The *index map reads the block
table from scalar-prefetch SMEM* — a data-dependent gather of pages straight into
VMEM, which is exactly the TPU-native replacement for the paper's pointer-chasing
remote reads. The compute of pages past a sequence's length is skipped (@pl.when);
their fetch is not.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1.0e30


def _paged_kernel(
    table_ref,            # scalar prefetch: (B, max_pages) int32
    len_ref,              # scalar prefetch: (B,) int32
    window_ref,           # scalar prefetch: (1,) int32
    q_ref,                # (1, N, hd)
    k_ref,                # (1, page_size, K, hd)  — page selected by index map
    v_ref,
    o_ref,                # (1, N, hd)
    m_scr, l_scr, acc_scr,
    *,
    page_size: int,
    kv_heads: int,
    scale: float,
):
    b = pl.program_id(0)
    p = pl.program_id(1)
    np_ = pl.num_programs(1)
    length = len_ref[b]
    window = window_ref[0]

    @pl.when(p == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    page_start = p * page_size
    q_pos = length - 1
    live = jnp.logical_and(page_start < length,
                           page_start + page_size - 1 > q_pos - window)

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                     # (N, hd)
        n, hd = q.shape
        # Row t*K + h of the flattened page is token t of kv head h.
        k = k_ref[0].astype(jnp.float32).reshape(page_size * kv_heads, hd)
        v = v_ref[0].astype(jnp.float32).reshape(page_size * kv_heads, hd)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        ) * scale                                            # (N, page_size*K)
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        pos = page_start + col // kv_heads
        # GQA: query head n attends only to kv head n // (N // K).
        mask = ((col % kv_heads == row // (n // kv_heads))
                & (pos < length) & (q_pos - pos < window))
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                                  # (N, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        pexp = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(pexp, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            pexp, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )
        m_scr[...] = m_new

    @pl.when(p == np_ - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-20)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("scale", "interpret")
)
def paged_attention(
    q: jax.Array,            # (B, N, hd) — one token per sequence
    k_pages: jax.Array,      # (P, page_size, K, hd)
    v_pages: jax.Array,
    block_table: jax.Array,  # (B, max_pages) int32 page ids
    lengths: jax.Array,      # (B,) int32
    window: jax.Array,       # () int32
    *,
    scale: float,
    interpret: bool = False,
) -> jax.Array:
    B, N, hd = q.shape
    P, page_size, K, _ = k_pages.shape
    max_pages = block_table.shape[1]

    kernel = functools.partial(_paged_kernel, page_size=page_size, kv_heads=K,
                               scale=scale)
    # Every block spans the full extent of the array's two minor dims (N, hd)
    # and (K, hd), as Mosaic requires of blocks smaller than 8x128.
    page_spec = pl.BlockSpec(
        (1, page_size, K, hd),
        # data-dependent page fetch: the block table IS the index map
        lambda b, p, table, lens, win: (table[b, p], 0, 0, 0),
    )
    q_spec = pl.BlockSpec((1, N, hd), lambda b, p, *_: (b, 0, 0))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, max_pages),
            in_specs=[q_spec, page_spec, page_spec],
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((N, 1), jnp.float32),
                pltpu.VMEM((N, 1), jnp.float32),
                pltpu.VMEM((N, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, N, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(block_table.astype(jnp.int32), lengths.astype(jnp.int32),
      jnp.asarray(window, jnp.int32).reshape(1), q, k_pages, v_pages)
