"""Model-integrated paged decode: one token against the paged KV pool.

The jit-compiled counterpart of kv_manager: attention-family archs decode against
(L, slots, page, K, hd) pools + a block table, using the paged_attention Pallas
kernel per layer (scanned). A decode step is one compiled program: embed -> scan
layers [paged attn + mlp/moe] -> unembed, all reading pages the engine has
promoted to the hot tier. ``cfg`` and ``opts`` are static, so each batch shape is
traced and compiled once and every later call dispatches the cached executable.

New-token K/V are written into the owning page slot of new pools, which the step
returns. The input pools are not donated: a caller may still hold them (a wrapper
around the step may hand its input pools back), and a donated buffer is deleted
once the step runs.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.kernels.paged_attention.ops import paged_attention
from repro.models import layers as ll
from repro.models import moe as moe_lib
from repro.models import transformer as tf
from repro.models.layers import apply_rope, rms_norm

# Times paged_decode_step was traced in this process: one per distinct
# (cfg, opts, argument shapes), since the body runs only while JAX traces.
TRACES = 0


@functools.partial(jax.jit, static_argnums=(1, 7))
def paged_decode_step(
    params,
    cfg: ArchConfig,
    k_pool: jax.Array,        # (L, slots, page, K, hd)
    v_pool: jax.Array,
    block_table: jax.Array,   # (B, max_pages) int32 hot slots
    lengths: jax.Array,       # (B,) int32
    inputs: jax.Array,        # (B, 1) tokens or (B, 1, D)
    opts: tf.ModelOptions = tf.ModelOptions(),
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (logits (B, V), new_k_pool, new_v_pool). Attention families only."""
    global TRACES
    TRACES += 1
    assert cfg.family not in ("ssm", "hybrid"), "paged decode is for attention archs"
    assert not (cfg.moe and cfg.moe_first_dense), "use uniform stacks for paged demo"
    B = inputs.shape[0]
    L, slots, page, K, hd = k_pool.shape
    h = tf.embed_inputs(params, cfg, inputs)
    windows = jnp.asarray(tf.layer_windows(cfg, cfg.num_layers))
    positions = lengths[:, None].astype(jnp.int32)
    page_idx = lengths // page
    offset = lengths % page
    slot_of = block_table[jnp.arange(B), page_idx]             # (B,)

    def body(hh, xs):
        p, win, k_pages, v_pages = xs                          # pools per layer
        x = rms_norm(hh, p["ln1"])
        q = jnp.einsum("bsd,dnh->bsnh", x, p["attn"]["wq"])
        k_new = jnp.einsum("bsd,dkh->bskh", x, p["attn"]["wk"])
        v_new = jnp.einsum("bsd,dkh->bskh", x, p["attn"]["wv"])
        if cfg.qk_norm:
            q = rms_norm(q, p["attn"]["q_norm"])
            k_new = rms_norm(k_new, p["attn"]["k_norm"])
        q = apply_rope(q, positions, cfg.rope_theta)
        k_new = apply_rope(k_new, positions, cfg.rope_theta)
        # write the new token's K/V into its page slot
        k_pages = k_pages.at[slot_of, offset].set(k_new[:, 0].astype(k_pages.dtype))
        v_pages = v_pages.at[slot_of, offset].set(v_new[:, 0].astype(v_pages.dtype))
        out = paged_attention(
            q[:, 0], k_pages, v_pages, block_table, lengths + 1, win,
            scale=float(cfg.resolved_head_dim) ** -0.5,
        )
        a_out = jnp.einsum("bnh,nhd->bd", out.astype(x.dtype), p["attn"]["wo"])
        if cfg.post_norms:
            a_out = rms_norm(a_out[:, None], p["post_ln1"])[:, 0]
        hh = hh + a_out[:, None]
        x2 = rms_norm(hh, p["ln2"])
        if "moe" in p:
            f_out, _ = moe_lib.moe_layer(p["moe"], x2, cfg, impl=opts.moe_impl)
        else:
            f_out = ll.mlp(p["mlp"], x2, cfg.mlp_activation)
        if cfg.post_norms:
            f_out = rms_norm(f_out, p["post_ln2"])
        return hh + f_out, (k_pages, v_pages)

    h, (k_pool, v_pool) = jax.lax.scan(
        body, h, (params["stack"], windows, k_pool, v_pool)
    )
    logits = tf.unembed(params, cfg, h)[:, 0]
    return logits, k_pool, v_pool
