"""jit'd wrapper: model-facing flash attention with GQA head handling.

On the CPU backend the kernel runs in interpret mode (Python execution of the
kernel body); on an accelerator it is compiled.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import interpret_default
from repro.kernels.flash_attention.flash_attention import flash_attention_bnh


def flash_attention(q, k, v, *, window, scale: float, causal: bool = True,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool | None = None) -> jax.Array:
    """q: (B,S,N,hd); k,v: (B,T,K,hd) -> (B,S,N,hd)."""
    B, S, N, hd = q.shape
    K = k.shape[2]
    if K != N:
        rep = N // K
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    T = k.shape[1]
    qf = jnp.moveaxis(q, 2, 1).reshape(B * N, S, hd)
    kf = jnp.moveaxis(k, 2, 1).reshape(B * N, T, hd)
    vf = jnp.moveaxis(v, 2, 1).reshape(B * N, T, hd)
    out = flash_attention_bnh(
        qf, kf, vf, window, causal=causal, scale=scale,
        block_q=block_q, block_k=block_k,
        interpret=interpret_default() if interpret is None else interpret,
    )
    return jnp.moveaxis(out.reshape(B, N, S, hd), 1, 2)
