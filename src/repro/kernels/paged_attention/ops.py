"""jit'd wrapper for paged decode attention (impl selection + interpret gating)."""

from __future__ import annotations

import jax

from repro.kernels import interpret_default
from repro.kernels.paged_attention.paged_attention import paged_attention as _kernel
from repro.kernels.paged_attention.ref import paged_attention_ref


def paged_attention(q, k_pages, v_pages, block_table, lengths, window, *,
                    scale: float, impl: str = "pallas",
                    interpret: bool | None = None) -> jax.Array:
    if impl == "ref":
        return paged_attention_ref(q, k_pages, v_pages, block_table, lengths,
                                   window, scale=scale)
    return _kernel(q, k_pages, v_pages, block_table, lengths, window, scale=scale,
                   interpret=interpret_default() if interpret is None else interpret)
