"""Pieces that every architecture's plain reference shares: normalisation,
rotary positions, matrix products at full float32 precision with a `quant` on
their inputs, the float8 control's rounding, the blocks of requests the
reference runs in, and the gaps that ``bench/run.py compare`` reads.

Nothing here is taken from the program.
"""

from __future__ import annotations

from typing import Callable, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

Quant = Callable[[jax.Array, int], jax.Array]   # (x, axis of the reduction) -> x


def identity(x: jax.Array, axis: int) -> jax.Array:
    return x


def fp8(x: jax.Array, axis: int) -> jax.Array:
    """float8_e4m3fn with one scale per slice along `axis` (per token for
    activations, per output channel for weights), back in float32."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def rms_norm(x: jax.Array, gain: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x (B, S, H, hd): rotate the first half of each head against the second."""
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = positions[:, :, None, None].astype(jnp.float32) * freqs
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1)


def mm(quant: Quant, spec: str, x, w, x_axis: int, w_axis: int):
    return jnp.einsum(spec, quant(x, x_axis), quant(w, w_axis),
                      precision=jax.lax.Precision.HIGHEST)


def blocks(requests: Sequence[tuple], seq_len: int, batch: int):
    """Blocks of `batch` (prompt, served tokens) pairs as token arrays padded to
    `seq_len`, so that every call has the same shapes. A sequence holds its
    prompt and every served token but the last, which nothing reads."""
    for i in range(0, len(requests), batch):
        chunk = requests[i:i + batch]
        tokens = np.zeros((batch, seq_len), np.int32)
        for j, (prompt, served) in enumerate(chunk):
            seq = list(prompt) + list(served)[:-1]
            tokens[j, : len(seq)] = seq
        yield chunk, tokens


def served_rows(logits: Callable[[np.ndarray], np.ndarray], requests: Sequence[tuple],
                seq_len: int, batch: int) -> List[np.ndarray]:
    """For each (prompt, served tokens) pair, the rows (served, vocab) of
    `logits` (tokens (B, S) -> logits (B, S, vocab)) at the positions that
    produced the served tokens."""
    rows = []
    for chunk, tokens in blocks(requests, seq_len, batch):
        lg = logits(tokens)
        for j, (prompt, served) in enumerate(chunk):
            rows.append(lg[j, len(prompt) - 1: len(prompt) - 1 + len(served)])
    return rows


def token_gaps(reference: np.ndarray, chosen) -> np.ndarray:
    """By how much each chosen token's reference logit lies below the best of
    its row (0 where it is the reference's first choice)."""
    return reference.max(axis=-1) - reference[np.arange(len(reference)), np.asarray(chosen)]


def logprob_gap(reference: np.ndarray, other: np.ndarray) -> float:
    """The widest gap, over every position and token, between the log-softmax
    of two sets of logit rows, in float64."""
    def log_softmax(x):
        x = np.asarray(x, np.float64)
        x = x - x.max(axis=-1, keepdims=True)
        return x - np.log(np.exp(x).sum(axis=-1, keepdims=True))
    return float(np.abs(log_softmax(reference) - log_softmax(other)).max())
