"""Seeded random weights, made by the benchmark and not by the program.

An architecture module (the configuration's ``"reference"``) states its leaves
as tables, ``name -> (shape, kind, fan-in, dtype)`` with kind "matrix",
"embed" or "norm": one table of global leaves, and one per layer group, a
group being a table and a layer count. Each leaf is drawn from its own key:
a global leaf from ``fold_in(seed key, leaf id)``, layer ``l`` of a group's
leaf from ``fold_in(fold_in(group key, leaf id), l)``. The group key of the
group named ``layers`` is the seed key itself; any other group's is
``fold_in(seed key, group id)``, so that leaves named alike in two groups
differ. An id is the CRC-32 of the name.

The program's weights are made on the device in one jitted call, in the type
they are served in; the reference makes one layer at a time from the same keys
and reads the same values in float32 (``layer_weights``).

Values: a matrix is normal with standard deviation 1/sqrt(fan-in); the
embedding is standard normal; a norm's gain is 1 + s with s normal of standard
deviation 0.1, held as s in the served type.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

NORM_SPREAD = 0.1
DEFAULT_GROUP = "layers"


def seed_key(seed: int) -> jax.Array:
    """A threefry key from a seed of any size (more than 32 bits hold)."""
    words = np.random.SeedSequence(seed).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words), impl="threefry2x32")


def padded_vocab(cfg: dict) -> int:
    """Rows of the embedding as the program holds it: the vocabulary rounded up
    to a multiple of 256 (rows past ``vocab_size`` are never used)."""
    return -(-cfg["vocab_size"] // 256) * 256


def served_dtype(cfg: dict):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[cfg["torch_dtype"]]


def _leaf_key(key, name: str):
    return jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)


def _group_key(key, group: str):
    return key if group == DEFAULT_GROUP else _leaf_key(key, group)


def _draw(key, spec) -> jax.Array:
    """The leaf's values in its served type; a norm holds s, not 1 + s."""
    shape, kind, fan_in, dtype = spec
    x = jax.random.normal(key, shape, jnp.float32)
    scale = {"matrix": fan_in ** -0.5, "embed": 1.0, "norm": NORM_SPREAD}[kind]
    return (x * scale).astype(dtype)


def _as_reference(x: jax.Array, kind: str) -> jax.Array:
    x = x.astype(jnp.float32)
    return 1.0 + x if kind == "norm" else x


def _stacked(key, name: str, spec, num_layers: int) -> jax.Array:
    k = _leaf_key(key, name)
    return jax.vmap(lambda l: _draw(jax.random.fold_in(k, l), spec))(
        jnp.arange(num_layers))


def make_served(key, global_table: dict, groups: dict) -> dict:
    """Every leaf, in its served type: {"global": {...}, <group>: {name: (L, ...)}}
    for each group of `groups` ({group: (table, layers)}). Call under jit, so
    that only the served type is materialized."""
    if "global" in groups:
        raise ValueError("a layer group may not be called 'global'")
    out = {"global": {n: _draw(_leaf_key(key, n), s) for n, s in global_table.items()}}
    for group, (table, num_layers) in groups.items():
        gk = _group_key(key, group)
        out[group] = {n: _stacked(gk, n, s, num_layers) for n, s in table.items()}
    return out


def served_bytes(global_table: dict, groups: dict) -> int:
    """Bytes of every leaf in its served type."""
    def size(table, layers=1):
        return sum(layers * int(np.prod(s[0])) * jnp.dtype(s[3]).itemsize
                   for s in table.values())
    return size(global_table) + sum(size(t, l) for t, l in groups.values())


def global_weights(key, table: dict) -> dict:
    """The reference's view of the global leaves, float32, norms as gains."""
    return {n: _as_reference(_draw(_leaf_key(key, n), s), s[1]) for n, s in table.items()}


def layer_weights(key, group: str, table: dict, layer) -> dict:
    """The reference's view of one layer of a group, float32, norms as gains."""
    gk = _group_key(key, group)
    return {n: _as_reference(_draw(jax.random.fold_in(_leaf_key(gk, n), layer), s), s[1])
            for n, s in table.items()}
