"""The main path's kernel and decode step, compiled for a TPU v5e without a chip.

The TPU compiler ships with jaxlib and compiles for a described topology. It
refuses what the chip would refuse: block shapes Mosaic cannot tile, programs
that do not fit the device's memory. Nothing runs, so these tests say nothing of
results or times.

The topology is described inside a fixture, never at import: only one process at
a time may load the TPU library, and every test worker imports this file.
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.paged_attention import ops as paged_ops
from repro.models import transformer as tf
from repro.serving.paged_decode import paged_decode_step

V5E_HBM_BYTES = 16 * 2**30
PAGE_SIZE = 16


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # else the compiler logs under /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def compiled_kernels(monkeypatch):
    """The CPU backend would interpret the Pallas kernel; compile it instead."""
    monkeypatch.setattr(paged_ops, "interpret_default", lambda: False)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("arch", ["deepseek-coder-33b", "gemma3-1b"])
def test_paged_attention_compiles(arch, one_chip, compiled_kernels):
    """At the model's head layout, with what the engine passes: bf16 queries
    against float32 pages."""
    cfg = get_config(arch)
    N, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    B, P, max_pages = 4, 64, 10
    pages = _spec((P, PAGE_SIZE, K, hd), jnp.float32, one_chip)
    compiled = jax.jit(
        lambda *a: paged_ops.paged_attention(*a, scale=hd ** -0.5)
    ).lower(
        _spec((B, N, hd), jnp.bfloat16, one_chip), pages, pages,
        _spec((B, max_pages), jnp.int32, one_chip),
        _spec((B,), jnp.int32, one_chip), _spec((), jnp.int32, one_chip),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_paged_decode_step_compiles(one_chip, compiled_kernels):
    """One DeepSeek-Coder-33B layer at its published widths fits the chip."""
    cfg = dataclasses.replace(get_config("deepseek-coder-33b"), num_layers=1)
    params = jax.tree.map(
        lambda s: _spec(s.shape, s.dtype, one_chip),
        jax.eval_shape(lambda k: tf.init_params(k, cfg),
                       jax.ShapeDtypeStruct((2,), jnp.uint32)),
    )
    B, slots, max_pages = 4, 64, 10
    pool = _spec((cfg.num_layers, slots, PAGE_SIZE, cfg.num_kv_heads,
                  cfg.resolved_head_dim), jnp.float32, one_chip)
    compiled = jax.jit(paged_decode_step, static_argnums=(1, 7)).lower(
        params, cfg, pool, pool, _spec((B, max_pages), jnp.int32, one_chip),
        _spec((B,), jnp.int32, one_chip), _spec((B, 1), jnp.int32, one_chip),
        tf.ModelOptions(moe_impl="dense"),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert held <= V5E_HBM_BYTES, f"{held / 2**30:.2f} GiB"
