"""Production mesh construction (single-pod 16x16, multi-pod 2x16x16).

Defined as FUNCTIONS so importing this module never touches jax device state; the
dry-run sets ``--xla_force_host_platform_device_count=512`` before first jax use and
both mesh sizes slice from the same 512 emulated devices.
"""

from __future__ import annotations

from typing import Tuple

import jax
from jax.sharding import AxisType, Mesh


def _auto_axes(num_axes: int) -> Tuple[AxisType, ...]:
    return (AxisType.Auto,) * num_axes


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape: Tuple[int, ...] = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"production mesh needs {n} devices, found {len(devices)} — the dry-run "
            "must set XLA_FLAGS=--xla_force_host_platform_device_count=512 before "
            "any jax import"
        )
    return jax.make_mesh(
        shape, axes, devices=devices[:n], axis_types=_auto_axes(len(axes))
    )


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    """Arbitrary mesh for tests/examples (sliced from available devices)."""
    n = 1
    for s in shape:
        n *= s
    return jax.make_mesh(
        shape, axes, devices=jax.devices()[:n], axis_types=_auto_axes(len(axes))
    )


def single_device_mesh() -> Mesh:
    return jax.make_mesh((1,), ("data",), devices=jax.devices()[:1],
                         axis_types=_auto_axes(1))
