"""Tier-placement helpers wiring the emucxl model into jit-compiled training/serving.

Everything here expresses the paper's local/remote split in XLA terms:
  * shardings with ``memory_kind="pinned_host"`` place persistent state (optimizer
    moments, fp32 master params, cold KV pages) in the remote tier;
  * ``device_put`` against a memory-kind sharding *inside* jit emits the cross-space
    DMA, which XLA overlaps with compute — the "distributed-optimization trick" that
    makes offloaded AdamW viable (double-buffered moment fetch);
  * remat policies offload named activations to the host between forward and backward.

Backend gating: the XLA *CPU* backend cannot execute ``annotate_device_placement``,
so memory-space placement inside a compiled computation runs on an accelerator only.
On CPU (tests + the 512-device dry-run) ``resolve_memory_kind`` degrades
``pinned_host`` to ``device`` so everything still compiles, while the
**OffloadManifest** records the intended host residency; the roofline derives the
host-DMA term (the paper's remote-tier latency, Table III analogue) from the manifest
instead of from ``memory_analysis()``. On TPU the same code paths emit real host
placement. Outside-jit placement (``emucxl_alloc/migrate``, KV-page demotion between
decode steps) compiles nothing and uses real ``pinned_host`` memory on the CPU too.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Sequence

import jax
import numpy as np

HOST = "pinned_host"
DEVICE = "device"


def backend_supports_memory_spaces() -> bool:
    """True when the compiled computation may carry buffer-placement annotations."""
    return jax.default_backend() not in ("cpu",)


def resolve_memory_kind(kind: str) -> str:
    """Degrade host placement to device on backends without memory-space support."""
    if kind == HOST and not backend_supports_memory_spaces():
        return DEVICE
    return kind


def with_memory_kind(sharding: jax.sharding.Sharding, kind: str) -> jax.sharding.Sharding:
    """Clone a sharding onto the given memory tier (layout-preserving)."""
    return sharding.with_memory_kind(resolve_memory_kind(kind))


def host_sharding_tree(shardings: Any) -> Any:
    """Map a pytree of shardings to the remote (host) tier."""
    return jax.tree.map(lambda s: with_memory_kind(s, HOST), shardings)


def device_sharding_tree(shardings: Any) -> Any:
    return jax.tree.map(lambda s: with_memory_kind(s, DEVICE), shardings)


def to_tier(tree: Any, shardings: Any, kind: str) -> Any:
    """Inside-jit tier move of a pytree (emucxl_migrate) given its shardings."""
    return jax.tree.map(
        lambda x, s: jax.device_put(x, with_memory_kind(s, kind)), tree, shardings
    )


@dataclasses.dataclass
class OffloadEntry:
    name: str
    nbytes: int
    direction: str  # "resident" (host-held, fetched+written back each step) or "oneway"


@dataclasses.dataclass
class OffloadManifest:
    """Ledger of intended remote-tier residency, independent of backend support.

    The roofline's host-DMA term is ``2 * resident_bytes / host_link_bandwidth`` per
    step (fetch + write-back), matching what ``memory_analysis()`` would report on TPU.
    """

    entries: List[OffloadEntry] = dataclasses.field(default_factory=list)

    def add_tree(self, name: str, tree: Any, direction: str = "resident") -> None:
        leaves = jax.tree.leaves(tree)
        nbytes = 0
        for leaf in leaves:
            if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
                nbytes += int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
        if nbytes:
            self.entries.append(OffloadEntry(name, nbytes, direction))

    @property
    def resident_bytes(self) -> int:
        return sum(e.nbytes for e in self.entries if e.direction == "resident")

    @property
    def total_bytes(self) -> int:
        return sum(e.nbytes for e in self.entries)

    def dma_bytes_per_step(self) -> int:
        """Host-link traffic per train step: resident state crosses twice."""
        return 2 * self.resident_bytes + sum(
            e.nbytes for e in self.entries if e.direction == "oneway"
        )

    def summary(self) -> Dict[str, int]:
        return {e.name: e.nbytes for e in self.entries}

    def stage(self, session, host: int = 0) -> Dict[str, Any]:
        """Materialize the manifest in a v2 ``CXLSession`` (see stage_manifest)."""
        return stage_manifest(self, session, host)


def stage_manifest(manifest: OffloadManifest, session, host: int = 0) -> Dict[str, Any]:
    """Back every manifest entry with a remote-tier v2 session allocation.

    Bridges the jit-side ledger to the emucxl model: each intended host-resident
    tensor becomes a generation-counted ``Buffer`` in the session's shared pool,
    charged to `host`'s quota and placed by the session's placement policy — so
    offload pressure from a training/serving job shows up in ``pool_stats`` and
    (with a fabric) link occupancy, alongside every other consumer. Returns
    {entry name: Buffer}.
    """
    from repro.core.emucxl import REMOTE_MEMORY

    return {
        e.name: session.alloc(e.nbytes, REMOTE_MEMORY, host)
        for e in manifest.entries
        if e.nbytes > 0
    }


def offload_checkpoint_policy(names: Sequence[str]):
    """Remat policy: save listed residuals by name, offloaded to the host tier.

    Only valid on backends with memory-space support; callers must gate on
    ``backend_supports_memory_spaces()`` (the config plumbing in ``optim``/``runtime``
    does this automatically and falls back to plain ``save_only_these_names``).
    """
    if backend_supports_memory_spaces():
        return jax.checkpoint_policies.save_and_offload_only_these_names(
            names_which_can_be_saved=[],
            names_which_can_be_offloaded=list(names),
            offload_src=DEVICE,
            offload_dst=HOST,
        )
    return jax.checkpoint_policies.save_only_these_names(*names)


def remat(fn=None, *, policy=None, prevent_cse: bool = True):
    """``jax.checkpoint`` wrapper with the framework's default settings."""
    if fn is None:
        return functools.partial(remat, policy=policy, prevent_cse=prevent_cse)
    return jax.checkpoint(fn, policy=policy, prevent_cse=prevent_cse)
