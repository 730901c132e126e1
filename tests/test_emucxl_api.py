"""Paper Table II API surface + Fig 3 lifecycle + hypothesis property tests."""

import contextlib
import types

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import emucxl as ecxl
from repro.core.emucxl import EmuCXL, EmuCXLError, OutOfTierMemory


# ------------------------------------------------------------------ lifecycle (Fig 3)
def test_lifecycle(lib):
    addr = lib.alloc(4096, ecxl.LOCAL_MEMORY)
    assert lib.is_local(addr)
    lib.free(addr)
    lib.exit()
    with pytest.raises(EmuCXLError):
        lib.alloc(16, ecxl.LOCAL_MEMORY)
    lib.init()  # re-init works after exit


def test_double_init_rejected(lib):
    with pytest.raises(EmuCXLError):
        lib.init()


def test_alloc_invalid_node(lib):
    with pytest.raises(EmuCXLError):
        lib.alloc(16, 2)


# ------------------------------------------------------------------ Table II semantics
def test_alloc_tiers_and_memory_kind(lib):
    a = lib.alloc(128, ecxl.LOCAL_MEMORY)
    b = lib.alloc(128, ecxl.REMOTE_MEMORY)
    assert lib.get_numa_node(a) == 0 and lib.get_numa_node(b) == 1
    # Tier -> memory-space mapping is resolved against the runtime: "device" /
    # "pinned_host" where supported, the device default kind otherwise.
    assert (lib.allocations()[a].data.sharding.memory_kind
            == lib.memory_kind(ecxl.LOCAL_MEMORY))
    assert (lib.allocations()[b].data.sharding.memory_kind
            == lib.memory_kind(ecxl.REMOTE_MEMORY))


def test_data_ops_keep_each_tier_in_its_memory_kind(lib):
    """Writes, memsets, copies and resizes replace an allocation's buffer; the
    new buffer must stay in its tier's memory kind."""
    a = lib.alloc(256, ecxl.REMOTE_MEMORY)
    b = lib.alloc(256, ecxl.LOCAL_MEMORY)
    lib.write(np.arange(64, dtype=np.uint8), 32, a)
    lib.memset(b, 0xFF, 128)
    lib.memcpy(a, b, 64)
    lib.memcpy(b, a, 16)
    c = lib.resize(a, 512)
    for addr, node in ((b, ecxl.LOCAL_MEMORY), (c, ecxl.REMOTE_MEMORY)):
        assert (lib.allocations()[addr].data.sharding.memory_kind
                == lib.memory_kind(node))
    assert np.array_equal(lib.read(c, 0, 80)[60:80], np.r_[
        np.full(4, 0xFF, np.uint8), np.arange(32, 48, dtype=np.uint8)])


class _FakeDevice:
    def __init__(self, platform, kind, memory_kinds):
        self.platform, self.device_kind = platform, kind
        self._kinds = memory_kinds

    def addressable_memories(self):
        return [types.SimpleNamespace(kind=k) for k in self._kinds]

    def default_memory(self):
        return types.SimpleNamespace(kind=self._kinds[0])


@pytest.mark.parametrize("device, error", [
    (_FakeDevice("tpu", "TPU v5 lite", ["device"]), "memory kinds"),
    (_FakeDevice("tpu", "TPU v99", ["device", "pinned_host"]), "HARDWARE_MODELS"),
])
def test_init_refuses_an_accelerator_it_cannot_model(device, error):
    with pytest.raises(EmuCXLError, match=error):
        EmuCXL().init(device=device)


def test_cpu_without_host_memory_collapses_tiers():
    lib = EmuCXL()
    lib.init(device=_FakeDevice("cpu", "cpu", ["device"]))
    assert lib.memory_kind(ecxl.LOCAL_MEMORY) == lib.memory_kind(
        ecxl.REMOTE_MEMORY) == "device"


def test_read_write_roundtrip(lib):
    a = lib.alloc(256, ecxl.REMOTE_MEMORY)
    payload = np.arange(64, dtype=np.uint8)
    assert lib.write(payload, 32, a)
    assert np.array_equal(lib.read(a, 32, 64), payload)


def test_migrate_preserves_data_and_accounting(lib):
    a = lib.alloc(512, ecxl.LOCAL_MEMORY)
    lib.write(np.full(512, 7, np.uint8), 0, a)
    before_local = lib.stats(0)
    b = lib.migrate(a, ecxl.REMOTE_MEMORY)
    assert lib.stats(0) == before_local - 512
    assert lib.stats(1) >= 512
    assert not lib.is_local(b)
    assert np.all(lib.read(b, 0, 512) == 7)
    with pytest.raises(EmuCXLError):
        lib.get_size(a)  # old address invalid after migration


def test_resize_copies_prefix(lib):
    a = lib.alloc(64, ecxl.LOCAL_MEMORY)
    lib.write(np.arange(64, dtype=np.uint8), 0, a)
    b = lib.resize(a, 128)
    assert lib.get_size(b) == 128
    assert np.array_equal(lib.read(b, 0, 64), np.arange(64, dtype=np.uint8))


def test_memset_memcpy_memmove(lib):
    a = lib.alloc(64, ecxl.LOCAL_MEMORY)
    b = lib.alloc(64, ecxl.REMOTE_MEMORY)
    lib.memset(a, -1, 64)
    assert np.all(lib.read(a, 0, 64) == 255)
    lib.memcpy(b, a, 64)
    assert np.all(lib.read(b, 0, 64) == 255)
    lib.memset(a, 0, 32)
    lib.memmove(b, a, 64)
    assert np.all(lib.read(b, 0, 32) == 0)


def test_oom_raises_with_details(lib):
    with pytest.raises(OutOfTierMemory) as ei:
        lib.alloc((1 << 24) + 1, ecxl.LOCAL_MEMORY)
    assert ei.value.node == 0


def test_free_size_validation(lib):
    a = lib.alloc(100, ecxl.LOCAL_MEMORY)
    with pytest.raises(EmuCXLError):
        lib.free(a, 200)
    lib.free(a, 100)


def test_bounds_checking(lib):
    a = lib.alloc(64, ecxl.LOCAL_MEMORY)
    with pytest.raises(EmuCXLError):
        lib.read(a, 60, 8)
    with pytest.raises(EmuCXLError):
        lib.write(np.zeros(8, np.uint8), 60, a)


# ------------------------------------------------------------------ properties
@settings(max_examples=30)
@given(
    ops=st.lists(
        st.tuples(st.integers(1, 4096), st.integers(0, 1), st.booleans()),
        min_size=1, max_size=40,
    )
)
def test_accounting_invariant(ops):
    """stats(node) always equals the sum of live allocation sizes per node."""
    lib = EmuCXL()
    lib.init(local_capacity=1 << 20, remote_capacity=1 << 20)
    live = {}
    for size, node, also_free in ops:
        with contextlib.suppress(OutOfTierMemory):
            addr = lib.alloc(size, node)
            live[addr] = (size, node)
        if also_free and live:
            addr = next(iter(live))
            lib.free(addr)
            del live[addr]
        for n in (0, 1):
            expect = sum(s for s, nn in live.values() if nn == n)
            assert lib.stats(n) == expect
    lib.exit()


@settings(max_examples=20)
@given(
    size=st.integers(1, 2048),
    offset_frac=st.floats(0, 1),
    data=st.binary(min_size=1, max_size=256),
)
def test_write_read_identity(size, offset_frac, data):
    lib = EmuCXL()
    lib.init(local_capacity=1 << 20, remote_capacity=1 << 20)
    n = min(len(data), size)
    offset = int((size - n) * offset_frac)
    a = lib.alloc(size, ecxl.REMOTE_MEMORY)
    lib.write(np.frombuffer(data[:n], np.uint8), offset, a)
    assert lib.read(a, offset, n).tobytes() == data[:n]
    lib.exit()


@settings(max_examples=15)
@given(st.lists(st.integers(0, 1), min_size=1, max_size=8),
       st.binary(min_size=1, max_size=128))
def test_migration_chain_preserves_bytes(nodes, data):
    """Any sequence of migrations preserves contents exactly."""
    lib = EmuCXL()
    lib.init(local_capacity=1 << 20, remote_capacity=1 << 20)
    a = lib.alloc(len(data), ecxl.LOCAL_MEMORY)
    lib.write(np.frombuffer(data, np.uint8), 0, a)
    for node in nodes:
        a = lib.migrate(a, node)
    assert lib.read(a, 0, len(data)).tobytes() == data
    lib.exit()
