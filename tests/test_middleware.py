"""Middleware tests: KV store (paper Table IV), slab allocator, direct-access queue."""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import emucxl as ecxl
from repro.core.emucxl import EmuCXL
from repro.core.kvstore import KVStore
from repro.core.policy import Policy1, Policy2
from repro.core.pool import LRUTier
from repro.core.queue import EmuQueue
from repro.core.slab import SlabAllocator


def fresh_lib(local=1 << 22, remote=1 << 24) -> EmuCXL:
    lib = EmuCXL()
    lib.init(local_capacity=local, remote_capacity=remote)
    return lib


# ------------------------------------------------------------------ LRU tier
@settings(max_examples=30)
@given(st.lists(st.integers(0, 20), min_size=1, max_size=100), st.integers(1, 8))
def test_lru_tier_never_exceeds_capacity(keys, cap):
    tier = LRUTier(cap)
    live = set()
    for k in keys:
        if k in tier:
            tier.touch(k)
        else:
            for victim in tier.add(k):
                live.discard(victim)
            live.add(k)
        assert len(tier) <= cap
        assert set(tier.keys()) == live


def test_lru_eviction_order():
    tier = LRUTier(2)
    assert tier.add("a") == []
    assert tier.add("b") == []
    tier.touch("a")          # b becomes LRU
    assert tier.add("c") == ["b"]


# ------------------------------------------------------------------ KV store
def test_kvstore_put_get_delete():
    lib = fresh_lib()
    kv = KVStore(lib=lib, local_capacity_objects=2)
    kv.put("x", b"1")
    kv.put("y", b"2")
    kv.put("z", b"3")        # x demoted (LRU)
    assert kv.tier_of("x") == ecxl.REMOTE_MEMORY
    assert kv.get("y") == b"2" and kv.stats.local_hits == 1
    assert kv.get("x") == b"1" and kv.stats.remote_hits == 1
    assert kv.tier_of("x") == ecxl.LOCAL_MEMORY  # Policy1 promoted
    assert kv.delete("z") and not kv.delete("z")
    assert kv.get("missing") is None and kv.stats.misses == 1
    lib.exit()


def test_kvstore_policy2_never_moves():
    lib = fresh_lib()
    kv = KVStore(lib=lib, local_capacity_objects=1, policy=Policy2())
    kv.put("a", b"a")
    kv.put("b", b"b")        # a demoted
    for _ in range(5):
        assert kv.get("a") == b"a"
    assert kv.tier_of("a") == ecxl.REMOTE_MEMORY
    lib.exit()


def _policy_experiment(policy, hot_frac, n_objects=200, local_cap=60,
                       n_gets=3000, seed=0):
    """Scaled-down paper §IV-B experiment: 90% of GETs to hot_frac of objects."""
    lib = fresh_lib()
    kv = KVStore(lib=lib, local_capacity_objects=local_cap, policy=policy)
    for i in range(n_objects):
        kv.put(f"k{i}", f"v{i}".encode())
    g = np.random.default_rng(seed)
    hot = max(int(hot_frac * n_objects), 1)
    for _ in range(n_gets):
        i = int(g.integers(0, hot)) if g.random() < 0.9 \
            else int(g.integers(0, n_objects))
        kv.get(f"k{i}")
    pct = kv.stats.percent_local
    lib.exit()
    return pct


def test_policy_table_trend():
    """Paper Table IV: Policy1 >> Policy2 for small hot sets; gap collapses as the
    hot set approaches the full object set."""
    gap_small = _policy_experiment(Policy1(), 0.1) - _policy_experiment(Policy2(), 0.1)
    gap_large = _policy_experiment(Policy1(), 0.9) - _policy_experiment(Policy2(), 0.9)
    assert gap_small > 30.0          # paper: 78.08 points at 10%
    assert gap_large < 10.0          # paper: 0.48 points at 90%
    assert gap_small > gap_large


# ------------------------------------------------------------------ slab allocator
def test_slab_basics():
    lib = fresh_lib()
    slab = SlabAllocator(lib, slab_pages=1)
    p = slab.alloc(100, ecxl.LOCAL_MEMORY)
    assert p.size_class == 128
    slab.write(p, np.arange(100, dtype=np.uint8))
    assert np.array_equal(slab.read(p, 100), np.arange(100, dtype=np.uint8))
    with pytest.raises(ecxl.EmuCXLError):
        slab.write(p, np.zeros(200, np.uint8))
    slab.free(p)
    with pytest.raises(ecxl.EmuCXLError):
        slab.free(p)  # double free detected
    assert slab.slab_count() == 0  # empty slab reclaimed
    lib.exit()


def test_slab_migration():
    lib = fresh_lib()
    slab = SlabAllocator(lib, slab_pages=1)
    p = slab.alloc(64, ecxl.LOCAL_MEMORY)
    slab.write(p, np.full(64, 9, np.uint8))
    slab.migrate_slab(p.slab_id, ecxl.REMOTE_MEMORY)
    assert slab.node_of(p) == ecxl.REMOTE_MEMORY
    assert np.all(slab.read(p, 64) == 9)
    lib.exit()


@settings(max_examples=20)
@given(st.lists(st.tuples(st.integers(1, 1024), st.booleans()),
                min_size=1, max_size=60))
def test_slab_alloc_free_invariants(ops):
    """Live chunks never exceed slab capacity; fragmentation in [0, 1]; constant-time
    alloc returns chunks that never alias."""
    lib = fresh_lib()
    slab = SlabAllocator(lib, slab_pages=1)
    live = []
    for size, do_free in ops:
        p = slab.alloc(size, ecxl.LOCAL_MEMORY)
        assert p.size_class >= size
        live.append(p)
        keys = {(q.slab_id, q.chunk) for q in live}
        assert len(keys) == len(live)  # no aliasing
        if do_free and live:
            slab.free(live.pop(0))
        for node in (0, 1):
            assert 0.0 <= slab.fragmentation(node) <= 1.0
    lib.exit()


# ------------------------------------------------------------------ queue (paper §IV-A)
@settings(max_examples=25)
@given(st.lists(st.one_of(st.integers(-1000, 1000), st.none()),
                min_size=1, max_size=50),
       st.integers(0, 1))
def test_queue_fifo_matches_oracle(ops, policy):
    """enqueue(int) / dequeue(None) sequence matches collections.deque exactly."""
    from collections import deque

    lib = fresh_lib()
    q = EmuQueue(policy=policy, lib=lib)
    oracle = deque()
    for op in ops:
        if op is None:
            assert q.dequeue() == (oracle.popleft() if oracle else None)
        else:
            q.enqueue(op)
            oracle.append(op)
        assert len(q) == len(oracle)
    q.destroy()
    assert lib.stats(policy) == 0  # all nodes freed
    lib.exit()


def test_queue_nodes_live_on_selected_tier():
    lib = fresh_lib()
    q = EmuQueue(policy=ecxl.REMOTE_MEMORY, lib=lib)
    for i in range(5):
        q.enqueue(i)
    assert lib.stats(1) == 5 * 16 and lib.stats(0) == 0
    q.destroy()
    lib.exit()
