"""Property suite: tier accounting equals live allocations under any interleaving.

Random sequences of alloc/free/resize/migrate/migrate_batch — including
operations that fail on quota/capacity mid-batch — must keep ``stats(node,
host)`` exactly equal to the sum of live allocation sizes on that (node, host)
and must never drive the ``SharedPool`` byte counters negative.
"""

import contextlib

import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import emucxl as ecxl
from repro.core.emucxl import EmuCXL, EmuCXLError
from repro.core.fabric import Fabric

NUM_HOSTS = 2
LOCAL_CAP = 8 * 1024          # deliberately tight so failures actually happen
REMOTE_CAP = 12 * 1024
QUOTA = 8 * 1024


def _make_lib(with_fabric: bool) -> EmuCXL:
    lib = EmuCXL()
    lib.init(
        local_capacity=LOCAL_CAP, remote_capacity=REMOTE_CAP,
        num_hosts=NUM_HOSTS, host_quota=QUOTA,
        fabric=Fabric(num_hosts=NUM_HOSTS, pool_ports=2) if with_fabric else None,
    )
    return lib


def _check_invariants(lib: EmuCXL, shadow: dict) -> None:
    """shadow: addr -> (size, node, host) for every allocation we believe live."""
    for node in (ecxl.LOCAL_MEMORY, ecxl.REMOTE_MEMORY):
        total = 0
        for host in range(NUM_HOSTS):
            expected = sum(sz for sz, n, h in shadow.values()
                           if n == node and h == host)
            assert lib.stats(node, host) == expected, (
                f"stats({node},{host}) drifted from live allocations"
            )
            total += expected
        assert lib.stats(node) == total
    pool = lib._pool
    assert pool.used >= 0, "SharedPool used-bytes went negative"
    assert all(v >= 0 for v in pool.used_by_host.values())
    assert pool.used == sum(pool.used_by_host.values())
    assert pool.used <= pool.capacity
    for host in range(NUM_HOSTS):
        q = pool.quota(host)
        if q is not None:
            assert pool.used_by_host[host] <= q
    # local accounting never exceeds capacity either
    for host in range(NUM_HOSTS):
        assert 0 <= lib._used_local[host] <= LOCAL_CAP
    # the registry agrees with the shadow entirely
    assert set(lib._allocs) == set(shadow)


# op tuple: (kind 0..4, size-ish, node, host)
_OP = st.tuples(st.integers(0, 4), st.integers(1, 6 * 1024),
                st.integers(0, 1), st.integers(0, NUM_HOSTS - 1))


def _apply_op(lib, shadow, addrs, op):
    kind, size, node, host = op
    if kind == 0 or not addrs:                       # alloc
        addr = lib.alloc(size, node, host)
        shadow[addr] = (size, node, host)
        addrs.append(addr)
        return
    target = addrs[size % len(addrs)]
    if kind == 1:                                    # free
        lib.free(target)
        del shadow[target]
        addrs.remove(target)
    elif kind == 2:                                  # resize
        new_addr = lib.resize(target, size)
        _, n, h = shadow.pop(target)
        shadow[new_addr] = (size, n, h)
        addrs.remove(target)
        addrs.append(new_addr)
    elif kind == 3:                                  # migrate
        new_addr = lib.migrate(target, node, host)
        sz, _, _ = shadow.pop(target)
        shadow[new_addr] = (sz, node, host)
        addrs.remove(target)
        addrs.append(new_addr)
    else:                                            # migrate_batch (1-3 moves)
        picks = addrs[: (size % 3) + 1]
        moves = [(a, node, (host + i) % NUM_HOSTS)
                 for i, a in enumerate(picks)]
        addr_map, _ = lib.migrate_batch(moves)
        for i, a in enumerate(picks):
            sz, _, _ = shadow.pop(a)
            shadow[addr_map[a]] = (sz, node, (host + i) % NUM_HOSTS)
            addrs.remove(a)
            addrs.append(addr_map[a])


@pytest.mark.parametrize("with_fabric", [False, True],
                         ids=["no-fabric", "fabric"])
@settings(max_examples=25)
@given(ops=st.lists(_OP, min_size=1, max_size=40))
def test_any_interleaving_preserves_accounting(with_fabric, ops):
    lib = _make_lib(with_fabric)
    try:
        shadow: dict = {}
        addrs: list = []
        for op in ops:
            # Modeled failures (quota/capacity/invalid size) are expected
            # under tight limits — they must leave accounting untouched,
            # which the per-op check below verifies.
            with contextlib.suppress(EmuCXLError):
                _apply_op(lib, shadow, addrs, op)
            _check_invariants(lib, shadow)
    finally:
        lib.exit()
    assert lib._pool.used == 0                      # exit() drains everything


def test_mid_batch_quota_failure_rolls_back_cleanly():
    """A migrate_batch whose Nth move trips the quota must leave sources
    intact, destinations released, and the fabric idle (deterministic twin of
    the property above, pinned so the failure path is always exercised)."""
    lib = _make_lib(with_fabric=True)
    try:
        a = lib.alloc(4 * 1024, ecxl.LOCAL_MEMORY, host=0)
        b = lib.alloc(4 * 1024, ecxl.LOCAL_MEMORY, host=0)
        c = lib.alloc(4 * 1024, ecxl.LOCAL_MEMORY, host=1)
        # host0 quota is 8K: a and b fit, c (moved to host0's quota) cannot
        with pytest.raises(ecxl.QuotaExceeded):
            lib.migrate_batch([
                (a, ecxl.REMOTE_MEMORY, 0),
                (b, ecxl.REMOTE_MEMORY, 0),
                (c, ecxl.REMOTE_MEMORY, 0),
            ])
        shadow = {a: (4096, 0, 0), b: (4096, 0, 0), c: (4096, 0, 1)}
        _check_invariants(lib, shadow)
        assert lib.fabric.idle()
        # the batch is repeatable once the offending move is fixed
        addr_map, _ = lib.migrate_batch([
            (a, ecxl.REMOTE_MEMORY, 0),
            (b, ecxl.REMOTE_MEMORY, 0),
            (c, ecxl.REMOTE_MEMORY, 1),
        ])
        shadow = {addr_map[a]: (4096, 1, 0), addr_map[b]: (4096, 1, 0),
                  addr_map[c]: (4096, 1, 1)}
        _check_invariants(lib, shadow)
    finally:
        lib.exit()


def test_failed_resize_keeps_original_alive():
    lib = _make_lib(with_fabric=False)
    try:
        addr = lib.alloc(6 * 1024, ecxl.REMOTE_MEMORY, host=0)
        with pytest.raises(EmuCXLError):
            lib.resize(addr, 7 * 1024)       # old+new would exceed the quota
        _check_invariants(lib, {addr: (6 * 1024, 1, 0)})
        assert lib.get_size(addr) == 6 * 1024
    finally:
        lib.exit()


def test_shared_segments_do_not_break_pool_accounting():
    """Attachments alias the backing bytes: N mappings, one charge; detach and
    destroy return the pool to exactly zero."""
    lib = _make_lib(with_fabric=True)
    try:
        seg = lib.share(4 * 1024, host=0)
        attachments = [lib.attach(seg, host=h % NUM_HOSTS) for h in range(4)]
        assert lib.stats(ecxl.REMOTE_MEMORY) == 4 * 1024
        assert lib.stats(ecxl.REMOTE_MEMORY, host=0) == 4 * 1024
        for addr in attachments:
            lib.detach(addr)
        lib.destroy_segment(seg)
        assert lib._pool.used == 0
        assert lib.stats(ecxl.REMOTE_MEMORY) == 0
    finally:
        lib.exit()
