"""Transactional coherence: a failed flush leaves the directory untouched.

The regression guard for the directory undo-journal: any async batch whose
planning fails mid-way (bounds, short payload, pinned-segment migrate, quota)
must leave directory holders, per-segment stats, write-combining buffers —
*including their LRU order*, which decides future forced-drain victims — and
``coherence_stats()`` byte-identical to the pre-batch snapshot, under random
op interleavings (hypothesis), with capacity-bounded
buffers whose forced partial drains are themselves journaled, and in
deterministic twins that pin each failure mode.

The same generator also pins the stream scheduler's semantics: a flushed
batch of random reads/writes/fences/acquires produces exactly the read
values, directory state, protocol counts, and write-combining buffers that
the same ops run synchronously in submission order produce — per-host program
order within a segment survives however the dependency graph overlaps the
schedule.
"""

import copy

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import emucxl as ecxl
from repro.core.api import CXLSession
from repro.core.coherence import DirectoryJournal
from repro.core.emucxl import EmuCXLError
from repro.core.fabric import Fabric
from repro.core.queue import (AcquireOp, FenceOp, MemsetOp, MigrateOp, ReadOp,
                              WriteOp)

NUM_HOSTS = 3
PAGE = 4096
PAGES = 4


def make_session(fabric=True, consistency="eager", wc_capacity=None,
                 race_detect="off"):
    # race_detect="off" explicitly: the random interleavings below are
    # unsynchronized by construction, so the detector is armed only by the
    # tests that opt into "warn" and assert on its rollback.
    f = Fabric(num_hosts=NUM_HOSTS, pool_ports=2) if fabric else None
    sess = CXLSession(1 << 22, 1 << 24, num_hosts=NUM_HOSTS, fabric=f)
    seg = sess.share(PAGES * PAGE, host=0, page_bytes=PAGE,
                     consistency=consistency, wc_capacity=wc_capacity,
                     race_detect=race_detect)
    bufs = [sess.attach(seg, host=h) for h in range(NUM_HOSTS)]
    return sess, seg, bufs


def snapshot(sess, seg):
    return (
        seg.directory.snapshot(),
        seg.stats.as_dict(),
        # list(), not set(): the buffer's LRU *order* picks forced-drain
        # victims, so rollback must restore it byte-identically.
        {h: list(p) for h, p in seg.wc.items()},
        copy.deepcopy(sess.coherence_stats()),
        # vector clocks, release snapshots, write epochs, recorded races
        seg.detector.snapshot() if seg.detector is not None else None,
    )


def warm_up(seg, bufs, pattern):
    """Pre-batch traffic so rollback must restore a non-trivial directory."""
    for i, (host, write) in enumerate(pattern):
        page = i % PAGES
        if write:
            bufs[host].write(np.ones(32, np.uint8), offset=page * PAGE)
        else:
            bufs[host].read(page * PAGE, 32)
    if seg.consistency == "release":
        bufs[0].fence()


def submit_coherent_ops(sess, bufs, ops):
    for kind, host, page in ops:
        buf = bufs[host]
        if kind == 0:
            sess.submit(ReadOp(buf, page * PAGE, 32))
        elif kind == 1:
            sess.submit(WriteOp(buf, np.ones(32, np.uint8), offset=page * PAGE))
        elif kind == 2:
            sess.submit(MemsetOp(buf, value=7, size=32))
        elif kind == 3:
            sess.submit(FenceOp(buf))
        else:
            sess.submit(AcquireOp(buf))


_FAILERS = [
    ("short-payload", lambda sess, bufs:
        sess.submit(WriteOp(bufs[0], np.ones(4, np.uint8), size=64))),
    ("out-of-bounds", lambda sess, bufs:
        sess.submit(ReadOp(bufs[1], PAGES * PAGE, 64))),
    ("pinned-migrate", lambda sess, bufs:
        sess.submit(MigrateOp(bufs[2], ecxl.LOCAL_MEMORY))),
]

_OP = st.tuples(st.integers(0, 4), st.integers(0, NUM_HOSTS - 1),
                st.integers(0, PAGES - 1))
_WARM = st.tuples(st.integers(0, NUM_HOSTS - 1), st.booleans())


@pytest.mark.parametrize("consistency,wc_capacity,race_detect",
                         [("eager", None, "off"), ("release", None, "off"),
                          ("release", 2, "off"), ("release", None, "warn"),
                          ("release", 2, "warn")],
                         ids=["eager", "release-unbounded", "release-cap2",
                              "release-warn", "release-cap2-warn"])
@pytest.mark.parametrize("with_fabric", [True, False],
                         ids=["fabric", "no-fabric"])
@settings(max_examples=15)
@given(warm=st.lists(_WARM, min_size=0, max_size=8),
       before=st.lists(_OP, min_size=0, max_size=8),
       after=st.lists(_OP, min_size=0, max_size=8),
       failer=st.integers(0, len(_FAILERS) - 1))
def test_failed_flush_restores_coherence_state(consistency, wc_capacity,
                                               race_detect, with_fabric, warm,
                                               before, after, failer):
    # wc_capacity=2 with 4 pages makes the random batches overflow the
    # write-combining buffer, so forced partial drains (and their LRU
    # evictions) are exercised under rollback, not just plain buffering.
    # The "warn" rows arm the race detector: the random unsynchronized
    # interleavings record races mid-batch, and the snapshot (which includes
    # vector clocks, write epochs, and the race log) must still restore
    # byte-identically after the injected failure.
    sess, seg, bufs = make_session(with_fabric, consistency, wc_capacity,
                                   race_detect)
    try:
        warm_up(seg, bufs, warm)
        pre = snapshot(sess, seg)
        modeled_pre = dict(sess.modeled_time)
        submit_coherent_ops(sess, bufs, before)
        _FAILERS[failer][1](sess, bufs)      # the op that fails at plan time
        submit_coherent_ops(sess, bufs, after)
        with pytest.raises(EmuCXLError):
            sess.flush()
        assert snapshot(sess, seg) == pre, (
            f"failed batch ({_FAILERS[failer][0]}) leaked coherence state"
        )
        # a failed batch also charges no modeled time
        assert dict(sess.modeled_time) == modeled_pre
        if with_fabric:
            assert sess.fabric.idle()
        # the directory still works: a clean batch afterwards succeeds
        submit_coherent_ops(sess, bufs, before + after)
        sess.flush()
    finally:
        sess.close()


def test_failed_flush_rolls_back_directory_deterministic():
    """Pinned twin of the property: known transitions, known rollback."""
    sess, seg, bufs = make_session()
    try:
        bufs[0].write(np.ones(32, np.uint8))             # host0: M on page 0
        bufs[1].read(PAGE, 32)                           # host1: E on page 1
        pre = snapshot(sess, seg)
        sess.submit(
            WriteOp(bufs[2], np.ones(32, np.uint8)),     # would steal page 0
            ReadOp(bufs[0], PAGE, 32),                   # would downgrade E
            WriteOp(bufs[1], np.ones(4, np.uint8), size=64),   # fails planning
        )
        with pytest.raises(EmuCXLError, match="supplies 4 bytes"):
            sess.flush()
        assert snapshot(sess, seg) == pre
        # the planned-but-rolled-back transitions really would have happened
        bufs[2].write(np.ones(32, np.uint8))
        assert seg.directory.holders(0) == {2: "M"}
    finally:
        sess.close()


def test_failed_flush_restores_write_combining_buffer():
    sess, seg, bufs = make_session(consistency="release")
    try:
        bufs[0].write(np.ones(32, np.uint8))             # pending page 0
        pre = snapshot(sess, seg)
        assert seg.pending_pages(0) == 1
        sess.submit(
            WriteOp(bufs[0], np.ones(32, np.uint8), offset=PAGE),  # page 1
            FenceOp(bufs[0]),                            # would drain both
            ReadOp(bufs[1], PAGES * PAGE, 64),           # fails planning
        )
        with pytest.raises(EmuCXLError, match="out-of-bounds"):
            sess.flush()
        assert snapshot(sess, seg) == pre
        assert seg.pending_pages(0) == 1                 # page 1 un-buffered,
        assert list(seg.wc[0]) == [0]                    # page 0 re-buffered
    finally:
        sess.close()


def test_failed_flush_restores_forced_drain_state():
    """A rolled-back forced drain restores the victim page to its original
    LRU slot and zeroes the forced-drain counters."""
    sess, seg, bufs = make_session(consistency="release", wc_capacity=2)
    try:
        bufs[0].write(np.ones(32, np.uint8), offset=0)       # pending: [0,
        bufs[0].write(np.ones(32, np.uint8), offset=PAGE)    #           1]
        pre = snapshot(sess, seg)
        assert list(seg.wc[0]) == [0, 1]
        sess.submit(
            # Buffer full: planning this write force-drains LRU page 0 ...
            WriteOp(bufs[0], np.ones(32, np.uint8), offset=2 * PAGE),
            # ... and this op fails, unwinding the whole batch.
            ReadOp(bufs[1], PAGES * PAGE, 64),
        )
        with pytest.raises(EmuCXLError, match="out-of-bounds"):
            sess.flush()
        assert snapshot(sess, seg) == pre
        assert list(seg.wc[0]) == [0, 1]                 # order restored too
        assert seg.stats.forced_drains == 0
        assert seg.directory.holders(0) == {}            # upgrade undone
        # replaying the same write for real evicts page 0 as planned
        bufs[0].write(np.ones(32, np.uint8), offset=2 * PAGE)
        assert list(seg.wc[0]) == [1, 2]
        assert seg.stats.forced_drains == 1
        assert seg.directory.holders(0) == {0: "M"}
    finally:
        sess.close()


def test_rewrite_touch_rollback_restores_lru_order():
    """Re-writing a pending page moves it to MRU; rollback puts it back."""
    sess, seg, bufs = make_session(consistency="release", wc_capacity=3)
    try:
        for p in range(3):
            bufs[0].write(np.ones(8, np.uint8), offset=p * PAGE)
        assert list(seg.wc[0]) == [0, 1, 2]
        sess.submit(
            WriteOp(bufs[0], np.ones(8, np.uint8), offset=0),   # touch: 0->MRU
            ReadOp(bufs[1], PAGES * PAGE, 64),                  # fails
        )
        with pytest.raises(EmuCXLError, match="out-of-bounds"):
            sess.flush()
        assert list(seg.wc[0]) == [0, 1, 2]
        bufs[0].write(np.ones(8, np.uint8), offset=0)
        assert list(seg.wc[0]) == [1, 2, 0]              # the touch, for real
    finally:
        sess.close()


def test_failed_flush_between_release_and_pending_acquire():
    """A batch that fails after a release fence but before the acquire that
    would synchronize with it unwinds everything: the fence's drain (directory
    upgrades, WC buffer, fences counter) and the acquire's `acquires` stat —
    and the would-be reader still observes only pre-batch bytes."""
    sess, seg, bufs = make_session(consistency="release")
    try:
        bufs[0].write(np.full(32, 9, np.uint8))          # pending page 0
        pre = snapshot(sess, seg)
        assert seg.pending_pages(0) == 1
        sess.submit(
            FenceOp(bufs[0]),                            # release: drains page 0
            ReadOp(bufs[1], PAGES * PAGE, 64),           # fails planning
            AcquireOp(bufs[1]),                          # pending acquire
            ReadOp(bufs[1], 0, 32),
        )
        with pytest.raises(EmuCXLError, match="out-of-bounds"):
            sess.flush()
        assert snapshot(sess, seg) == pre
        assert seg.pending_pages(0) == 1                 # release un-published
        assert seg.stats.fences == 0
        assert seg.stats.acquires == 0
        if sess.fabric is not None:
            assert sess.fabric.idle()
        # replayed cleanly, the same chain publishes and synchronizes
        t = sess.submit(
            FenceOp(bufs[0]), AcquireOp(bufs[1]), ReadOp(bufs[1], 0, 32))
        sess.flush()
        assert seg.stats.fences == 1
        assert seg.stats.acquires == 1
        np.testing.assert_array_equal(t[2].result(),
                                      np.full(32, 9, np.uint8))
    finally:
        sess.close()


def test_failed_flush_after_acquire_unwinds_acquire_stat():
    """Failure *after* a synchronized acquire in the batch: the acquire's
    journaled stat bump rolls back with everything else."""
    sess, seg, bufs = make_session(consistency="release")
    try:
        bufs[0].write(np.ones(32, np.uint8))
        pre = snapshot(sess, seg)
        sess.submit(
            FenceOp(bufs[0]),
            AcquireOp(bufs[1]),                          # syncs with the fence
            ReadOp(bufs[1], PAGES * PAGE, 64),           # fails planning
        )
        with pytest.raises(EmuCXLError, match="out-of-bounds"):
            sess.flush()
        assert snapshot(sess, seg) == pre
        assert seg.stats.acquires == 0
    finally:
        sess.close()


def test_failed_flush_restores_race_detector_state():
    """Pinned twin for the detector: a failed batch unwinds vector clocks,
    release snapshots, write epochs, the race log, and ``stats.races``."""
    sess, seg, bufs = make_session(consistency="release", race_detect="warn")
    try:
        # Build non-trivial happens-before state: host 0 publishes page 0,
        # host 1 joins the release — a proper edge, no race recorded.
        bufs[0].write(np.ones(32, np.uint8))
        bufs[0].fence()
        bufs[1].acquire()
        bufs[1].read(0, 32)
        assert seg.stats.races == 0
        pre = snapshot(sess, seg)
        det_pre = seg.detector.snapshot()
        sess.submit(
            # host 2 never acquired: write-write race on page 0, recorded
            # (warn mode) and journaled mid-batch ...
            WriteOp(bufs[2], np.ones(32, np.uint8)),
            FenceOp(bufs[2]),                        # ... clock bump journaled
            ReadOp(bufs[1], PAGES * PAGE, 64),       # fails planning
        )
        with pytest.raises(EmuCXLError, match="out-of-bounds"):
            sess.flush()
        assert snapshot(sess, seg) == pre
        assert seg.detector.snapshot() == det_pre
        assert seg.stats.races == 0
        assert sess.coherence_stats()["races"] == []
        # replayed for real, the same unsynchronized write records the race
        bufs[2].write(np.ones(32, np.uint8))
        assert seg.stats.races == 1
        assert len(seg.detector.races) == 1
        assert sess.coherence_stats()["races"][0]["page"] == 0
    finally:
        sess.close()


# ---------------------------------------------------------------- program order
def _run_ops(sess, seg, bufs, ops, *, async_batch):
    """Execute the op stream either as one flushed batch or synchronously in
    submission order; returns the list of read results."""
    if async_batch:
        tickets = []
        for kind, host, page in ops:
            buf = bufs[host]
            if kind == 0:
                tickets.append(sess.submit(ReadOp(buf, page * PAGE, 32)))
            elif kind == 1:
                payload = np.full(32, (host * PAGES + page + 1) % 251, np.uint8)
                sess.submit(WriteOp(buf, payload, offset=page * PAGE))
            elif kind == 2:
                sess.submit(MemsetOp(buf, value=host + 1, size=32))
            elif kind == 3:
                sess.submit(FenceOp(buf))
            else:
                sess.submit(AcquireOp(buf))
        sess.flush()
        return [t.result() for t in tickets]
    out = []
    for kind, host, page in ops:
        buf = bufs[host]
        if kind == 0:
            out.append(buf.read(page * PAGE, 32))
        elif kind == 1:
            payload = np.full(32, (host * PAGES + page + 1) % 251, np.uint8)
            buf.write(payload, offset=page * PAGE)
        elif kind == 2:
            buf.memset(host + 1, 32)
        elif kind == 3:
            buf.fence()
        else:
            buf.acquire()
    return out


@pytest.mark.parametrize("consistency,wc_capacity",
                         [("eager", None), ("release", 2)],
                         ids=["eager", "release-cap2"])
@settings(max_examples=15)
@given(ops=st.lists(_OP, min_size=1, max_size=12))
def test_flush_preserves_program_order(consistency, wc_capacity, ops):
    """The stream scheduler only re-times ops; it must not reorder their
    effects. One flushed batch of random reads/writes/memsets/fences/acquires
    lands on exactly the bytes, read values, directory state, and protocol
    counts that the same stream run synchronously produces — including forced
    partial drains, whose victims depend on LRU order."""
    sess_a, seg_a, bufs_a = make_session(True, consistency, wc_capacity)
    sess_b, seg_b, bufs_b = make_session(True, consistency, wc_capacity)
    try:
        got = _run_ops(sess_a, seg_a, bufs_a, ops, async_batch=True)
        want = _run_ops(sess_b, seg_b, bufs_b, ops, async_batch=False)
        assert len(got) == len(want)
        for g, w in zip(got, want, strict=True):
            np.testing.assert_array_equal(g, w)
        assert seg_a.directory.snapshot() == seg_b.directory.snapshot()
        stats_a, stats_b = seg_a.stats.as_dict(), seg_b.stats.as_dict()
        # fence_coalesced and acquires count batch-level scheduling events
        # (fence folding, acquire-release synchronization) the serial
        # reference definitionally cannot accrue.
        for scheduler_stat in ("fence_coalesced", "acquires"):
            stats_a.pop(scheduler_stat), stats_b.pop(scheduler_stat)
        assert stats_a == stats_b
        assert {h: list(p) for h, p in seg_a.wc.items()} == \
               {h: list(p) for h, p in seg_b.wc.items()}
        assert np.array_equal(bufs_a[0].read(0, PAGES * PAGE),
                              bufs_b[0].read(0, PAGES * PAGE))
    finally:
        sess_a.close()
        sess_b.close()


def test_journal_partial_rollback_marks():
    """rollback(mark) unwinds only the entries recorded after the mark."""
    sess, seg, bufs = make_session()
    try:
        journal = DirectoryJournal()
        seg.plan_write(sess.fabric, 0, 0, 32, journal)       # host0 M page 0
        mark = journal.mark()
        seg.plan_read(sess.fabric, 1, 0, 32, journal)        # forward, S+S
        seg.plan_write(sess.fabric, 2, 0, 32, journal)       # host2 steals M
        journal.rollback(mark)
        assert seg.directory.holders(0) == {0: "M"}          # first op kept
        assert seg.stats.write_misses == 1
        assert seg.stats.forwards == 0
        journal.rollback()
        assert seg.directory.holders(0) == {}
        assert seg.stats.as_dict() == {k: 0 for k in seg.stats.as_dict()}
    finally:
        sess.close()
