"""Tier-1 wiring for the plan-time symbolic batch verifier (core/verify.py).

The load-bearing gates:

* **Soundness vs the dynamic detector** — for every litmus program in the
  model-checker corpus and every permitted schedule, each page the dynamic
  happens-before detector flags on the real replay must be inside the
  verifier's PF005 may-race set for the same schedule-order batch (the
  static analysis over-approximates, never misses).
* **No false musts** — race-free corpus programs draw zero must-severity
  diagnostics on every permitted schedule.
* **Preflight is pure** — running the verifier (standalone or through
  ``flush(preflight=...)``) leaves directory / WC / detector / stats state
  byte-identical, and a warned flush commits exactly what an unchecked
  flush commits.
* Property sweep over random batches (hypothesis): replay and verify agree
  on soundness for arbitrary op soups, and the verifier is deterministic.

Plus the plumbing: ``flush(preflight="raise")`` raises ``PreflightError``
and fails the batch's tickets, ``coherence_stats()["preflight"]``
accumulates, and ``EMUCXL_CHECK=preflight`` switches the default on.
"""

import importlib.util
import sys
from pathlib import Path

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import mc, verify
from repro.core.api import CXLSession
from repro.core.coherence import DirectoryJournal, SharedSegment
from repro.core.queue import AcquireOp, FenceOp, ReadOp, WriteOp
from repro.core.verify import (
    MUST, OpDesc, PoolView, PreflightError, descs_from_events,
    fresh_segment_view, resolve_preflight_mode, verify_batch,
)

REPO_ROOT = Path(__file__).resolve().parent.parent
_spec = importlib.util.spec_from_file_location(
    "emucxl_verify", REPO_ROOT / "tools" / "emucxl_verify.py")
emucxl_verify = importlib.util.module_from_spec(_spec)
sys.modules["emucxl_verify"] = emucxl_verify
_spec.loader.exec_module(emucxl_verify)


def _session(**kw):
    kw.setdefault("local_capacity", 1 << 20)
    kw.setdefault("remote_capacity", 1 << 20)
    kw.setdefault("num_hosts", 2)
    return CXLSession(**kw)


def _segment_snapshot(seg):
    """Every piece of planner-visible state, deep enough to diff."""
    return (
        seg.directory.snapshot(),
        seg.stats.as_dict(),
        {h: list(ps) for h, ps in seg.wc.items()},
        seg.detector.snapshot() if seg.detector is not None else None,
    )


# ------------------------------------------------------------------ soundness
@pytest.mark.parametrize("program", mc.CORPUS, ids=[p.name for p in mc.CORPUS])
def test_dynamic_races_are_inside_the_pf005_may_set(program):
    """The soundness theorem, checked exhaustively: on every permitted
    schedule, dynamic race pages ⊆ static PF005 may-race pages."""
    for schedule in mc.all_schedules(program):
        events, dynamic = emucxl_verify.replay_schedule(mc, program, schedule)
        result = emucxl_verify.verify_schedule(mc, verify, program, events)
        assert dynamic <= result.race_pages(0), (
            f"{program.name} @ {schedule}: dynamic detector flagged "
            f"{sorted(dynamic - result.race_pages(0))} outside the may-set")


@pytest.mark.parametrize(
    "program", [p for p in mc.CORPUS if not p.expect_race],
    ids=[p.name for p in mc.CORPUS if not p.expect_race])
def test_race_free_programs_draw_zero_must_diagnostics(program):
    for schedule in mc.all_schedules(program):
        events, _ = emucxl_verify.replay_schedule(mc, program, schedule)
        result = emucxl_verify.verify_schedule(mc, verify, program, events)
        assert result.ok, (
            f"{program.name} @ {schedule}: "
            f"{[str(d) for d in result.by_severity(MUST)]}")


def test_missing_fence_draws_pf001_and_capacity_draws_pf004():
    """The pinned spot-checks: the classic defects map to their codes."""
    def codes(name):
        program = mc.find_program(name)
        out = set()
        for schedule in mc.all_schedules(program):
            events, _ = emucxl_verify.replay_schedule(mc, program, schedule)
            out |= emucxl_verify.verify_schedule(
                mc, verify, program, events).codes()
        return out

    assert "PF001" in codes("mp_missing_fence")
    assert "PF004" in codes("wc_capacity_eviction")
    assert codes("mp_handoff") == set()


# ---------------------------------------------------------------- random soup
_EV = st.tuples(
    st.sampled_from(["read", "write", "fence", "acquire", "detach"]),
    st.integers(0, 2),                       # host
    st.integers(0, 2),                       # page (ignored for sync ops)
)


@settings(max_examples=40)
@given(batch=st.lists(_EV, min_size=1, max_size=14),
       wc_capacity=st.one_of(st.none(), st.integers(1, 2)))
def test_soundness_holds_on_random_batches(batch, wc_capacity):
    """Property: for arbitrary op soups on one segment, (a) every dynamic
    race page is in the PF005 may-set for the same submission order, and
    (b) the verifier is deterministic."""
    seg = SharedSegment(3 * 4096, 4096, backing_addr=0, home_host=0, port=0,
                        sid=0, consistency="release",
                        wc_capacity=wc_capacity, race_detect="warn")
    journal = DirectoryJournal()
    events = []
    for kind, host, page in batch:
        data_page = page if kind in ("read", "write") else None
        events.append((kind, 0, host, data_page))
        offset = page * 4096
        if kind == "read":
            seg.plan_read(None, host, offset, 4096, journal)
        elif kind == "write":
            seg.plan_write(None, host, offset, 4096, journal)
        elif kind == "fence":
            seg.plan_fence(None, host, journal)
        elif kind == "acquire":
            seg.plan_acquire(host, journal)
        elif kind == "detach":
            seg.plan_detach(None, host, journal)
    dynamic = {r.page for r in seg.detector.races}

    views = {0: fresh_segment_view(0, num_pages=3, wc_capacity=wc_capacity)}
    result = verify_batch(descs_from_events(events), views)
    assert dynamic <= result.race_pages(0), (
        f"batch {batch}: dynamic {sorted(dynamic)} not within "
        f"PF005 {sorted(result.race_pages(0))}")

    again = verify_batch(descs_from_events(events), {
        0: fresh_segment_view(0, num_pages=3, wc_capacity=wc_capacity)})
    assert [d.as_dict() for d in again.diagnostics] \
        == [d.as_dict() for d in result.diagnostics]


# ------------------------------------------------------------------- purity
def test_verify_batch_never_mutates_the_segment_views():
    """The standalone entry point: live-state snapshots taken through
    ``preflight_view()`` are fresh containers; verifying cannot write back."""
    seg = SharedSegment(2 * 4096, 4096, backing_addr=0, home_host=0, port=0,
                        sid=0, consistency="release", race_detect="warn")
    journal = DirectoryJournal()
    seg.plan_write(None, 0, 0, 4096, journal)       # host 0 buffers page 0
    before = _segment_snapshot(seg)

    view = verify.SegmentView(**seg.preflight_view())
    result = verify_batch(
        descs_from_events([("acquire", 0, 1, None), ("read", 0, 1, 0)]),
        {0: view})
    assert result.codes()                            # it found something
    assert _segment_snapshot(seg) == before


def test_preflight_check_leaves_flush_state_byte_identical():
    """`OpQueue._preflight_check` against a live session mutates nothing:
    directory, WC order, detector, and stats snapshots all match."""
    s = _session()
    seg = s.share(4 * 4096, consistency="release", wc_capacity=2,
                  race_detect="warn")
    w = s.attach(seg, host=0)
    r = s.attach(seg, host=1)
    s.submit(WriteOp(w, np.ones(4096, np.uint8)))
    s.flush()                                        # non-trivial prior state
    s.submit(WriteOp(w, np.full(4096, 7, np.uint8)))
    s.submit(ReadOp(r, 0, 4096))
    tickets = list(s.queue._pending)
    before = _segment_snapshot(seg)
    stats_before = s.coherence_stats()

    result = s.queue._preflight_check(s.lib, tickets)
    assert result.ops == 2
    assert _segment_snapshot(seg) == before
    after = s.coherence_stats()
    stats_before.pop("preflight")
    after.pop("preflight")
    assert after == stats_before
    s.flush(preflight="off")
    s.close()


def test_warned_flush_commits_exactly_what_an_unchecked_flush_commits():
    """Run the same batch through two twin sessions, preflight on vs off:
    the committed coherence state must be identical."""
    def run(mode):
        s = _session()
        seg = s.share(4 * 4096, consistency="release", wc_capacity=2,
                      race_detect="warn")
        w = s.attach(seg, host=0)
        r = s.attach(seg, host=1)
        s.submit(WriteOp(w, np.arange(4096, dtype=np.uint8) % 251))
        s.submit(FenceOp(w))
        s.submit(AcquireOp(r))
        out = s.submit(ReadOp(r, 0, 4096))
        s.flush(preflight=mode)
        data = np.asarray(out.result())
        snap = _segment_snapshot(seg)
        s.close()
        return data, snap

    data_on, snap_on = run("warn")
    data_off, snap_off = run("off")
    np.testing.assert_array_equal(data_on, data_off)
    assert snap_on == snap_off


# ------------------------------------------------------------------ plumbing
def test_raise_mode_fails_the_batch_and_its_tickets():
    s = _session()
    seg = s.share(2 * 4096, consistency="release", race_detect="off")
    r = s.attach(seg, host=1)
    t = s.submit(AcquireOp(r))                       # unmatched: PF001 must
    with pytest.raises(PreflightError) as exc:
        s.flush(preflight="raise")
    assert "PF001" in str(exc.value)
    assert exc.value.result.must_count >= 1
    with pytest.raises(PreflightError):
        t.result()                                   # the ticket failed too
    s.close()


def test_cross_batch_handoff_is_clean_in_raise_mode():
    # The acquire legally pairs with a release drained by an EARLIER
    # flush; the peer's held pages in the segment snapshot are the
    # evidence, so PF001's "guaranteed no-op" claim is no longer provable.
    s = _session()
    seg = s.share(2 * 4096, consistency="release", race_detect="off")
    w, r = s.attach(seg, host=0), s.attach(seg, host=1)
    s.submit(WriteOp(w, np.full(64, 7, np.uint8)), FenceOp(w))
    s.flush(preflight="raise")
    s.submit(AcquireOp(r))
    t = s.submit(ReadOp(r, 0, 64))
    s.flush(preflight="raise")                       # must not raise
    assert bytes(np.asarray(t.result())) == b"\x07" * 64
    assert s.coherence_stats()["preflight"]["last"]["must"] == 0
    s.close()


def test_armed_detector_still_proves_a_redundant_reacquire():
    # With clocks available, a re-acquire that would join nothing new is
    # provably a no-op even though the peer HAS released before.
    s = _session()
    seg = s.share(2 * 4096, consistency="release", race_detect="warn")
    w, r = s.attach(seg, host=0), s.attach(seg, host=1)
    s.submit(WriteOp(w, np.ones(64, np.uint8)), FenceOp(w),
             AcquireOp(r), ReadOp(r, 0, 64))
    s.flush(preflight="raise")                       # full handoff: clean
    s.submit(AcquireOp(r))                           # joins nothing new
    with pytest.raises(PreflightError) as exc:
        s.flush(preflight="raise")
    assert "PF001" in str(exc.value)
    s.close()


def test_armed_detector_lets_a_first_acquire_pair_across_batches():
    s = _session()
    seg = s.share(2 * 4096, consistency="release", race_detect="warn")
    w, r = s.attach(seg, host=0), s.attach(seg, host=1)
    s.submit(WriteOp(w, np.ones(64, np.uint8)), FenceOp(w))
    s.flush(preflight="raise")
    s.submit(AcquireOp(r), ReadOp(r, 0, 64))
    s.flush(preflight="raise")                       # must not raise
    assert s.coherence_stats()["preflight"]["last"]["must"] == 0
    s.close()


def test_warn_mode_surfaces_without_failing():
    s = _session(preflight="warn")
    seg = s.share(2 * 4096, consistency="release", race_detect="off")
    w = s.attach(seg, host=0)
    s.submit(WriteOp(w, np.ones(4096, np.uint8)))    # unfenced: PF002 must
    s.flush()                                        # session default: warn
    pf = s.coherence_stats()["preflight"]
    assert pf["totals"]["batches"] == 1
    assert pf["totals"]["PF002"] == 1
    assert pf["last"]["must"] >= 1
    s.submit(FenceOp(w))
    s.flush()
    pf = s.coherence_stats()["preflight"]
    assert pf["totals"]["batches"] == 2              # totals accumulate
    assert pf["last"]["must"] == 0                   # last batch was clean
    s.close()


def test_env_var_switches_the_default_on(monkeypatch):
    monkeypatch.delenv("EMUCXL_CHECK", raising=False)
    assert resolve_preflight_mode() == "off"
    monkeypatch.setenv("EMUCXL_CHECK", "race, preflight")
    assert resolve_preflight_mode() == "raise"
    assert resolve_preflight_mode("warn") == "warn"  # explicit wins
    with pytest.raises(ValueError):
        resolve_preflight_mode("loud")


def test_session_validates_the_mode_eagerly():
    with pytest.raises(ValueError):
        _session(preflight="everything")


def test_pool_overflow_draws_pf003():
    batch = [OpDesc(kind="migrate", sid=0, host=0, pages=(0, 1),
                    node=verify.REMOTE_MEMORY, size=2 * 4096)]
    views = {0: fresh_segment_view(0, num_pages=2)}
    tight = verify_batch(batch, views,
                         PoolView(pool_free=4096, quota_free={},
                                  local_free={}))
    assert [d.code for d in tight.by_severity(MUST)] == ["PF003"]
    roomy = verify_batch(batch, views,
                         PoolView(pool_free=4 * 4096, quota_free={},
                                  local_free={}))
    assert "PF003" not in roomy.codes()


def test_verifier_stays_stdlib_only():
    """core/verify.py (and mc/trace) must import on a bare interpreter."""
    import subprocess
    src = REPO_ROOT / "src"
    proc = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.path.insert(0, sys.argv[1]); "
         "import repro.core.verify, repro.core.mc, repro.core.trace; "
         "bad = [m for m in sys.modules "
         "       if m.split('.')[0] in ('numpy', 'jax', 'jaxlib')]; "
         "sys.exit(1 if bad else 0)", str(src)],
        capture_output=True)
    assert proc.returncode == 0, proc.stderr.decode()
