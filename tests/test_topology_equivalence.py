"""Property suite: the default single-switch topology is bit-identical to the
legacy fabric construction.

The pluggable-topology refactor (core/topology.py) must be invisible when you
don't ask for a shape: ``Fabric(num_hosts=N, pool_ports=P)`` (the pre-refactor
constructor) and ``CXLSession(topology=single_switch(N, P))`` must evolve the
same virtual clock, the same per-link stats, the same coherence counters, and
the same modeled times for *any* operation sequence. Two sessions replay
identical random programs — alloc / write / read / migrate batches / fence /
acquire — and every observable is compared exactly (``==``, not approx: both
run the identical arithmetic, so the floats must match to the last bit).
"""

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import emucxl as ecxl
from repro.core.api import CXLSession, MigrateOp, WriteOp
from repro.core.fabric import Fabric
from repro.core.topology import single_switch

NUM_HOSTS = 2
POOL_PORTS = 2
PAGES = 4
PAGE = 4096


def _legacy_session() -> CXLSession:
    return CXLSession(1 << 22, 1 << 24, num_hosts=NUM_HOSTS,
                      fabric=Fabric(num_hosts=NUM_HOSTS,
                                    pool_ports=POOL_PORTS))


def _topology_session() -> CXLSession:
    return CXLSession(1 << 22, 1 << 24,
                      topology=single_switch(NUM_HOSTS, POOL_PORTS))


class _Program:
    """One session's replay state: a release segment with a writer and a
    reader attachment, plus a list of private buffers the ops churn."""

    def __init__(self, sess: CXLSession):
        self.sess = sess
        self.seg = sess.share(PAGES * PAGE, host=0, page_bytes=PAGE,
                              writers=[0], consistency="release")
        self.w = sess.attach(self.seg, host=0)
        self.r = sess.attach(self.seg, host=1)
        self.bufs = []

    def apply(self, op):
        kind, a, b = op
        sess = self.sess
        if kind == 0:                                     # alloc
            self.bufs.append(sess.alloc(1 + a * 512,
                                        node=ecxl.REMOTE_MEMORY,
                                        host=b % NUM_HOSTS))
        elif kind == 1:                                   # coherent write
            self.w.write(np.full(PAGE, a % 251, np.uint8),
                         offset=(a % PAGES) * PAGE)
        elif kind == 2:                                   # coherent read
            self.r.read((a % PAGES) * PAGE, PAGE)
        elif kind == 3:                                   # async migrate batch
            if not self.bufs:
                return
            ops = [MigrateOp(buf, node=(a + i) % 2, host=b % NUM_HOSTS)
                   for i, buf in enumerate(self.bufs[-2:])]
            for o in ops:
                sess.submit(o)
            sess.flush()
        elif kind == 4:                                   # release fence
            self.w.fence()
        elif kind == 5:                                   # acquire
            self.r.acquire()
        elif kind == 6 and self.bufs:                     # overlapped writes
            payload = np.zeros(2048, np.uint8)
            for buf in self.bufs[-2:]:
                sess.submit(WriteOp(buf, payload))
            sess.flush()

    def observe(self):
        fab = self.sess.fabric
        return {
            "clock": fab.clock,
            "fabric": fab.stats(),
            "modeled": dict(self.sess.modeled_time),
            "coherence": self.sess.lib.coherence_stats()["total"],
            "segment": {k: v for k, v in self.seg.describe().items()
                        if k != "sid"},
        }


_OP = st.tuples(st.integers(0, 6), st.integers(0, 7), st.integers(0, 3))


@settings(max_examples=15)
@given(st.lists(_OP, min_size=1, max_size=12))
def test_any_op_sequence_is_bit_identical_across_constructions(ops):
    with _legacy_session() as legacy, _topology_session() as topo:
        pl, pt = _Program(legacy), _Program(topo)
        for op in ops:
            el = et = None
            try:
                pl.apply(op)
            except Exception as exc:          # must fail identically too
                el = type(exc)
            try:
                pt.apply(op)
            except Exception as exc:
                et = type(exc)
            assert el is et, f"op {op}: legacy raised {el}, topology {et}"
        ol, ot = pl.observe(), pt.observe()
        assert ol["clock"] == ot["clock"]
        assert ol["fabric"] == ot["fabric"]
        assert ol["modeled"] == ot["modeled"]
        assert ol["coherence"] == ot["coherence"]
        assert ol["segment"] == ot["segment"]


def test_default_fabric_construction_is_the_single_switch_topology():
    fab = Fabric(num_hosts=3, pool_ports=2)
    assert fab.topology.name == "single-switch"
    assert list(fab.links) == [fab.host_link(i) for i in range(3)] \
        + [fab.pool_link(j) for j in range(2)]
    assert fab.pool_path(2, 1) == (fab.host_link(2), fab.pool_link(1))
    assert fab.host_path(0, 1) == (fab.host_link(0), fab.host_link(1))
    assert fab.host_path(1, 1) == (fab.host_link(1),)


def test_lone_transfer_cost_matches_the_legacy_closed_form():
    """The pre-refactor contract: latency + bytes/bandwidth, with one switch
    traversal on a two-link path. Anchors the arithmetic to hand-computed
    constants, independent of the equivalence pairing above."""
    bw, lat, swl = 1e9, 1e-6, 25e-9
    fab = Fabric(num_hosts=1, pool_ports=1, host_bandwidth=bw,
                 pool_port_bandwidth=bw, link_latency=lat, switch_latency=swl)
    elapsed = fab.transfer(fab.pool_path(0, 0), 1 << 20)
    assert elapsed == pytest.approx(2 * lat + swl + (1 << 20) / bw)


def test_legacy_error_strings_survive_the_refactor():
    with pytest.raises(Exception, match="need >= 1 host and >= 1 pool port"):
        Fabric(num_hosts=0, pool_ports=1)
    fab = Fabric(num_hosts=1, pool_ports=1)
    with pytest.raises(Exception, match="invalid host"):
        fab.pool_path(5, 0)
    with pytest.raises(Exception, match="invalid pool port"):
        fab.pool_path(0, 5)
