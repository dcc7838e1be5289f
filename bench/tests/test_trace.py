"""The reduction from a profiler trace to numbers: the interval
arithmetic on hand-made operations, and the whole path on two small
recorded ``.xplane.pb`` files of the four-partition fixture cell, two
epochs each: one from four virtual CPU devices (device operations on
the host plane, as the CPU backend writes them) and one from a four-chip
TPU v5e host (device planes, ``XLA Ops`` lines, HLO text for names)."""

import os

import pytest

from conftest import FIXTURES

from harness import trace
from harness.trace import Op, Trace

RECORDED = os.path.join(FIXTURES, "traces", "cpu_4dev_tiny_gcn.xplane.pb")
# the same cell on a four-chip TPU v5e host (PR 22's chip run); the
# unread /host:metadata plane (0.6 MB of HLO text) was taken out
RECORDED_TPU = os.path.join(FIXTURES, "traces",
                            "tpu_4chip_tiny_gcn.xplane.pb")


def ops(*rows):
    out = [Op(name, lo, hi) for name, lo, hi in rows]
    trace.mark_nesting(out)
    return out


def test_union_and_subtract():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 7)]) == [(0, 3), (5, 7)]
    assert trace.length([(0, 3), (5, 7)]) == 5
    assert trace.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert trace.subtract([(0, 4), (6, 9)], []) == [(0, 4), (6, 9)]
    assert trace.subtract([(0, 4)], [(0, 4)]) == []


def test_nesting_gives_self_time_and_leaves():
    got = {o.name: o for o in ops(("while", 0, 100), ("fusion.1", 10, 40),
                                  ("all-gather.2", 40, 70), ("tail", 100, 120))}
    assert got["while"].self_ns == 40 and not got["while"].leaf
    assert got["fusion.1"].self_ns == 30 and got["fusion.1"].leaf
    assert got["tail"].leaf
    assert got["all-gather.2"].collective and not got["fusion.1"].collective
    assert Op("psum_invariant.7", 0, 1).collective
    assert Op("fusion.9", 0, 1, category="all-reduce").collective


def test_idle_tops_gaps_and_collectives_by_hand():
    tr = Trace(chips={
        0: ops(("fusion.1", 0, 40), ("all-reduce.1", 40, 60),
               ("fusion.2", 50, 90)),
        1: ops(("fusion.1", 0, 30), ("all-reduce.1", 30, 60),
               ("fusion.2", 80, 90))},
        host_spans=[Op("bench:stretch", 0, 100), Op("bench:sync", 55, 100),
                    Op("bench:train_dispatch", 0, 55)])
    # chip 1 is busy 70 of 100 ns, chip 0 90: the worst chip counts
    assert trace.idle_share(tr, 100e-9) == pytest.approx(0.30)
    assert trace.busy_seconds(tr) == {0: pytest.approx(90e-9),
                                      1: pytest.approx(70e-9)}
    top = dict((n, s) for n, s in trace.top_ops(tr))
    # kinds: fusion.1 and fusion.2 are both "fusion"
    assert top["fusion x2"] == pytest.approx(60e-9)      # (80 + 40) / 2
    assert top["all-reduce x1"] == pytest.approx(25e-9)  # (20 + 30) / 2
    # chip 1: gap 60-80 under bench:sync, tail 90-100 under bench:sync
    assert trace.idle_gaps(tr) == [["bench:sync x2", pytest.approx(30e-9)]]
    coll = trace.collectives(tr)
    assert coll["chip"] == 1 and coll["calls"] == 1
    assert coll["collective_s"] == pytest.approx(30e-9)
    assert coll["exposed_s"] == pytest.approx(30e-9)
    # on chip 0 half of the all-reduce hides under fusion.2
    only0 = Trace(chips={0: tr.chips[0]})
    assert trace.collectives(only0)["exposed_s"] == pytest.approx(10e-9)
    assert trace.collectives(Trace(chips={0: ops(("f", 0, 1))})) is None
    assert trace.idle_share(Trace(), 1.0) is None


def test_collective_in_flight_counts_until_its_done():
    """Across chips a collective is a ``-start``, a span in flight on
    the async line, and a ``-done`` that waits: it is exposed wherever
    nothing else runs under it."""
    tr = Trace(
        chips={0: ops(("%fusion.1 = bf16[8,128]{1,0} fusion(...)", 0, 20),
                      ("%all-gather-done.3 = bf16[32,128]{1,0} "
                       "all-gather-done(...)", 45, 50))},
        in_flight={0: [Op("%all-gather-start.3 = (bf16[8,128]{1,0}, "
                          "bf16[32,128]{1,0}) all-gather-start(...)",
                          10, 50)]})
    coll = trace.collectives(tr)
    assert coll["collective_s"] == pytest.approx(40e-9)
    assert coll["exposed_s"] == pytest.approx(30e-9)
    assert trace.busy_seconds(tr) == {0: pytest.approx(25e-9)}
    assert [n for n, _ in trace.top_ops(tr)] == [
        "fusion bf16[8,128] x1", "all-gather-done bf16[32,128] x1"]


def test_recorded_trace_reduces():
    tr = trace.load(RECORDED)
    assert sorted(tr.chips) == [0, 1, 2, 3]
    assert {s.name for s in tr.host_spans} == {
        "bench:stretch", "bench:sync", "bench:train_dispatch"}
    stretch = next(s for s in tr.host_spans if s.name == "bench:stretch")
    window_s = (stretch.end - stretch.start) / 1e9
    busy = trace.busy_seconds(tr)
    assert all(0 < b < window_s for b in busy.values())
    idle = trace.idle_share(tr, window_s)
    assert idle == pytest.approx(1 - min(busy.values()) / window_s)
    assert 0.05 < idle < 0.5
    # two epochs, each with four halo all_gathers + the gradient psum
    coll = trace.collectives(tr)
    assert coll["calls"] == 10
    assert 0 < coll["exposed_s"] <= coll["collective_s"] < window_s
    names = [n for n, _ in trace.top_ops(tr, 10)]
    assert "all-reduce x2" in names and "all_gather x8" in names
    seconds = [s for _, s in trace.top_ops(tr, 10)]
    assert seconds == sorted(seconds, reverse=True)
    gaps = trace.idle_gaps(tr)
    assert gaps and all(g[0].startswith("bench:") for g in gaps)
    assert sum(s for _, s in gaps) == pytest.approx(
        window_s - min(busy.values()), rel=0.05)


def test_recorded_tpu_trace_reduces():
    tr = trace.load(RECORDED_TPU)
    assert sorted(tr.chips) == [0, 1, 2, 3]
    stretch = next(s for s in tr.host_spans if s.name == "bench:stretch")
    window_s = (stretch.end - stretch.start) / 1e9
    # host spans and device operations share a clock
    for ops_ in tr.chips.values():
        assert stretch.start <= min(o.start for o in ops_)
        assert max(o.end for o in ops_) <= stretch.end
    busy = trace.busy_seconds(tr)
    assert busy[0] == pytest.approx(294.038e-6, rel=1e-6)
    # a 2,048-vertex graph leaves a chip idle 98% of the time
    assert trace.idle_share(tr, window_s) == pytest.approx(
        1 - min(busy.values()) / window_s)
    assert 0.95 < trace.idle_share(tr, window_s) < 0.99
    # two epochs: three halo all-gathers and one gradient all-reduce each
    coll = trace.collectives(tr)
    assert coll["calls"] == 8
    assert coll["collective_s"] == pytest.approx(39.157e-6, rel=1e-6)
    assert coll["exposed_s"] == pytest.approx(coll["collective_s"])
    top = trace.top_ops(tr, 10)
    assert top[0][0] == "all-gather f32[4,536,7] x4"
    assert all("{" not in name and "%" not in name for name, _ in top)
    # copies in flight are kept apart and are not collectives
    assert tr.in_flight[0] and not any(
        o.collective for o in tr.in_flight[0])
    gaps = trace.idle_gaps(tr)
    assert gaps[0][0].startswith("bench:train_dispatch x")
    assert sum(s for _, s in gaps) == pytest.approx(
        window_s - min(busy.values()), rel=0.02)
