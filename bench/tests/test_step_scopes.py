"""Device time inside the train step by program scope
(``layer_metrics/_step_scopes.py``): the reduction on hand-built traces
against a hand-built instruction -> scope map, the rehearsal of the
fixture cell through a fixture table that has the three metrics, and a
recorded pair from a TPU v5e chip — a trace of two epochs of the tiny
GCN and the scope map of the train step that ran them (PR 25's chip
run) — on which the classes add up to the chip's busy time."""

import importlib.util
import json
import os

import pytest

from conftest import BENCH, FIXTURES, ROOT, run_cell

from harness import trace
from harness.trace import Op, Trace

TABLE = os.path.join(FIXTURES, "BENCHMARK.step_scopes.json")
METRICS = ("step_agg_ms", "step_model_ms", "step_unscoped_share")
RECORDED = os.path.join(FIXTURES, "traces",
                        "tpu_1chip_tiny_gcn_scoped.xplane.pb")
RECORDED_MAP = os.path.join(FIXTURES, "traces",
                            "tpu_1chip_tiny_gcn_scoped.scopes.json")


@pytest.fixture(scope="module")
def helper():
    spec = importlib.util.spec_from_file_location(
        "step_scopes_under_test",
        os.path.join(BENCH, "layer_metrics", "_step_scopes.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def ops(*rows):
    out = [Op(name, lo, hi) for name, lo, hi in rows]
    trace.mark_nesting(out)
    return out


FWD3 = "jit(step)/jvp(roc.agg.op03)/while/body/gather"
BWD3 = "jit(step)/transpose(jvp(roc.agg.op03))/while/body/closed_call/add"
SCOPES = {
    "while.1": FWD3, "fusion.5": FWD3, "fusion.6": BWD3,
    "all-gather.2": "jit(step)/jvp(roc.agg.op03)/roc.halo/all_gather",
    "all-reduce.9": "jit(step)/roc.allreduce/psum",
    "fusion.7": "jit(step)/jvp(roc.dense.op02.linear)/dot_general",
    "fusion.8": "jit(step)/roc.opt/mul",
    "fusion.2": "jit(step)/jvp(roc.loss)/reduce_sum",
    "copy.4": "",
}


def chip(offset=0):
    """One chip's line: an eager program whose ``fusion.5`` clashes with
    the step's, then the step: a ``while`` holding its body, a halo
    gather, dense, loss, opt, an all-reduce, a copy without metadata
    and an instruction the map has never heard of."""
    return ops(
        ("%fusion.5 = f32[8]{0} fusion(%p)", 0, 50 + offset),       # eager
        ("%while.1 = (s32[], bf16[64,8]{1,0}) while(%t)", 100, 400),
        ("%fusion.5 = bf16[64,8]{1,0} fusion(%a), kind=kLoop", 110, 250),
        ("%fusion.6 = bf16[64,8]{1,0} fusion(%b), kind=kLoop", 250, 390),
        ("%all-gather.2 = bf16[256,8]{1,0} all-gather(%x)", 400, 430),
        ("%fusion.7 = bf16[64,4]{1,0} fusion(%c), kind=kOutput", 430, 470),
        ("%fusion.2 = f32[]{:T(128)} fusion(%d), kind=kLoop", 470, 480),
        ("%fusion.8 = f32[8,4]{1,0} fusion(%e), kind=kLoop", 480, 500),
        ("%all-reduce.9 = f32[8,4]{1,0} all-reduce(%g)", 500, 520),
        ("%copy.4 = f32[8,4]{1,0} copy(%g)", 520, 540 + offset),
        ("%fusion.99 = f32[4]{0} fusion(%h), kind=kLoop", 560, 570))


def test_attribution_by_hand(helper):
    tr = Trace(chips={0: chip(), 1: chip(offset=20)})
    inside = {0: [(100, 600)], 1: [(100, 600)]}
    got = helper.attribute(tr, inside, SCOPES, epochs=2)
    ms = 1e-6 / 2                         # ns -> ms an epoch, one chip
    rows = {(c, i, w): (v, n) for c, i, w, v, n in got["rows"]}
    # the while's own 20 ns and its forward body; the backward body
    assert rows[("agg", 3, "fwd")] == (pytest.approx(160 * ms), 2)
    assert rows[("agg", 3, "bwd")] == (pytest.approx(140 * ms), 1)
    # the innermost roc. component wins, the index comes from outside
    assert rows[("halo", 3, "fwd")] == (pytest.approx(30 * ms), 1)
    assert rows[("allreduce", None, "fwd")] == (pytest.approx(20 * ms), 1)
    assert rows[("dense", 2, "fwd")][0] == pytest.approx(40 * ms)
    assert ("unscoped", None, "fwd") not in rows
    assert got["by_class"]["agg"] == pytest.approx(300 * ms)
    assert got["by_class"]["dense"] + got["by_class"]["loss"] + got[
        "by_class"]["opt"] == pytest.approx(70 * ms)
    # copy.4 (20 and 40 ns) has no metadata, fusion.99 is not in the map
    assert got["by_class"]["unscoped"] == pytest.approx(40 * ms)
    assert got["unmatched_ms"] == pytest.approx(10 * ms)
    assert got["unscoped_top"][0] == ["copy f32[8,4]", pytest.approx(30 * ms)]
    # the eager fusion.5 is outside the module: not booked to agg
    assert got["outside_ms"] == pytest.approx(60 * ms)
    # nothing lost: the classes add up to all self time in the module,
    # which is the union of its operations
    assert sum(got["by_class"].values()) == pytest.approx(got["step_ms"])
    busy = sum(trace.busy_ns(o) for o in tr.chips.values()) / 2
    assert (got["step_ms"] + got["outside_ms"]) == pytest.approx(busy * ms)


def test_names_as_the_cpu_backend_writes_them(helper):
    tr = Trace(chips={0: ops(("fusion.5", 0, 10), ("copy.4", 10, 14))})
    got = helper.attribute(tr, {0: [(0, 20)]}, SCOPES, epochs=1)
    assert got["by_class"] == {"agg": pytest.approx(10e-6),
                               "unscoped": pytest.approx(4e-6)}
    assert helper.attribute(tr, {}, SCOPES, 1)["step_ms"] == 0


def test_fixture_table_is_the_accepted_one_plus_three_entries():
    def load(path):
        with open(path) as f:
            return json.load(f)
    a, b = load(TABLE), load(os.path.join(FIXTURES, "BENCHMARK.json"))
    added = a["per_layer"][len(b["per_layer"]):]
    a["per_layer"] = a["per_layer"][:len(b["per_layer"])]
    assert a == b
    assert [m["name"] for m in added] == list(METRICS)
    repo = load(os.path.join(ROOT, "BENCHMARK.json"))["per_layer"][-3:]
    assert repo == added
    for m in repo:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves"}
        assert (m["moves"], m["source"], m["better"]) == (
            "epoch_ms", "device_trace", "lower")
        assert os.path.isfile(os.path.join(
            BENCH, "layer_metrics", m["name"] + ".py"))
    assert [m["layer"] for m in repo] == ["aggregation", "model",
                                          "step_loop"]


@pytest.mark.parametrize("cell,classes", [
    ("tiny-gcn.fullgraph", {"agg", "dense", "loss", "opt"}),
    ("tiny-gcn.fullgraph-p4", {"agg", "dense", "loss", "opt", "halo",
                               "allreduce"})])
def test_rehearsal_prints_the_line_and_the_three_metrics(work, cell,
                                                         classes):
    rc, lines, err = run_cell(work, cell, "--trace", "1", benchmark=TABLE)
    assert rc == 0, err[-2000:]
    result = lines[-1]
    assert result["correct"] is True
    for name in METRICS:                  # there, with the timing null
        assert result["metrics"][name]["value"] is None
    (line,) = [ln["step_scopes"] for ln in lines if "step_scopes" in ln]
    assert lines.index({"step_scopes": line}) < len(lines) - 1
    assert line["map_from"] == "loaded" and line["map_s"] is None
    assert line["module"].startswith("jit_") and line["text_bytes"] > 0
    assert {row[0] for row in line["rows"]} == classes
    assert all(row[3] is None and row[4] > 0 for row in line["rows"])
    both = {(c, i) for c, i, way, _, _ in line["rows"] if way == "bwd"}
    assert {(c, i) for c, i, _, _, _ in line["rows"] if c == "agg"} == {
        ("agg", 3), ("agg", 6)} == {k for k in both if k[0] == "agg"}


def test_recorded_chip_pair_adds_up_to_busy_time(helper):
    with open(RECORDED_MAP) as f:
        got = json.load(f)
    tr = trace.load(RECORDED)
    assert list(tr.chips) == [0]
    assert all(o.name.startswith("%") for o in tr.chips[0])
    inside = helper.module_intervals(RECORDED, got["module"])
    assert len(inside[0]) == 2                      # two traced epochs
    res = helper.attribute(tr, inside, got["scopes"], epochs=2)
    # every operation of the step is in the program's own text
    assert res["unmatched_ms"] == 0
    # the classes + what ran outside the module = the chip's busy time
    busy_ms = trace.busy_seconds(tr)[0] * 1e3 / 2
    assert sum(res["by_class"].values()) == pytest.approx(res["step_ms"])
    assert res["step_ms"] + res["outside_ms"] == pytest.approx(
        busy_ms, rel=5e-3)
    rows = {(c, i, w) for c, i, w, _, _ in res["rows"]}
    assert {("agg", 3, "fwd"), ("agg", 3, "bwd"), ("agg", 6, "fwd"),
            ("agg", 6, "bwd")} <= rows
    # no "opt" row at this size: XLA folded the Adam update into the
    # weight-gradient fusions, which carry the linear's metadata
    assert {"dense", "loss"} <= {c for c, _, _ in rows}
    assert res["by_class"].get("unscoped", 0.0) < 0.25 * res["step_ms"]
