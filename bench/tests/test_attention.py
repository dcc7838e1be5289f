"""The attention configuration's part of the benchmark: the byte and
operation model against hand-counted shapes, the phase reduction on a
hand-built trace, the precision probe's verdict function, the tiny
attention cell of the fixture table end to end, and the rehearsal of
the admitted cell ``gat-arxiv.fullgraph`` itself (every timing null)."""

import importlib.util
import json
import os

import numpy as np
import pytest

from conftest import BENCH, FIXTURES, ROOT, run_cell

from harness import trace
from harness.trace import Op, Trace

TABLE = os.path.join(FIXTURES, "BENCHMARK.attention.json")
METRICS = ("attn_softmax_ms", "attn_gather_ms", "attn_roofline")


def load_module(*rel):
    path = os.path.join(BENCH, *rel)
    spec = importlib.util.spec_from_file_location(
        "under_test_" + rel[-1].replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load(path):
    with open(path) as f:
        return json.load(f)


@pytest.mark.parametrize("shape,nbytes,flops", [
    # 5 edges x (2 heads x 4 wide x 2 B = 16, + 2 scores x 4 B, + 4 B
    # index = 28) + 3 vertices x (16 read + 16 written + 8 = 40)
    ((5, 3, 2, 4, 2), 5 * 28 + 3 * 40, 2 * 5 * 2 * 4),
    # one head, float32 rows: 7 x (12 + 4 + 4) + 2 x (24 + 4)
    ((7, 2, 1, 3, 4), 7 * 20 + 2 * 28, 2 * 7 * 3),
    # gat-arxiv's wide layers in bfloat16: 1,516 B an edge, 3,012 a vertex
    ((2_501_829, 169_343, 3, 250, 2),
     2_501_829 * 1516 + 169_343 * 3012, 2 * 2_501_829 * 750),
])
def test_byte_model_from_hand_counted_shapes(shape, nbytes, flops):
    model = load_module("layer_metrics", "_attention.py")
    E, V, K, d, itemsize = shape
    assert model.attention_bytes(E, V, K, d, itemsize) == nbytes
    assert model.attention_flops(E, K, d) == flops


def test_least_time_at_arxiv_shape_is_bound_by_hbm():
    import roofline
    model = load_module("layer_metrics", "_attention.py")
    peaks = load(os.path.join(BENCH, "peaks.json"))["TPU v5 lite"]
    nbytes = model.attention_bytes(2_501_829, 169_343, 3, 250, 2)
    flops = model.attention_flops(2_501_829, 3, 250)
    assert nbytes == 4_302_833_880
    least = roofline.least_seconds(nbytes, flops, peaks)
    assert least == pytest.approx(nbytes / 819e9)
    assert least * 1e3 == pytest.approx(5.2538, rel=1e-4)


def test_widest_op_is_the_first_of_equals():
    from types import SimpleNamespace
    model = load_module("layer_metrics", "_attention.py")
    entries = [{"op": 3, "heads": 3, "head_width": 250},
               {"op": 9, "heads": 3, "head_width": 250},
               {"op": 15, "heads": 1, "head_width": 40}]
    run = SimpleNamespace(scratch={"resolved": {"attention": entries}})
    assert model.widest_op(run)["op"] == 3
    assert model.widest_op(SimpleNamespace(scratch={})) is None
    assert model.widest_op(SimpleNamespace(
        scratch={"resolved": {"aggr_impl": "sectioned"}})) is None


def ops(*rows):
    out = [Op(name, lo, hi) for name, lo, hi in rows]
    trace.mark_nesting(out)
    return out


FWD = "jit(step)/jvp(roc.agg.op03)/"
BWD = "jit(step)/transpose(jvp(roc.agg.op03))/"
SCOPES = {
    "while.1": FWD + "while",
    "fusion.1": FWD + "while/body/checkpoint/roc.attn.scores/gather",
    "fusion.2": FWD + "while/body/checkpoint/roc.attn.stats/exp",
    "fusion.3": FWD + "while/body/checkpoint/roc.attn.gather/dot_general",
    "fusion.4": BWD + "while/body/checkpoint/roc.attn.gather/scatter-add",
    "fusion.5": FWD + "roc.attn.scores/dot_general",
    "concatenate.6": FWD + "concatenate",
    "all-gather.7": FWD + "roc.halo/all_gather",
    "fusion.8": "jit(step)/jvp(roc.agg.op06)/while/body/gather",
    "fusion.9": "jit(step)/jvp(roc.dense.op02.linear)/dot_general",
}


def test_phase_reduction_by_hand():
    phases = load_module("layer_metrics", "_attention_phases.py")
    step_scopes = load_module("layer_metrics", "_step_scopes.py")
    tr = Trace(chips={0: ops(
        ("%fusion.1 = f32[8]{0} fusion(%p)", 0, 50),        # an eager op
        ("%fusion.5 = f32[64,3]{1,0} fusion(%z)", 100, 110),
        ("%concatenate.6 = bf16[65,8]{1,0} concatenate(%z)", 110, 114),
        ("%all-gather.7 = bf16[64,8]{1,0} all-gather(%z)", 114, 120),
        ("%while.1 = (s32[], bf16[64,8]{1,0}) while(%t)", 120, 400),
        ("%fusion.1 = f32[16,8,3]{2,1,0} fusion(%a)", 130, 160),
        ("%fusion.2 = f32[16,8,3]{2,1,0} fusion(%b)", 160, 200),
        ("%fusion.3 = bf16[16,8]{1,0} fusion(%c)", 200, 390),
        ("%fusion.4 = bf16[65,8]{1,0} fusion(%d)", 400, 700),
        ("%fusion.8 = bf16[64,8]{1,0} fusion(%e)", 700, 800),
        ("%fusion.9 = bf16[64,4]{1,0} fusion(%f)", 800, 900))})
    got = phases.attribute(tr, {0: [(100, 1000)]}, SCOPES, {3},
                           epochs=2, step_scopes=step_scopes)
    ms = 1e-6 / 2
    rows = {(i, ph, way): (v, n) for i, ph, way, v, n in got["rows"]}
    assert rows == {
        (3, "scores", "fwd"): (pytest.approx(40 * ms), 2),
        (3, "stats", "fwd"): (pytest.approx(40 * ms), 1),
        (3, "gather", "fwd"): (pytest.approx(190 * ms), 1),
        (3, "gather", "bwd"): (pytest.approx(300 * ms), 1)}
    # the while's own 20 ns and the concatenate; the halo is not agg's,
    # op 6 is no attention op, the eager fusion.1 is outside the step
    assert got["unphased_ms"] == pytest.approx(24 * ms)
    # no attention op: nothing unphased either
    none = phases.attribute(tr, {0: [(100, 1000)]}, SCOPES, set(),
                            epochs=2, step_scopes=step_scopes)
    assert none["unphased_ms"] == 0 and len(none["rows"]) == 4


def test_probe_verdicts_by_hand():
    probe = load_module("probes", "attention_precision.py")
    tol = {"row_rel_l2_max": 0.03, "row_rel_l2_median": 0.008,
           "loss_rel": 0.01, "loss_abs": 0.5}
    fine = {"row_rel_l2_max": 0.01, "row_rel_l2_median": 0.003}
    assert probe.held_to(tol, fine, 100.4, 100.0) == {
        "row_rel_l2_max": True, "row_rel_l2_median": True, "loss": True}
    coarse = {"row_rel_l2_max": 0.2, "row_rel_l2_median": 0.004}
    assert probe.held_to(tol, coarse, 120.0, 100.0) == {
        "row_rel_l2_max": False, "row_rel_l2_median": True,
        "loss": False}
    assert probe.MUST_PASS == {"as_configured": True,
                               "softmax_bf16": False,
                               "numerator_bf16": False}


def test_probe_accumulates_a_row_in_order():
    """Float32: the staged walk over the rows is a segment sum.
    Rounded to bfloat16 after every addition: a row of 600 ones stalls
    at 256 (257 is not a bfloat16), a row of 5 is exact."""
    import jax.numpy as jnp
    probe = load_module("probes", "attention_precision.py")
    deg = np.array([5, 600, 0, 1, 40], np.int64)
    row_ptr = np.concatenate([[0], np.cumsum(deg)])
    rng = np.random.default_rng(0)
    col = rng.integers(0, 5, int(deg.sum())).astype(np.int32)
    rows = probe.Rows(row_ptr, col)
    assert [n for _, _, n in rows.stages][:3] == [4, 3, 3]
    assert rows.stages[-1][1] == 600
    z = rng.standard_normal((5, 6)).astype(np.float32)
    w = rng.random((int(deg.sum()), 2)).astype(np.float32)
    got = np.asarray(probe.accumulate(rows, jnp.asarray(w), jnp.asarray(z)))
    dst = np.repeat(np.arange(5), deg)
    want = np.zeros((5, 6), np.float32)
    np.add.at(want, dst, (w[:, :, None] * z[col].reshape(-1, 2, 3)
                          ).reshape(-1, 6))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    ones = probe.accumulate(rows, jnp.ones((int(deg.sum()), 1)),
                            jnp.ones((5, 1)), probe.bf16)
    assert np.asarray(ones)[:, 0].tolist() == [5.0, 256.0, 0.0, 1.0, 40.0]


def test_fixture_table_is_the_scoped_one_plus_the_attention_entries():
    a = load(TABLE)
    b = load(os.path.join(FIXTURES, "BENCHMARK.step_scopes.json"))
    added = a["per_layer"][len(b["per_layer"]):]
    a["per_layer"] = a["per_layer"][:len(b["per_layer"])]
    assert a.pop("configs")[:-1] == b.pop("configs")
    assert a.pop("workloads")[:-1] == b.pop("workloads")
    assert a == b
    assert [m["name"] for m in added] == list(METRICS)
    repo = load(os.path.join(ROOT, "BENCHMARK.json"))
    mine = [m for m in repo["per_layer"] if m["name"] in METRICS]
    assert [m["name"] for m in mine] == list(METRICS)
    for m, fixture in zip(mine, added):
        assert m["workloads"] == ["gat-arxiv.fullgraph"]
        assert {**m, "workloads": fixture["workloads"]} == fixture
        assert (m["layer"], m["moves"], m["source"]) == (
            "aggregation", "epoch_ms", "device_trace")
        assert os.path.isfile(os.path.join(
            BENCH, "layer_metrics", m["name"] + ".py"))
    assert (mine[2]["unit"], mine[2]["better"]) == ("%", "higher")


def check_rehearsal(lines, ops):
    result = lines[-1]
    assert result["correct"] is True and result["failed"] == 0
    # there, with the timing null; the roofline share needs the chip's
    # peaks, which a rehearsal has not
    for name in ("attn_softmax_ms", "attn_gather_ms", "step_agg_ms",
                 "step_model_ms", "step_unscoped_share",
                 "device_idle_share", "host_build_s", "compile_s"):
        assert result["metrics"][name]["value"] is None, name
    assert result["metrics"]["compiles_in_window"]["value"] == 0
    assert "attn_roofline" not in result["metrics"]
    assert result["device"]["memory_peak_bytes"] is None
    (line,) = [ln["attn_phases"] for ln in lines if "attn_phases" in ln]
    assert line["unphased_ms"] is None
    assert {(i, ph, way) for i, ph, way, _, _ in line["rows"]} == {
        (i, ph, way) for i in ops for ph in ("scores", "stats", "gather")
        for way in ("fwd", "bwd")}
    assert all(ms is None and n > 0 for _, _, _, ms, n in line["rows"])
    plan = next(ln for ln in lines if "plan" in ln)["plan"]
    assert [e["op"] for e in plan["attention"]] == ops
    return plan


def test_tiny_attention_cell_end_to_end(work):
    rc, lines, err = run_cell(work, "tiny-gat.fullgraph", "--trace", "1",
                              "--probe", "attention_precision",
                              benchmark=TABLE)
    assert rc == 0, err[-2000:]
    plan = check_rehearsal(lines, [3, 9, 15])
    assert plan["attention"][0] == {
        "op": 3, "heads": 3, "head_width": 10, "layout": "ell",
        "edge_passes": 1, "padded_slots_per_pass": 37720,
        "carry_rows": None}
    probe = next(ln for ln in lines if "probe" in ln)["probe"]
    assert set(probe["variants"]) == {"as_configured", "softmax_bf16",
                                      "numerator_bf16"}
    # the fixture's tolerances are float32's: every bfloat16 variant
    # fails them, the float32 program does not
    assert not any(v["passes"] for v in probe["variants"].values())
    assert probe["as_the_program"]["row_rel_l2_max"] < 1e-4
    # an old cell of the same table reads none of the three
    rc, lines, err = run_cell(work, "tiny-gcn.fullgraph", "--trace", "1",
                              benchmark=TABLE)
    assert rc == 0, err[-2000:]
    assert not set(METRICS) & set(lines[-1]["metrics"])
    assert not [ln for ln in lines if "attn_phases" in ln]


@pytest.mark.skipif(
    os.environ.get("BENCH_ADMITTED_REHEARSAL") != "1",
    reason="the admitted cell at its real size on the CPU takes ~15 "
           "minutes on 8 cores: set BENCH_ADMITTED_REHEARSAL=1")
def test_admitted_cell_rehearsal_has_every_timing_null(work):
    """``gat-arxiv.fullgraph`` of the repository's own table, at its
    real size, through the same command line as the chip run."""
    import subprocess
    import sys
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "gat-arxiv.fullgraph", "--seed", "3000000019", "--seconds", "1",
         "--trace", "1", "--rehearsal", "--data-dir",
         os.path.join(work, "data")],
        capture_output=True, text=True, timeout=3600, cwd=ROOT,
        check=False, env={**os.environ, "JAX_COMPILATION_CACHE_DIR":
                          os.path.join(work, "cache")})
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [json.loads(ln) for ln in p.stdout.splitlines()
             if ln.startswith("{")]
    plan = check_rehearsal(lines, [3, 9, 15])
    assert [(e["heads"], e["head_width"]) for e in plan["attention"]] == [
        (3, 250), (3, 250), (1, 40)]
    assert lines[-1]["device"]["platform"] == "cpu"
    graph = next(ln for ln in lines if "plan" in ln)["graph"]
    assert graph["num_nodes"] == 169343
    assert abs(graph["num_edges"] - 2501829) < 2501829 // 1000
