"""The Graph Transformer configuration's part of the benchmark: the plain
reference against a dense-matrix evaluation of the layer equations on a
50-vertex graph, the two readers this configuration's cell brings
(``tfattn_ms``, ``tfattn_roofline``) and their byte model on hand-built
runs — and on a run of a program that lacks what they read, where each
must return nothing — the cell ``gtrans-arxiv.fullgraph`` as
``harness/cells.py`` finds it, and the tiny cell of the fixture table
end to end under ``--rehearsal`` with the precision probe."""

import json
import os
from types import SimpleNamespace

import numpy as np
import pytest

from conftest import FIXTURES, ROOT, run_cell

from harness import cells

TABLE = os.path.join(FIXTURES, "BENCHMARK.gtrans.json")
METRICS = ("tfattn_ms", "tfattn_roofline")
CELL = "gtrans-arxiv.fullgraph"


def load(path):
    with open(path) as f:
        return json.load(f)


# ------------------------------------------------------- the reference

def _dense_graph(n=50, seed=4):
    """Symmetric 0/1 adjacency with every self edge and one pair stored
    twice, as CSR."""
    rng = np.random.default_rng(seed)
    a = (rng.random((n, n)) < 0.12).astype(np.int64)
    a = np.maximum(a, a.T)
    np.fill_diagonal(a, 1)
    a[3, 7] = a[7, 3] = 2
    col = np.concatenate([np.repeat(np.arange(n), a[v]) for v in range(n)])
    row_ptr = np.concatenate([[0], np.cumsum(a.sum(axis=1))])
    return a.astype(np.float64), row_ptr.astype(np.int64), col.astype(np.int32)


def _dense_layer(x, a, p, l, heads, concat):
    """One Graph Transformer layer, float64, the adjacency a matrix of
    multiplicities: per head a softmax over each row's stored edges."""
    def lin(k):
        return x @ p[f"linear_{k}"] + p[f"linear_{k}_b"]

    q, kv, r = lin(3 * l), lin(3 * l + 1), lin(3 * l + 2)
    F = q.shape[1]
    d = F // heads
    k, v = kv[:, :F], kv[:, F:]
    m = np.zeros_like(q)
    for h in range(heads):
        sl = slice(h * d, (h + 1) * d)
        s = q[:, sl] @ k[:, sl].T / np.sqrt(d)
        s = np.where(a > 0, s, -np.inf)
        e = a * np.exp(s - s.max(axis=1, keepdims=True))
        m[:, sl] = (e / e.sum(axis=1, keepdims=True)) @ v[:, sl]
    if not concat:
        m = m.reshape(len(x), heads, d).mean(axis=1)
    w = p[f"tfattn_{l}_beta"].reshape(3, -1)
    beta = 1 / (1 + np.exp(-(m * w[0] + r * w[1] + (m - r) * w[2]).sum(1)))
    return beta[:, None] * r + (1 - beta[:, None]) * m


def test_reference_is_the_equations_on_a_dense_matrix():
    import jax
    import reference
    from references import gtrans
    n, f, hidden, c, heads = 50, 6, 8, 4, 2
    a, row_ptr, col = _dense_graph(n)
    rng = np.random.default_rng(1)
    x = rng.standard_normal((n, f))
    layers = [f, hidden, hidden, c]
    p = {}
    for l in range(3):
        fin = layers[l]
        out = heads * layers[l + 1] if l == 2 else layers[l + 1]
        root = layers[l + 1]
        for k, w in ((3 * l, out), (3 * l + 1, 2 * out), (3 * l + 2, root)):
            p[f"linear_{k}"] = rng.standard_normal((fin, w)) * 2 / np.sqrt(fin)
            p[f"linear_{k}_b"] = 0.2 * rng.standard_normal(w)
        p[f"tfattn_{l}_beta"] = rng.standard_normal(3 * root)
        if l < 2:
            p[f"ln_{l}_scale"] = 1 + 0.2 * rng.standard_normal(root)
            p[f"ln_{l}_shift"] = 0.2 * rng.standard_normal(root)
    h = x
    for l in range(2):
        o = _dense_layer(h, a, p, l, heads, True)
        z = (o - o.mean(1, keepdims=True)) / np.sqrt(o.var(1, keepdims=True)
                                                      + 1e-5)
        h = np.maximum(p[f"ln_{l}_scale"] * z + p[f"ln_{l}_shift"], 0)
    want = _dense_layer(h, a, p, 2, heads, False)
    model = {"family": "gtrans", "layers": layers, "heads": heads}
    got = reference.run(gtrans.forward, p, x.astype(np.float32),
                        np.zeros(n, np.int32), np.ones(n, np.int32),
                        row_ptr, col, model, on=jax.devices("cpu")[0])
    np.testing.assert_allclose(got["logits"], want, rtol=2e-4, atol=2e-5)
    assert np.isfinite(got["loss"])


# --------------------------------------------------------- the readers

def _cell():
    return cells.load_cell(TABLE, "tiny-gtrans.fullgraph")


def _entry(op, heads, d, out):
    return {"op": op, "heads": heads, "head_width": d, "layout": "ell",
            "score": "dot", "gather_lanes_fwd": 2 * heads * d,
            "out_width": out}


DOT = [_entry(4, 2, 128, 256), _entry(10, 2, 128, 256),
       _entry(16, 2, 40, 40)]
ROWS = [["agg", 4, "fwd", 30.0, 1], ["agg", 4, "bwd", 60.0, 1],
        ["agg", 10, "fwd", 32.0, 1], ["agg", 10, "bwd", 61.0, 1],
        ["agg", 16, "fwd", 9.0, 1], ["agg", 16, "bwd", 18.0, 1],
        ["agg", 20, "fwd", 99.0, 1],           # not a dot-product op
        ["dense", 5, "fwd", 1.0, 1], ["loss", None, "fwd", 4.0, 1]]
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
E, V = 2_501_829, 169_343


def _run(**over):
    import jax.numpy as jnp
    base = dict(
        cell=_cell(), peaks=PEAKS, rehearsal=False,
        trainer=SimpleNamespace(compute=jnp.bfloat16),
        data=SimpleNamespace(col_idx=np.zeros(E, np.int8),
                             row_ptr=np.zeros(V + 1, np.int8)),
        scratch={"step_scopes": {"rows": ROWS},
                 "resolved": {"attention": DOT}})
    base.update(over)
    return SimpleNamespace(**base)


def _reader(name):
    return _cell().module("layer_metrics", name)


def test_byte_model():
    tf = _reader("_tfattn")
    got = tf.forward_bytes(E, V, DOT[0], 2)
    # a 512-lane [k | v] row and an index a stored edge; the query and
    # output rows and four float32 statistics a vertex
    assert got == E * (1024 + 4) + V * (512 + 512 + 16)
    assert tf.forward_flops(E, DOT[0]) == 4.0 * E * 256
    # the output layer writes its averaged 40 lanes
    assert tf.forward_bytes(E, V, DOT[2], 2) == \
        E * (320 + 4) + V * (160 + 80 + 16)


def test_tfattn_ms_is_every_row_of_the_dot_product_ops():
    assert _reader("tfattn_ms").read(_run()) == pytest.approx(210.0)


def test_tfattn_roofline_is_the_widest_ops_forward():
    tf = _reader("_tfattn")
    least_ms = max(tf.forward_bytes(E, V, DOT[0], 2) / 819e9,
                   tf.forward_flops(E, DOT[0]) / 197e12) * 1e3
    got = _reader("tfattn_roofline").read(_run())
    # the first of the two widest ops: op 4, 30 ms
    assert got == pytest.approx(100 * least_ms / 30.0)
    assert 0 < got < 100


@pytest.mark.parametrize("name", METRICS)
def test_readers_find_nothing_in_a_program_without_their_sources(name):
    """A parent commit: no dot-product entry in the manifest, no
    instruction scopes at all.  Nothing raises."""
    read = _reader(name).read
    bare = _run(trainer=SimpleNamespace(), trace=None, trace_epochs=0,
                scratch={"resolved": {"aggr_impl": "sectioned"}})
    assert read(bare) is None
    assert read(_run(scratch={"step_scopes": None, "resolved": None})) \
        is None
    # another model's attention, traced: additive entries only
    gat = [{k: v for k, v in e.items() if k != "score"} for e in DOT]
    other = _run(scratch={"step_scopes": {"rows": ROWS},
                          "resolved": {"attention": gat}})
    assert read(other) is None


def test_probe_states_what_must_pass():
    probe = _cell().module("probes", "gtrans_precision")
    assert probe.MUST_PASS == {"as_configured": True,
                               "softmax_bf16": False,
                               "weighted_sum_bf16": False}


# ------------------------------------------------------------ the cell

def test_cell_is_found_by_name_with_its_files():
    cell = cells.load_cell(os.path.join(ROOT, "BENCHMARK.json"), CELL)
    assert cell.chips == 1 and cell.traffic["name"] == "fullgraph"
    cfg = cell.config
    assert cfg["reference"] == "gtrans"
    assert cfg["reduced"] == ["epochs"]
    assert cfg["model"] == {"family": "gtrans",
                            "layers": [128, 256, 256, 40], "heads": 2}
    assert cfg["graph"] == {"num_nodes": 169343, "num_edges": 2501829,
                            "in_dim": 128, "num_classes": 40}
    cli = cfg["cli"]
    assert cli[cli.index("--model") + 1] == "gtrans"
    assert cli[cli.index("--heads") + 1] == "2"
    assert cli[cli.index("-layers") + 1] == "128-256-256-40"
    assert cli[cli.index("-dropout") + 1] == "0.3"
    assert "--impl" not in cli and "--remat" not in cli
    assert cfg["parameters"]["here"] == 469_904 == \
        133_376 + 264_448 + 72_080
    assert {"substrate", "num_edges", "directed", "widths_recalled_offline",
            "label_input", "weight_decay", "dtype"} <= set(cfg["assumed"])
    assert len(cfg["source"]) <= 200
    assert cell.extras["trace"] == {"epochs": 2}
    assert os.path.isfile(cell.find("references", "gtrans", ".py"))
    assert os.path.isfile(cell.find("probes", "gtrans_precision", ".py"))
    mine = [m for m in cell.benchmark["per_layer"] if m["name"] in METRICS]
    assert [m["name"] for m in mine] == list(METRICS)
    assert all(m["workloads"] == [CELL] and m["moves"] == "epoch_ms"
               and m["source"] == "device_trace"
               and m["layer"] == "aggregation" for m in mine)
    for m in mine:
        assert os.path.isfile(cell.find("layer_metrics", m["name"], ".py"))
    tol = cell.extras["correct"]
    assert 0 < tol["row_rel_l2_median"] < tol["row_rel_l2_max"] <= 0.05
    assert (tol["loss_rel"], tol["loss_abs"]) == (0.03, 0.5)
    assert len(tol["reason"]) > 100
    names = [w["name"] for w in cell.benchmark["workloads"]]
    assert names.count(CELL) == 1
    assert sum(w["chips"] == 4 for w in cell.benchmark["workloads"]) == 1


def test_fixture_table_is_the_deepergcn_one_plus_this_cell():
    a = load(TABLE)
    b = load(os.path.join(FIXTURES, "BENCHMARK.deepergcn.json"))
    assert [m["name"] for m in a["per_layer"][len(b["per_layer"]):]] == \
        list(METRICS)
    a["per_layer"] = a["per_layer"][:len(b["per_layer"])]
    assert a.pop("configs")[:-1] == b.pop("configs")
    assert a.pop("workloads")[:-1] == b.pop("workloads")
    assert a == b


def test_tiny_cell_end_to_end(work):
    rc, lines, err = run_cell(work, "tiny-gtrans.fullgraph", "--trace", "1",
                              "--probe", "gtrans_precision",
                              benchmark=TABLE)
    assert rc == 0, err[-2000:]
    result = lines[-1]
    assert result["correct"] is True and result["failed"] == 0
    # there, with the timing null; the roofline needs the chip's peaks
    # and is left out of a rehearsal's line
    assert result["metrics"]["tfattn_ms"]["value"] is None
    assert "tfattn_roofline" not in result["metrics"]
    plan = next(ln for ln in lines if "plan" in ln)["plan"]
    assert plan["aggr_impl"] == "ell"
    att = plan["attention"]
    assert [e["op"] for e in att] == [4, 10, 16]
    assert {e["score"] for e in att} == {"dot"}
    assert [e["gather_lanes_fwd"] for e in att] == [48, 48, 28]
    assert {b["rule"] for b in plan["attention_backward"]} == {
        "transposed_two_pass"}
    mem = plan["memory_plan"]
    assert (mem["aggregating_ops"], mem["linear_ops"]) == (3, 9)
    kinds = [k for _op, k, _n, _row in mem["saved"]]
    assert kinds.count("layer_norm") == 2
    (scopes,) = [ln["step_scopes"] for ln in lines if "step_scopes" in ln]
    assert {(i, way) for cls, i, way, _, _ in scopes["rows"]
            if cls == "agg"} == {(i, w) for i in (4, 10, 16)
                                 for w in ("fwd", "bwd")}
    check = next(ln for ln in lines if "check" in ln)["check"]
    assert check["row_rel_l2_max"] < 1e-4
    probe = next(ln for ln in lines if "probe" in ln)["probe"]
    assert set(probe["variants"]) == {"as_configured", "softmax_bf16",
                                      "weighted_sum_bf16"}
    # the fixture's tolerances are float32's: every bfloat16 variant
    # fails them, the float32 program does not
    assert not any(v["passes"] for v in probe["variants"].values())
    assert probe["as_the_program"]["row_rel_l2_max"] < 1e-4
    # an old cell of the same table reads neither
    rc, lines, err = run_cell(work, "tiny-gcn.fullgraph", "--trace", "1",
                              benchmark=TABLE)
    assert rc == 0, err[-2000:]
    assert not set(METRICS) & set(lines[-1]["metrics"])
