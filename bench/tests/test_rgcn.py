"""The R-GCN configuration's part of the benchmark: the plain reference
against a dense-matrix evaluation of the layer equation on a small typed
graph, the byte models from hand-counted shapes, the three readers this
configuration's cell brings (``rel_agg_roofline``, ``opt_embed_ms``,
``opt_embed_roofline``) on hand-built runs — and on a run of a program
that lacks what they read, where each must return nothing — the
substrate, the cell ``rgcn-mag.fullgraph-typed`` as ``harness/cells.py``
finds it, and the tiny cell of the fixture table end to end under
``--rehearsal`` with the precision probe."""

import json
import os
from types import SimpleNamespace

import numpy as np
import pytest

from conftest import FIXTURES, ROOT, run_cell

from harness import cells

TABLE = os.path.join(FIXTURES, "BENCHMARK.rgcn.json")
METRICS = ("rel_agg_roofline", "opt_embed_ms", "opt_embed_roofline")
CELL = "rgcn-mag.fullgraph-typed"
MAG = [736389, 1134649, 8740, 59965]


def load(path):
    with open(path) as f:
        return json.load(f)


# ------------------------------------------------------- the reference

def _typed_graph(kinds=(7, 9, 3, 4), seed=2):
    """A dense 0/1 adjacency over four kinds: bipartite blocks and one
    block within kind 0, symmetric, every self edge; as CSR."""
    rng = np.random.default_rng(seed)
    off = np.concatenate([[0], np.cumsum(kinds)])
    n = int(off[-1])
    a = np.zeros((n, n), np.int64)
    for s, d in [(1, 2), (1, 0), (0, 0), (0, 3)]:
        blk = rng.random((kinds[d], kinds[s])) < 0.35
        a[off[d]:off[d + 1], off[s]:off[s + 1]] |= blk
    a = np.maximum(a, a.T)
    a[off[2], :] = a[:, off[2]] = 0          # an institution nobody names
    np.fill_diagonal(a, 1)
    col = np.concatenate([np.flatnonzero(a[v]) for v in range(n)])
    row_ptr = np.concatenate([[0], np.cumsum(a.sum(axis=1))])
    return a, off, row_ptr.astype(np.int64), col.astype(np.int32)


def test_reference_is_the_layer_equation_on_dense_blocks():
    import jax
    import reference
    from references import rgcn
    kinds, f, h, c = (7, 9, 3, 4), 6, 5, 4
    a, off, row_ptr, col = _typed_graph(kinds)
    n = int(off[-1])
    rng = np.random.default_rng(1)
    x = rng.standard_normal((n, f))
    x[kinds[0]:] = 0
    rels = [(s, d) for s in range(4) for d in range(4)
            if (a[off[d]:off[d + 1], off[s]:off[s + 1]]
                - (np.eye(n)[off[d]:off[d + 1], off[s]:off[s + 1]])
                ).clip(0).any()]
    assert len(rels) == 7
    params = {f"embed_{k}": rng.standard_normal((kinds[k], f))
              for k in (1, 2, 3)}
    for l, (i, o) in enumerate([(f, h), (h, c)]):
        for s, d in rels:
            params[f"rel{l}_{s}_{d}"] = rng.standard_normal((i, o)) / 2
        for k in range(4):
            params[f"root{l}_{k}"] = rng.standard_normal((i, o)) / 2
            params[f"root{l}_{k}_b"] = rng.standard_normal(o)
    # the equation, float64, a block of the adjacency a relation
    t = np.concatenate([x[:kinds[0]]] + [params[f"embed_{k}"]
                                         for k in (1, 2, 3)])
    adj = a - np.eye(n, dtype=np.int64)
    for l in (0, 1):
        out = np.zeros((n, params[f"root{l}_0"].shape[1]))
        for k in range(4):
            lo, hi = off[k], off[k + 1]
            out[lo:hi] = t[lo:hi] @ params[f"root{l}_{k}"] \
                + params[f"root{l}_{k}_b"]
        for s, d in rels:
            blk = adj[off[d]:off[d + 1], off[s]:off[s + 1]].astype(float)
            deg = blk.sum(axis=1, keepdims=True)
            mean = np.where(deg > 0, blk / np.maximum(deg, 1), 0.0)
            out[off[d]:off[d + 1]] += mean @ (
                t[off[s]:off[s + 1]] @ params[f"rel{l}_{s}_{d}"])
        t = np.maximum(out, 0.0) if l == 0 else out
    model = {"family": "rgcn", "layers": [f, h, c],
             "node_types": list(kinds), "embed_types": [1, 2, 3]}
    labels = np.zeros(n, np.int32)
    mask = np.zeros(n, np.int32)
    mask[:kinds[0]] = 1
    got = reference.run(rgcn.forward, params, x.astype(np.float32),
                        labels, mask, row_ptr, col, model,
                        on=jax.devices("cpu")[0])
    np.testing.assert_allclose(got["logits"], t, rtol=2e-4, atol=2e-5)
    assert np.isfinite(got["loss"])
    # a relation the parameters lack is not quietly skipped
    fewer = {k: v for k, v in params.items() if "_3_0" not in k}
    bad = reference.run(rgcn.forward, fewer, x.astype(np.float32),
                        labels, mask, row_ptr, col, model,
                        on=jax.devices("cpu")[0])
    assert np.isnan(bad["logits"]).all()


# ------------------------------------------------- bytes, from shapes

def _cell():
    return cells.load_cell(TABLE, "tiny-rgcn.fullgraph-typed")


def _reader(name):
    return _cell().module("layer_metrics", name)


def test_byte_models_from_hand_counted_shapes():
    rel = _reader("_relations")
    # 10 edges gathering 64-wide bf16 rows into 3 rows: an edge reads a
    # 128-byte row, a 4-byte index and a 4-byte weight; a row is written
    assert rel.relation_aggregation_bytes(10, 64, 2, 3) == \
        10 * (128 + 4 + 4) + 3 * 128
    # ogbn-mag's widest relation aggregation, gather_first at layer 1
    assert rel.relation_aggregation_bytes(42_222_014, 128, 2, 4_547_170) \
        == 42_222_014 * 264 + 4_547_170 * 256
    # Adam under --dtype mixed: 16 bytes read, 12 + 2 written
    assert rel.ADAM_MIXED_BYTES == 30
    assert rel.embedding_adam_bytes(1_203_354, 128) == 154_029_312 * 30


ROWS = [["agg", 2, "fwd", 500.0, 1], ["agg", 2, "bwd", 450.0, 1],
        ["agg", 8, "fwd", 520.0, 1], ["agg", 8, "bwd", 470.0, 1],
        ["dense", 3, "fwd", 9.0, 1], ["opt", None, "fwd", 12.0, 1]]
RESOLVED = {
    "node_types": MAG, "relation_edges": 42_222_014,
    "embedding_rows": 1_203_354, "embedding_bytes": 1_203_354 * 128 * 4,
    "rel_layers": [
        {"op": 2, "layer": 0, "rel_order": "gather_first", "in_dim": 128,
         "out_dim": 64, "gather_width": 128, "scan_width": 128,
         "stacked_rows": 4_547_170},
        {"op": 8, "layer": 1, "rel_order": "gather_first", "in_dim": 64,
         "out_dim": 349, "gather_width": 64, "scan_width": 128,
         "stacked_rows": 4_547_170}]}
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


def _run(**over):
    import jax.numpy as jnp
    scratch = {"step_scopes": {"rows": ROWS}, "resolved": RESOLVED,
               ("named_scope_ms", "roc.opt.embed"): 8.0}
    scratch.update(over.pop("scratch", {}))
    base = dict(cell=_cell(), peaks=PEAKS, rehearsal=False,
                trainer=SimpleNamespace(compute=jnp.bfloat16),
                trace=object(), trace_epochs=2, scratch=scratch)
    base.update(over)
    return SimpleNamespace(**base)


def test_rel_agg_roofline_reads_the_widest_ops_forward_row():
    rel = _reader("_relations")
    least_ms = rel.relation_aggregation_bytes(
        42_222_014, 128, 2, 4_547_170) / 819e9 * 1e3
    got = _reader("rel_agg_roofline").read(_run())
    # op 2 gathers 128 wide (op 8: 64): its forward row, 500 ms
    assert got == pytest.approx(100 * least_ms / 500.0)
    assert 0 < got < 105
    # transform_first sums into the vertices, not the stack
    tf = json.loads(json.dumps(RESOLVED))
    tf["rel_layers"][0]["rel_order"] = "transform_first"
    least_tf = rel.relation_aggregation_bytes(
        42_222_014, 128, 2, sum(MAG)) / 819e9 * 1e3
    assert _reader("rel_agg_roofline").read(
        _run(scratch={"resolved": tf})) == pytest.approx(
        100 * least_tf / 500.0)


def test_opt_embed_readers():
    assert _reader("opt_embed_ms").read(_run()) == 8.0
    least_ms = 154_029_312 * 30 / 819e9 * 1e3
    assert _reader("opt_embed_roofline").read(_run()) == pytest.approx(
        100 * least_ms / 8.0)
    assert 0 < _reader("opt_embed_roofline").read(_run()) < 105


@pytest.mark.parametrize("name", METRICS)
def test_readers_find_nothing_in_a_program_without_their_sources(name):
    """A parent commit or a model with no relation: no ``rel_layers``
    in the manifest, no instruction scopes, no trace.  Nothing
    raises."""
    read = _reader(name).read
    bare = SimpleNamespace(
        cell=_cell(), peaks=PEAKS, rehearsal=False,
        trainer=SimpleNamespace(), trace=None, trace_epochs=0,
        scratch={"resolved": {"aggr_impl": "sectioned"}})
    assert read(bare) is None
    assert read(_run(scratch={
        "step_scopes": None, "resolved": None,
        ("named_scope_ms", "roc.opt.embed"): None})) is None


def test_probe_states_what_must_pass():
    probe = _cell().module("probes", "rgcn_precision")
    assert probe.MUST_PASS == {"as_configured": True,
                               "relation_mean_bf16": False}


# -------------------------------------------------------- the substrate

def test_substrate_draws_the_typed_graph_it_is_asked_for():
    sub = _cell().module("substrates", "typed_relations")
    nt = [300, 450, 20, 60]
    rel = [[1, 2, 400], [1, 0, 2800], [0, 0, 2200], [0, 3, 3000]]
    V = sum(nt)
    E = 2 * sum(r[2] for r in rel) + V
    top = sub.make_topology(V, E, 7, 5, node_types=nt, relations=rel)
    row_ptr, col = top["row_ptr"], top["col_idx"]
    assert col.shape[0] == E and row_ptr[-1] == E
    dst = np.repeat(np.arange(V), np.diff(row_ptr))
    # symmetric, every self edge, no edge stored twice
    fwd, bwd = dst * V + col, col.astype(np.int64) * V + dst
    assert np.array_equal(np.sort(fwd), np.sort(bwd))
    assert np.unique(fwd).shape[0] == E and (dst == col).sum() == V
    off = np.concatenate([[0], np.cumsum(nt)])
    kind = np.searchsorted(off, np.arange(V), side="right") - 1
    for s, d, n in rel:
        sel = (kind[col] == s) & (kind[dst] == d) & (col != dst)
        assert sel.sum() == (2 * n if s == d else n)
    assert (top["labels"][nt[0]:] == 0).all()
    # lognormal endpoints: a hub well above the mean degree
    deg = np.bincount(dst[(kind[dst] == 3)] - off[3])
    assert deg.max() > 3 * deg.mean()
    same = sub.make_topology(V, E, 7, 5, node_types=nt, relations=rel)
    assert np.array_equal(same["col_idx"], col)
    got = sub.make_features(top["labels"], 8, 7, 3000000001,
                            node_types=nt, relations=rel)
    assert not got["features"][nt[0]:].any()
    assert got["features"][:nt[0]].any()
    assert (got["mask"][nt[0]:] == 0).all()
    assert set(np.unique(got["mask"][:nt[0]])) == {1, 2, 3}
    with pytest.raises(ValueError):
        sub.make_topology(V, E + 1, 7, 5, node_types=nt, relations=rel)


# ------------------------------------------------------------ the cell

def test_cell_is_found_by_name_with_its_files():
    cell = cells.load_cell(os.path.join(ROOT, "BENCHMARK.json"), CELL)
    assert cell.chips == 1 and cell.traffic["name"] == "fullgraph-typed"
    cfg = cell.config
    assert cfg["reference"] == "rgcn" and cfg["reduced"] == ["epochs"]
    assert cfg["model"] == {"family": "rgcn", "layers": [128, 64, 349],
                            "node_types": MAG, "embed_types": [1, 2, 3]}
    assert len(cfg["source"]) <= 200 and "mag/rgcn.py" in cfg["source"]
    sub = cell.traffic["substrate"]
    assert sub["name"] == "typed_relations" and sub["graph_seed"] == 22
    assert sub["params"]["node_types"] == MAG
    edges = sum(e for _, _, e in sub["params"]["relations"])
    assert edges == 21_111_007
    assert cfg["graph"] == {"num_nodes": sum(MAG),
                            "num_edges": 2 * edges + sum(MAG),
                            "in_dim": 128, "num_classes": 349}
    assert ",".join(map(str, MAG)) in cfg["cli"]
    assert "--impl" not in cfg["cli"] and "--remat" not in cfg["cli"]
    # the leaderboard row's parameter count, to the unit
    emb = sum(MAG[1:]) * 128
    l1 = 7 * 128 * 64 + 4 * (128 * 64 + 64)
    l2 = 7 * 64 * 349 + 4 * (64 * 349 + 349)
    assert emb + l1 + l2 == 154_366_772 == cfg["parameters"]["published"] \
        == cfg["parameters"]["here"]
    assert {"substrate", "num_edges", "reciprocal_citations", "split",
            "scalars_recalled_offline", "dtype", "predict"} <= \
        set(cfg["assumed"])
    assert os.path.isfile(cell.find("references", "rgcn", ".py"))
    assert os.path.isfile(cell.find("probes", "rgcn_precision", ".py"))
    mine = [m for m in cell.benchmark["per_layer"] if m["name"] in METRICS]
    assert [m["name"] for m in mine] == list(METRICS)
    assert cell.benchmark["per_layer"][-3:] == mine
    assert all(m["workloads"] == [CELL] and m["moves"] == "epoch_ms"
               and m["source"] == "device_trace" for m in mine)
    assert [m["layer"] for m in mine] == ["aggregation", "model", "model"]
    for m in mine:
        assert os.path.isfile(cell.find("layer_metrics", m["name"], ".py"))
    tol = cell.extras["correct"]
    assert 0 < tol["row_rel_l2_median"] < tol["row_rel_l2_max"] <= 0.05
    assert len(tol["reason"]) > 100


def test_fixture_table_is_the_gcn2_one_plus_this_cell():
    a, b = load(TABLE), load(os.path.join(FIXTURES, "BENCHMARK.gcn2.json"))
    assert [m["name"] for m in a["per_layer"][len(b["per_layer"]):]] == \
        list(METRICS)
    a["per_layer"] = a["per_layer"][:len(b["per_layer"])]
    assert a.pop("configs")[:-1] == b.pop("configs")
    assert a.pop("workloads")[:-1] == b.pop("workloads")
    assert a == b


def test_tiny_cell_end_to_end(work):
    rc, lines, err = run_cell(work, "tiny-rgcn.fullgraph-typed",
                              "--trace", "1", "--probe", "rgcn_precision",
                              benchmark=TABLE)
    assert rc == 0, err[-2000:]
    result = lines[-1]
    assert result["correct"] is True and result["failed"] == 0
    # there, with the timing null; the two that need the chip's peaks
    # are left out of a rehearsal's line
    assert result["metrics"]["opt_embed_ms"]["value"] is None
    assert "rel_agg_roofline" not in result["metrics"]
    assert "opt_embed_roofline" not in result["metrics"]
    for name in ("step_agg_ms", "step_model_ms", "step_unscoped_share",
                 "compiles_in_window"):
        assert name in result["metrics"]
    plan = next(ln for ln in lines if "plan" in ln)["plan"]
    assert plan["aggr_impl"] == "flat_sum"
    assert plan["node_types"] == [300, 450, 20, 60]
    assert [(r["src"], r["dst"]) for r in plan["relations"]] == [
        (0, 0), (0, 1), (0, 3), (1, 0), (1, 2), (2, 1), (3, 0)]
    assert sum(r["edges"] for r in plan["relations"]) == 16800 == \
        plan["relation_edges"]
    assert [l["rel_order"] for l in plan["rel_layers"]] == \
        ["gather_first"] * 2
    assert plan["embedding_rows"] == 530
    assert plan["memory_plan"]["components"]["params_opt"] == 20 * (
        530 * 32 + 7 * 32 * 16 + 4 * 33 * 16 + 7 * 16 * 7 + 4 * 17 * 7)
    (scopes,) = [ln["step_scopes"] for ln in lines if "step_scopes" in ln]
    assert {(c, w) for c, i, w, _, _ in scopes["rows"] if c == "agg"} == \
        {("agg", "fwd"), ("agg", "bwd")}
    check = next(ln for ln in lines if "check" in ln)["check"]
    assert check["rows"] == 830 and check["row_rel_l2_max"] < 1e-4
    probe = next(ln for ln in lines if "probe" in ln)["probe"]
    assert set(probe["variants"]) == {"as_configured",
                                      "relation_mean_bf16"}
    # the fixture's tolerances are float32's: both bfloat16 variants
    # fail them, the float32 program does not; the means accumulated in
    # bfloat16 lie further out than bfloat16 storage alone
    assert not any(v["passes"] for v in probe["variants"].values())
    assert probe["as_the_program"]["row_rel_l2_max"] < 1e-4
    v = probe["variants"]
    assert v["relation_mean_bf16"]["row_rel_l2_max"] > \
        v["as_configured"]["row_rel_l2_max"]
    # an old cell of the same table reads none of the three
    rc, lines, err = run_cell(work, "tiny-gcn.fullgraph", "--trace", "1",
                              benchmark=TABLE)
    assert rc == 0, err[-2000:]
    assert not set(METRICS) & set(lines[-1]["metrics"])
