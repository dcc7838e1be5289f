"""The DeeperGCN configuration's part of the benchmark: the plain
reference against a dense-matrix evaluation of the equations on a
50-vertex graph (inference, and a training step's statistics), the four
readers this configuration's cell brings (``bn_ms``, ``bn_roofline``,
``softagg_ms``, ``softagg_roofline``) and their byte models on
hand-built runs — and on a run of a program that lacks what they read,
where each must return nothing — the cell ``deepergcn-arxiv.fullgraph``
as ``harness/cells.py`` finds it, and the tiny cell of the fixture table
end to end under ``--rehearsal`` with the precision probe."""

import json
import os
from types import SimpleNamespace

import numpy as np
import pytest

from conftest import FIXTURES, ROOT, run_cell

from harness import cells

TABLE = os.path.join(FIXTURES, "BENCHMARK.deepergcn.json")
METRICS = ("bn_ms", "bn_roofline", "softagg_ms", "softagg_roofline")
CELL = "deepergcn-arxiv.fullgraph"


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


def _random_params(rng, f, h, c, depth):
    p = {}
    dims = [(f, h)] + [(h, h)] * depth + [(h, c)]
    for k, d in enumerate(dims):
        p[f"linear_{k}"] = rng.standard_normal(d) / np.sqrt(d[0])
        p[f"linear_{k}_b"] = 0.2 * rng.standard_normal(d[1])
    for l in range(depth):
        p[f"bn_{l}_scale"] = 1 + 0.2 * rng.standard_normal(h)
        p[f"bn_{l}_shift"] = 0.2 * rng.standard_normal(h)
        p[f"bn_{l}_mean"] = 0.3 * rng.standard_normal(h)
        p[f"bn_{l}_var"] = rng.uniform(0.5, 2.0, h)
    return p


def _soft_dense(z, a, t):
    """``S(z)`` with the adjacency as a matrix of multiplicities,
    float64: the weights of row ``v`` are a softmax over its stored
    in-edges, per channel."""
    m = np.maximum(z, 0.0) + 1e-7
    out = np.empty_like(z)
    for v in range(a.shape[0]):
        e = a[v][:, None] * np.exp(t * (m - (t * m[a[v] > 0]).max(0) / t))
        out[v] = z[v] + (e * m).sum(0) / e.sum(0)
    return out


def test_reference_is_the_equations_on_a_dense_matrix():
    import jax
    import jax.numpy as jnp
    import reference
    from references import deepergcn
    n, f, h, c, depth, t = 50, 6, 8, 4, 5, 0.1
    a, row_ptr, col = _dense_graph(n)
    rng = np.random.default_rng(1)
    x = rng.standard_normal((n, f))
    p = _random_params(rng, f, h, c, depth)

    def lin(v, k):
        return v @ p[f"linear_{k}"] + p[f"linear_{k}_b"]

    def bn(v, l):
        return (p[f"bn_{l}_scale"] * (v - p[f"bn_{l}_mean"])
                / np.sqrt(p[f"bn_{l}_var"] + 1e-5) + p[f"bn_{l}_shift"])

    hcur = lin(_soft_dense(lin(x, 0), a, t), 1)
    for l in range(1, depth):
        hcur = hcur + lin(_soft_dense(np.maximum(bn(hcur, l - 1), 0), a, t),
                          l + 1)
    want = lin(np.maximum(bn(hcur, depth - 1), 0), depth + 1)
    model = {"family": "deepergcn", "layers": [f] + [h] * depth + [c],
             "t": t}
    got = reference.run(deepergcn.forward, p, x.astype(np.float32),
                        np.zeros(n, np.int32), np.ones(n, np.int32),
                        row_ptr, col, model, on=jax.devices("cpu")[0])
    np.testing.assert_allclose(got["logits"], want, rtol=2e-4, atol=2e-5)
    assert np.isfinite(got["loss"])
    # the training twin: batch statistics (float64 here, two-pass),
    # unit dropout masks, and the statistics the step leaves
    g = reference.Graph.from_csr(row_ptr, col, widest=h)
    g = reference.Graph(*(jnp.asarray(v) for v in g.arrays()), n)
    p32 = {k: jnp.asarray(v, jnp.float32) for k, v in p.items()}
    ones = [jnp.ones((n, h), jnp.float32)] * depth
    labels = jnp.asarray(rng.integers(0, c, n), jnp.int32)
    with jax.default_matmul_precision("highest"):
        loss, grads, moved = deepergcn.loss_and_grads(
            p32, jnp.asarray(x, jnp.float32), labels,
            jnp.ones(n, jnp.int32), g, model, ones)
        _, through, _ = deepergcn.loss_and_grads(
            p32, jnp.asarray(x, jnp.float32), labels,
            jnp.ones(n, jnp.int32), g, model, ones, detach=False)
    h1 = lin(_soft_dense(lin(x, 0), a, t), 1)
    np.testing.assert_allclose(
        np.asarray(moved["bn_0_mean"]),
        0.9 * p["bn_0_mean"] + 0.1 * h1.mean(0), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(moved["bn_0_var"]),
        0.9 * p["bn_0_var"] + 0.1 * h1.var(0) * n / (n - 1), rtol=1e-4)
    assert np.isfinite(float(loss)) and sorted(grads) == sorted(p)
    # the statistics carry no gradient; the detached rule is not
    # autodiff of the forward
    assert not np.any(np.asarray(grads["bn_0_mean"]))
    d = np.abs(np.asarray(grads["linear_0"]) - np.asarray(
        through["linear_0"])).max()
    assert d > 1e-3 * np.abs(np.asarray(grads["linear_0"])).max()


# --------------------------------------------------------- the readers

def _cell():
    return cells.load_cell(TABLE, "tiny-deepergcn.fullgraph")


SOFT = {"ops": [2, 7], "count": 2, "t": 0.1, "width": 128,
        "gather_lanes_fwd": 256, "gather_lanes_bwd": 128,
        "passes_fwd": 1, "passes_bwd": 1, "e_dtype": "bfloat16"}
BN = {"ops": [4], "count": 28, "width": 128, "rows_counted": 169_343}
ROWS = [["agg", 2, "fwd", 30.0, 1], ["agg", 2, "bwd", 18.0, 1],
        ["agg", 7, "fwd", 34.0, 1], ["agg", 7, "bwd", 20.0, 1],
        ["agg", 9, "fwd", 99.0, 1],           # not a softmax aggregation
        ["dense", 4, "fwd", 1.0, 1], ["dense", 4, "bwd", 2.0, 1],
        ["loss", None, "fwd", 4.0, 1], ["opt", None, "fwd", 6.0, 1]]
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


def _run(**over):
    import jax.numpy as jnp
    base = dict(
        cell=_cell(), peaks=PEAKS, rehearsal=False,
        trainer=SimpleNamespace(gctx=object(), compute=jnp.bfloat16),
        data=SimpleNamespace(col_idx=np.zeros(2_501_829, np.int8),
                             row_ptr=np.zeros(169_344, np.int8)),
        scratch={"step_scopes": {"rows": ROWS},
                 ("named_scope_ms", ".batch_norm"): 60.0,
                 "resolved": {"soft_aggregate": SOFT, "batch_norm": BN}})
    base.update(over)
    return SimpleNamespace(**base)


def _reader(name):
    return _cell().module("layer_metrics", name)


def test_byte_models():
    sa = _reader("_softagg")
    # eight passes over [V, 128] bfloat16 an op
    assert sa.batch_norm_bytes(169_343, 128, 2, 28) \
        == 8 * 169_343 * 256 * 28
    V, E = 169_343, 2_501_829
    one = sa.soft_aggregation_bytes(E, V, 128, 256, 1, 2, 2)
    assert one == (E * (512 + 4) + 2 * V * 512
                   + V * (3 * 256 + 2 * 512 + 256 + 512))
    # two 128-lane gathers read the index twice
    two = sa.soft_aggregation_bytes(E, V, 128, 128, 2, 2, 2)
    assert two - one == E * 4
    # a float32 table doubles what is gathered, not what z costs
    f32 = sa.soft_aggregation_bytes(E, V, 128, 256, 1, 2, 4)
    assert f32 - one == E * 512 + 2 * V * 512 + V * 2 * 512


def test_bn_ms_and_its_roofline():
    assert _reader("bn_ms").read(_run()) == 60.0
    least_ms = 8 * 169_343 * 256 * 28 / 819e9 * 1e3
    got = _reader("bn_roofline").read(_run())
    assert got == pytest.approx(100 * least_ms / 60.0)
    assert 0 < got < 100


def test_softagg_ms_is_every_row_of_the_softmax_aggregations():
    # ops 2 and 7, both directions; op 9 aggregates otherwise
    assert _reader("softagg_ms").read(_run()) == pytest.approx(102.0)


def test_softagg_roofline_is_the_median_forward_row():
    sa = _reader("_softagg")
    least_ms = sa.soft_aggregation_bytes(
        2_501_829, 169_343, 128, 256, 1, 2, 2) / 819e9 * 1e3
    got = _reader("softagg_roofline").read(_run())
    assert got == pytest.approx(100 * least_ms / 32.0)
    assert 0 < got < 100


@pytest.mark.parametrize("name", METRICS)
def test_readers_find_nothing_in_a_program_without_their_sources(name):
    """A parent commit: no ``batch_norm`` / ``soft_aggregate`` in the
    manifest, no such scope in the program's text, no instruction
    scopes at all.  Nothing raises."""
    read = _reader(name).read
    bare = _run(trainer=SimpleNamespace(), trace=None, trace_epochs=0,
                scratch={"resolved": {"aggr_impl": "sectioned"}})
    assert read(bare) is None
    assert read(_run(scratch={"step_scopes": None, "resolved": None,
                              ("named_scope_ms", ".batch_norm"): None}
                     )) is None
    # another model's program, traced: scopes but none of these
    other = _run(scratch={"step_scopes": {"rows": ROWS},
                          ("named_scope_ms", ".batch_norm"): None,
                          "resolved": {"aggr_impl": "sectioned"}})
    assert read(other) is None


def test_probe_states_what_must_pass():
    probe = _cell().module("probes", "deepergcn_precision")
    assert probe.MUST_PASS == {"as_configured": True,
                               "neighbour_sum_bf16": False,
                               "batch_norm_bf16": True}


# ------------------------------------------------------------ the cell

def test_cell_is_found_by_name_with_its_files():
    cell = cells.load_cell(os.path.join(ROOT, "BENCHMARK.json"), CELL)
    assert cell.chips == 1 and cell.traffic["name"] == "fullgraph"
    cfg = cell.config
    assert cfg["reference"] == "deepergcn"
    assert cfg["reduced"] == ["epochs"]
    assert cfg["model"]["layers"] == [128] + [128] * 28 + [40]
    assert cfg["model"]["t"] == 0.1
    assert cfg["graph"] == {"num_nodes": 169343, "num_edges": 2501829,
                            "in_dim": 128, "num_classes": 40}
    cli = cfg["cli"]
    assert cli[cli.index("--model") + 1] == "deepergcn"
    assert cli[cli.index("-layers") + 1] == "-".join(
        map(str, cfg["model"]["layers"]))
    assert cli[cli.index("--t") + 1] == "0.1"
    assert "--remat" not in cli and "--impl" not in cli
    assert cfg["parameters"]["published"] == cfg["parameters"]["here"] \
        == 491_176 == 16_512 + 28 * 16_512 + 28 * 256 + 5_160
    assert {"substrate", "num_edges", "initialisation",
            "scalars_recalled_offline", "dtype"} <= set(cfg["assumed"])
    assert len(cfg["source"]) <= 200
    assert cell.extras["trace"] == {"epochs": 2}
    assert os.path.isfile(cell.find("references", "deepergcn", ".py"))
    assert os.path.isfile(cell.find("probes", "deepergcn_precision",
                                    ".py"))
    mine = [m for m in cell.benchmark["per_layer"] if m["name"] in METRICS]
    assert [m["name"] for m in mine] == list(METRICS)
    assert all(m["workloads"] == [CELL] and m["moves"] == "epoch_ms"
               and m["source"] == "device_trace" for m in mine)
    assert [m["layer"] for m in mine] == ["model", "model", "aggregation",
                                          "aggregation"]
    assert {m["name"] for m in cell.metrics("per_layer")} >= set(METRICS)
    for m in mine:
        assert os.path.isfile(cell.find("layer_metrics", m["name"], ".py"))
    tol = cell.extras["correct"]
    assert 0 < tol["row_rel_l2_median"] < tol["row_rel_l2_max"] <= 0.05
    assert len(tol["reason"]) > 100
    # the cell is the only new one, and it is on one chip
    names = [w["name"] for w in cell.benchmark["workloads"]]
    assert names.count(CELL) == 1
    assert sum(w["chips"] == 4 for w in cell.benchmark["workloads"]) == 1


def test_fixture_table_is_the_gcn2_one_plus_this_cell():
    a, b = load(TABLE), load(os.path.join(FIXTURES, "BENCHMARK.gcn2.json"))
    assert [m["name"] for m in a["per_layer"][len(b["per_layer"]):]] == \
        list(METRICS)
    a["per_layer"] = a["per_layer"][:len(b["per_layer"])]
    assert a.pop("configs")[:-1] == b.pop("configs")
    assert a.pop("workloads")[:-1] == b.pop("workloads")
    assert a == b


def test_tiny_cell_end_to_end(work):
    rc, lines, err = run_cell(work, "tiny-deepergcn.fullgraph",
                              "--trace", "1", "--probe",
                              "deepergcn_precision", benchmark=TABLE)
    assert rc == 0, err[-2000:]
    result = lines[-1]
    assert result["correct"] is True and result["failed"] == 0
    # there, with the timing null; the two that need the chip's peaks
    # are left out of a rehearsal's line
    assert result["metrics"]["bn_ms"]["value"] is None
    assert result["metrics"]["softagg_ms"]["value"] is None
    assert "bn_roofline" not in result["metrics"]
    assert "softagg_roofline" not in result["metrics"]
    plan = next(ln for ln in lines if "plan" in ln)["plan"]
    assert plan["batch_norm"]["count"] == 4
    assert plan["batch_norm"]["rows_counted"] == 2048
    assert plan["soft_aggregate"]["count"] == 4
    assert plan["soft_aggregate"]["t"] == 0.1
    mem = plan["memory_plan"]
    assert plan["remat"] is False and mem["remat"] is False
    assert (mem["aggregating_ops"], mem["linear_ops"]) == (4, 6)
    kinds = [k for _op, k, _n, _row in mem["saved"]]
    assert kinds.count("batch_norm") == 4
    assert kinds.count("soft_aggregate") == 4
    (scopes,) = [ln["step_scopes"] for ln in lines if "step_scopes" in ln]
    assert {way for _, _, way, _, _ in scopes["rows"]} == {"fwd", "bwd"}
    soft = set(plan["soft_aggregate"]["ops"])
    assert soft <= {i for cls, i, _, _, _ in scopes["rows"]
                    if cls == "agg"}
    check = next(ln for ln in lines if "check" in ln)["check"]
    assert check["row_rel_l2_max"] < 1e-4
    probe = next(ln for ln in lines if "probe" in ln)["probe"]
    assert set(probe["variants"]) == {"as_configured",
                                      "neighbour_sum_bf16",
                                      "batch_norm_bf16"}
    # the fixture's tolerances are float32's: every bfloat16 variant
    # fails them, the float32 program does not; each lower precision
    # reads worse than the configured one
    assert not any(v["passes"] for v in probe["variants"].values())
    assert probe["as_the_program"]["row_rel_l2_max"] < 1e-4
    v = probe["variants"]
    for low in ("neighbour_sum_bf16", "batch_norm_bf16"):
        assert v[low]["row_rel_l2_median"] > \
            v["as_configured"]["row_rel_l2_median"]
    # an old cell of the same table reads none of the four
    rc, lines, err = run_cell(work, "tiny-gcn.fullgraph", "--trace", "1",
                              benchmark=TABLE)
    assert rc == 0, err[-2000:]
    assert not set(METRICS) & set(lines[-1]["metrics"])
