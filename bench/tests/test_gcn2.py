"""The GCNII* configuration's part of the benchmark: the plain reference
against a dense-matrix evaluation of the equations on a 50-vertex graph,
the three readers this configuration's cell brings (``plan_miss_gib``,
``agg_step_roofline``, ``step_elementwise_ms``) on hand-built runs —
and on a run of a program that lacks what they read, where each must
return nothing — the cell ``gcn2-arxiv.fullgraph`` as
``harness/cells.py`` finds it, and the tiny cell of the fixture table
end to end under ``--rehearsal`` with the precision probe."""

import json
import math
import os
from types import SimpleNamespace

import numpy as np
import pytest

from conftest import BENCH, FIXTURES, ROOT, run_cell

from harness import cells

TABLE = os.path.join(FIXTURES, "BENCHMARK.gcn2.json")
METRICS = ("plan_miss_gib", "agg_step_roofline", "step_elementwise_ms")
CELL = "gcn2-arxiv.fullgraph"


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


@pytest.mark.parametrize("variant", ["gcn2star", "gcn2"])
def test_reference_is_the_equations_on_a_dense_matrix(variant):
    import jax
    import jax.numpy as jnp
    import reference
    from references import gcn2
    n, f, h, c, depth = 50, 6, 8, 4, 5
    alpha, lam = 0.5, 1.0
    a, row_ptr, col = _dense_graph(n)
    rng = np.random.default_rng(1)
    x = rng.standard_normal((n, f))
    n_w = (2 if variant == "gcn2star" else 1) * depth + 2
    dims = [(f, h)] + [(h, h)] * (n_w - 2) + [(h, c)]
    params = {f"linear_{k}": rng.standard_normal(d) / math.sqrt(d[0])
              for k, d in enumerate(dims)}
    # the equations, float64, with P = D^-1/2 A D^-1/2 as a matrix
    d = 1.0 / np.sqrt(a.sum(axis=1))
    p = d[:, None] * a * d[None, :]
    h0 = np.maximum(x @ params["linear_0"], 0.0)
    t, k = h0, 1
    for l in range(1, depth + 1):
        beta = math.log(lam / l + 1.0)
        pt = p @ t
        m = (1 - alpha) * pt + alpha * h0
        if variant == "gcn2star":
            w = pt @ params[f"linear_{k}"] + h0 @ params[f"linear_{k + 1}"]
            k += 2
        else:
            w = m @ params[f"linear_{k}"]
            k += 1
        t = np.maximum((1 - beta) * m + beta * w, 0.0)
    want = t @ params[f"linear_{k}"]
    model = {"family": "gcn2", "layers": [f] + [h] * depth + [c],
             "variant": variant, "alpha": alpha, "lam": lam}
    got = reference.run(gcn2.forward, params, x.astype(np.float32),
                        np.zeros(n, np.int32), np.ones(n, np.int32),
                        row_ptr, col, model, on=jax.devices("cpu")[0])
    np.testing.assert_allclose(got["logits"], want, rtol=2e-4, atol=2e-5)
    assert np.isfinite(got["loss"])
    # and the training twin differentiates it
    g = reference.Graph.from_csr(row_ptr, col, widest=h)
    g = reference.Graph(*(jnp.asarray(v) for v in g.arrays()), n)
    p32 = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    loss, grads = gcn2.loss_and_grads(
        p32, jnp.asarray(x, jnp.float32), jnp.zeros(n, jnp.int32),
        jnp.ones(n, jnp.int32), g, model)
    assert np.isfinite(float(loss)) and sorted(grads) == sorted(params)
    assert all(np.abs(np.asarray(v)).max() > 0 for v in grads.values())


# --------------------------------------------------------- the readers

def _cell():
    return cells.load_cell(TABLE, "tiny-gcn2.fullgraph")


def _op(kind, dim):
    return SimpleNamespace(kind=kind, dim=dim)


OPS = [_op("input", 128), _op("dropout", 128), _op("linear", 256),
       _op("fused_aggregate", 256), _op("lerp", 256), _op("linear", 256),
       _op("activation", 256), _op("fused_aggregate", 256),
       _op("add", 256), _op("linear", 40), _op("fused_aggregate", 40)]
ROWS = [["agg", 3, "fwd", 60.0, 1], ["agg", 3, "bwd", 61.0, 1],
        ["agg", 7, "fwd", 80.0, 1], ["agg", 7, "bwd", 81.0, 1],
        ["agg", 10, "fwd", 5.0, 1],
        ["dense", 1, "fwd", 1.0, 1], ["dense", 2, "fwd", 7.0, 1],
        ["dense", 4, "fwd", 2.0, 1], ["dense", 4, "bwd", 2.5, 1],
        ["dense", 4, "recompute", 1.5, 1], ["dense", 5, "bwd", 9.0, 1],
        ["dense", 6, "fwd", 3.0, 1], ["dense", 8, "bwd", 0.5, 1],
        ["loss", None, "fwd", 4.0, 1], ["opt", None, "fwd", 6.0, 1]]
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


def _run(**over):
    import jax.numpy as jnp
    base = dict(
        cell=_cell(), peaks=PEAKS, rehearsal=False,
        trainer=SimpleNamespace(model=SimpleNamespace(_ops=OPS),
                                gctx=object(), compute=jnp.bfloat16),
        data=SimpleNamespace(col_idx=np.zeros(2_501_829, np.int8),
                             row_ptr=np.zeros(169_344, np.int8)),
        scratch={"step_scopes": {"rows": ROWS},
                 "resolved": {"memory_plan": {"est_bytes": 3 * 2**30}}},
        memory_peak_bytes=lambda: int(5.5 * 2**30))
    base.update(over)
    return SimpleNamespace(**base)


def _reader(name):
    return _cell().module("layer_metrics", name)


def test_plan_miss_is_the_distance_either_way():
    read = _reader("plan_miss_gib").read
    assert read(_run()) == pytest.approx(2.5)
    over = _run(scratch={"resolved": {"memory_plan": {
        "est_bytes": 8 * 2**30}}})
    assert read(over) == pytest.approx(2.5)


def test_agg_step_roofline_is_the_median_widest_forward_row():
    import roofline
    read = _reader("agg_step_roofline").read
    # 256 wide: ops 3 and 7, forward rows 60 and 80 -> median 70; the
    # 40-wide op 10 and every backward row are left out
    nbytes = roofline.aggregation_bytes(2_501_829, 169_343, 256, 2)
    assert nbytes == 2_501_829 * 516 + 2 * 169_343 * 512
    least_ms = nbytes / 819e9 * 1e3
    assert read(_run()) == pytest.approx(100 * least_ms / 70.0)
    assert 0 < read(_run()) < 105


def test_step_elementwise_is_every_dense_row_but_the_linears():
    read = _reader("step_elementwise_ms").read
    # ops 1, 4, 6, 8 (dropout, lerp, activation, add), all directions;
    # linears 2 and 5, loss and opt are left out
    assert read(_run()) == pytest.approx(1.0 + 2.0 + 2.5 + 1.5 + 3.0 + 0.5)


@pytest.mark.parametrize("name", METRICS)
def test_readers_find_nothing_in_a_program_without_their_sources(name):
    """A parent commit: no ``memory_plan`` in the manifest, no
    instruction scopes (``_step_scopes.measure`` gives None), a trainer
    without a model or a context.  Nothing raises."""
    read = _reader(name).read
    bare = _run(trainer=SimpleNamespace(), trace=None, trace_epochs=0,
                scratch={"resolved": {"aggr_impl": "sectioned"}})
    assert read(bare) is None
    assert read(_run(scratch={"step_scopes": None, "resolved": None},
                     memory_peak_bytes=lambda: None)) is None


def test_probe_states_what_must_pass():
    probe = _cell().module("probes", "gcn2_precision")
    assert probe.MUST_PASS == {"as_configured": True,
                               "neighbour_sum_bf16": False,
                               "master_weights_bf16": True}


# ------------------------------------------------------------ the cell

def test_cell_is_found_by_name_with_its_files():
    cell = cells.load_cell(os.path.join(ROOT, "BENCHMARK.json"), CELL)
    assert cell.chips == 1 and cell.traffic["name"] == "fullgraph"
    cfg = cell.config
    assert cfg["reference"] == "gcn2" and cfg["reduced"] == ["epochs"]
    assert cfg["model"]["layers"] == [128] + [256] * 16 + [40]
    assert cfg["model"]["variant"] == "gcn2star"
    assert (cfg["model"]["alpha"], cfg["model"]["lam"]) == (0.5, 1.0)
    assert cfg["graph"] == {"num_nodes": 169343, "num_edges": 2501829,
                            "in_dim": 128, "num_classes": 40}
    assert "--star" in cfg["cli"] and "--remat" not in cfg["cli"]
    assert cfg["parameters"]["here"] + 16 * 512 + 256 + 40 == 2_148_648
    assert {"substrate", "batch_norm", "biases", "scalars_recalled_offline",
            "absorbed_constants", "dtype"} <= set(cfg["assumed"])
    assert cell.extras["trace"] == {"epochs": 2}
    assert os.path.isfile(cell.find("references", "gcn2", ".py"))
    assert os.path.isfile(cell.find("probes", "gcn2_precision", ".py"))
    mine = [m for m in cell.benchmark["per_layer"] if m["name"] in METRICS]
    assert [m["name"] for m in mine] == list(METRICS)
    assert all(m["workloads"] == [CELL] and m["moves"] == "epoch_ms"
               for m in mine)
    assert [m["layer"] for m in mine] == ["entry", "aggregation", "model"]
    assert {m["name"] for m in cell.metrics("per_layer")} >= set(METRICS)
    for m in mine:
        assert os.path.isfile(cell.find("layer_metrics", m["name"], ".py"))
    tol = cell.extras["correct"]
    assert 0 < tol["row_rel_l2_median"] < tol["row_rel_l2_max"] <= 0.05
    assert len(tol["reason"]) > 100


def test_fixture_table_is_the_attention_one_plus_this_cell():
    a, b = load(TABLE), load(os.path.join(FIXTURES,
                                          "BENCHMARK.attention.json"))
    assert [m["name"] for m in a["per_layer"][len(b["per_layer"]):]] == \
        list(METRICS)
    a["per_layer"] = a["per_layer"][:len(b["per_layer"])]
    assert a.pop("configs")[:-1] == b.pop("configs")
    assert a.pop("workloads")[:-1] == b.pop("workloads")
    assert a == b


def test_tiny_cell_end_to_end(work):
    rc, lines, err = run_cell(work, "tiny-gcn2.fullgraph", "--trace", "1",
                              "--probe", "gcn2_precision", benchmark=TABLE)
    assert rc == 0, err[-2000:]
    result = lines[-1]
    assert result["correct"] is True and result["failed"] == 0
    # there, with the timing null; the two that need the chip's peaks
    # or its memory are left out of a rehearsal's line
    assert result["metrics"]["step_elementwise_ms"]["value"] is None
    assert "agg_step_roofline" not in result["metrics"]
    assert "plan_miss_gib" not in result["metrics"]
    plan = next(ln for ln in lines if "plan" in ln)["plan"]
    mem = plan["memory_plan"]
    assert plan["remat"] is False and mem["remat"] is False
    assert (mem["aggregating_ops"], mem["linear_ops"]) == (4, 10)
    assert mem["est_bytes"] == sum(mem["components"].values())
    assert set(mem["components"]) == {"params_opt", "features", "tables",
                                      "activations", "transient"}
    # a layer keeps its dropout mask, P H (the first linear's input; the
    # second reads H_0, kept once) and its ReLU's output: three arrays
    assert [k for _op, k, _n, _row in mem["saved"]].count("linear") == 7
    assert sum(n for _op, _k, n, _row in mem["saved"]) \
        == mem["saved_arrays"]
    (scopes,) = [ln["step_scopes"] for ln in lines if "step_scopes" in ln]
    assert {way for _, _, way, _, _ in scopes["rows"]} == {"fwd", "bwd"}
    probe = next(ln for ln in lines if "probe" in ln)["probe"]
    assert set(probe["variants"]) == {"as_configured", "neighbour_sum_bf16",
                                      "master_weights_bf16"}
    # the fixture's tolerances are float32's: every bfloat16 variant
    # fails them, the float32 program does not; rounding the parameters
    # first changes nothing the forward does not do itself
    assert not any(v["passes"] for v in probe["variants"].values())
    assert probe["as_the_program"]["row_rel_l2_max"] < 1e-4
    v = probe["variants"]
    assert v["master_weights_bf16"]["row_rel_l2_max"] == \
        v["as_configured"]["row_rel_l2_max"]
    assert v["neighbour_sum_bf16"]["row_rel_l2_max"] > \
        v["as_configured"]["row_rel_l2_max"]
    # an old cell of the same table reads none of the three
    rc, lines, err = run_cell(work, "tiny-gcn.fullgraph", "--trace", "1",
                              benchmark=TABLE)
    assert rc == 0, err[-2000:]
    assert not set(METRICS) & set(lines[-1]["metrics"])
