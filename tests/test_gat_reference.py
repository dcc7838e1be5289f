"""The system's GAT with the linear skip (``models/gat.py`` through
``Model.apply`` and both attention layouts of ``ops/attention.py``)
against the benchmark's plain reference (``bench/references/gat.py``)
on seeded random weights, on the CPU.

float32: logits, loss and every parameter's gradient, one case each,
for both layouts (``ell``, ``attn_flat8``) on a graph with a hub row
and an isolated row, 3 heads of a width (10) that is no multiple of 8,
with and without padding in the layout's scan (bucket segments with
padding rows / a padding chunk of sub-rows against tables that divide
exactly).  ``--dtype mixed``: inside the tolerances of the cell
``gat-arxiv.fullgraph``.  And the phase scopes of the compiled step.

The symmetric graph's gradient rule (``ops/attention.py
gat_ell_backward``: the backward as a second pass over the forward's
own tables) against autodiff, which the cases above hold to the
reference: the same graph made symmetric, with self edges and two
pairs stored twice; float32 and mixed; one partition and two; the
lowered step's shape; the ``attention_backward`` entries of the run
manifest.
"""

import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from roc_tpu.core.ell import flat_sum_from_graph
from roc_tpu.core.graph import Dataset, Graph, MASK_TRAIN
from roc_tpu.models.gat import build_gat
from roc_tpu.obs.scopes import (AGG, ATTN_PHASES, parse_op_name,
                                parse_op_phase)
from roc_tpu.train.trainer import (TrainConfig, Trainer, cast_floats,
                                   make_graph_context)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")

V, HEADS, HEAD_W, CLASSES = 72, 3, 10, 5
LAYERS = [12, HEADS * HEAD_W, HEADS * HEAD_W, CLASSES]
MODEL = {"family": "gat", "layers": LAYERS, "heads": HEADS,
         "skip": "linear", "activation": "relu"}
HUB, ISOLATED = 0, V - 1
LAYOUTS = ("ell", "attn_flat8")
PADDING = ("padded", "exact")
SEG_ROWS = 16                   # sub-rows a flat8 chunk in these tests
PARAMS = ([f"linear_{k}" for k in range(6)]
          + [f"gat_{i}_{end}" for i in range(3) for end in ("src", "dst")])


@pytest.fixture(scope="module")
def ref():
    """The benchmark's reference modules, imported as the benchmark
    imports them (``bench/`` on the path)."""
    sys.path.insert(0, BENCH)
    try:
        import reference
        from references import gat
    finally:
        sys.path.remove(BENCH)
    return reference, gat


def _graph():
    """Every row but the last holds its self edge and 1-6 others; row
    ``HUB`` holds all of 1..60; row ``ISOLATED`` holds nothing and is
    nobody's source.  Directed.  Edges are added to row 1 until the
    width-8 sub-rows fill whole chunks of ``SEG_ROWS``."""
    rng = np.random.default_rng(3)
    rows = [np.unique(np.r_[v, rng.integers(0, V - 1,
                                            rng.integers(1, 7))])
            for v in range(V - 1)] + [np.zeros(0, np.int64)]
    rows[HUB] = np.arange(0, 61)

    def sub_rows():
        return sum(-(-len(r) // 8) for r in rows)

    spare = [u for u in range(V - 1) if u not in set(rows[1])]
    while sub_rows() % SEG_ROWS:
        rows[1] = np.append(rows[1], spare.pop())
    row_ptr = np.concatenate([[0], np.cumsum([len(r) for r in rows])])
    col = np.concatenate([np.sort(r) for r in rows]).astype(np.int32)
    return row_ptr.astype(np.int64), col


@pytest.fixture(scope="module")
def data():
    row_ptr, col = _graph()
    rng = np.random.default_rng(5)
    feats = rng.standard_normal((V, LAYERS[0])).astype(np.float32)
    labels = rng.integers(0, CLASSES, V).astype(np.int32)
    mask = np.where(rng.random(V) < 0.6, MASK_TRAIN, 0).astype(np.int32)
    mask[[HUB, ISOLATED]] = MASK_TRAIN
    ds = Dataset(Graph(row_ptr, col), feats, labels, mask,
                 num_classes=CLASSES)
    model = build_gat(LAYERS, dropout_rate=0.75, heads=HEADS, skip=True,
                      activation="relu", input_dropout=0.1)
    params = model.init_params(jax.random.PRNGKey(7))
    # attention vectors large enough that the softmax is far from uniform
    params = {k: (3.0 * v if k.startswith("gat_") else v)
              for k, v in params.items()}
    return ds, model, params


def _symmetric_graph():
    """``_graph`` with every edge stored both ways — the hub and the 60
    rows it holds now hold each other — every self edge kept, row
    ``ISOLATED`` still empty, and two pairs stored twice."""
    row_ptr, col = _graph()
    count = np.zeros((V, V), np.int64)
    count[np.repeat(np.arange(V), np.diff(row_ptr)), col] = 1
    count = np.maximum(count, count.T)
    for a, b in ((5, 9), (HUB, 17)):
        count[a, b] = count[b, a] = 2
    col = np.concatenate([np.repeat(np.arange(V), count[v])
                          for v in range(V)]).astype(np.int32)
    row_ptr = np.concatenate([[0], np.cumsum(count.sum(axis=1))])
    return row_ptr.astype(np.int64), col


@pytest.fixture(scope="module")
def sym_data(data):
    """``data``'s model and parameters on the symmetric graph."""
    ds, model, params = data
    g = Graph(*_symmetric_graph())
    assert g.is_symmetric() and not ds.graph.is_symmetric()
    assert g.num_edges > np.unique(
        g.edge_dst().astype(np.int64) * V + g.col_idx).size  # repeats
    return (Dataset(g, ds.features, ds.labels, ds.mask,
                    num_classes=CLASSES), model, params)


def _gctx(ds, layout, padding, symmetric=False):
    """The layout's tables as the trainer builds them; ``padded`` /
    ``exact`` choose whether its scan meets padding."""
    gctx = make_graph_context(ds, layout, symmetric=symmetric)
    if layout == "attn_flat8":
        g = ds.graph
        seg = SEG_ROWS if padding == "exact" else SEG_ROWS - 3
        sect = flat_sum_from_graph(g.row_ptr, g.col_idx, g.num_nodes,
                                   seg_rows=seg)
        idx, dst = sect.idx[0], sect.sub_dst[0]
        assert idx.shape[0] > 2
        assert (dst == g.num_nodes).any() == (padding == "padded")
        gctx.flat8_idx, gctx.flat8_dst = jnp.asarray(idx), jnp.asarray(dst)
    return gctx


def _budget(layout, padding):
    """ELL: ``padded`` segments every bucket into scans whose last
    segment holds padding rows; ``exact`` takes each bucket whole."""
    return 700 if (layout, padding) == ("ell", "padded") else 1 << 24


_cache = {}
# every entry point of the bucketed layout that takes the budget
ELL_ENTRIES = ("gat_aggregate_ell", "gat_ell_forward", "gat_ell_backward")


def _set_budget(monkeypatch, budget):
    from roc_tpu.ops import attention
    for name in ELL_ENTRIES:
        monkeypatch.setattr(attention, name, functools.partial(
            getattr(attention, name), budget_elems=budget))


def _system(data, layout, padding, monkeypatch, dtype=jnp.float32,
            symmetric=False):
    ds, model, params = data
    key = (id(ds), layout, padding, jnp.dtype(dtype).name, symmetric)
    if key in _cache:
        return _cache[key]
    gctx = _gctx(ds, layout, padding, symmetric)
    _set_budget(monkeypatch, _budget(layout, padding))
    feats = jnp.asarray(ds.features, dtype)
    labels, mask = jnp.asarray(ds.labels), jnp.asarray(ds.mask)

    def objective(p):
        loss, logits = model.loss_fn(cast_floats(p, dtype), feats, labels,
                                     mask, gctx, key=None, train=False)
        return loss, logits

    (loss, logits), grads = jax.value_and_grad(objective,
                                               has_aux=True)(params)
    _cache[key] = (np.asarray(logits, np.float32), float(loss),
                   {k: np.asarray(v) for k, v in grads.items()})
    return _cache[key]


@pytest.fixture(scope="module")
def plain(ref, data):
    """Reference logits, loss and gradients, float32."""
    reference, gat = ref
    ds, _, params = data
    g = reference.Graph.from_csr(ds.graph.row_ptr, ds.graph.col_idx,
                                 widest=1 << 16)   # chunks of 1,024 edges
    assert g.tail_src.shape[0]
    whole = (g.tail_src.shape[0] // 100) * 100     # 100-edge chunks + tail
    g = reference.Graph(
        *(jnp.asarray(a) for a in (
            g.tail_src[:whole].reshape(-1, 100),
            g.tail_dst[:whole].reshape(-1, 100),
            g.tail_src[whole:], g.tail_dst[whole:], g.degree)), V)
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(ds.features)
        logits = gat.forward(params, x, g, MODEL)
        loss, grads = gat.loss_and_grads(
            params, x, jnp.asarray(ds.labels), jnp.asarray(ds.mask), g,
            MODEL)
    return (np.asarray(logits), float(loss),
            {k: np.asarray(v) for k, v in grads.items()})


def test_parameter_names_are_the_models(data):
    _, model, params = data
    assert sorted(params) == sorted(PARAMS)
    kinds = [op.kind for op in model._ops[1:]]
    assert kinds == ["dropout", "linear", "gat", "linear", "add",
                     "activation"] * 2 + ["dropout", "linear", "gat",
                                          "linear", "add"]
    assert [op.attrs["rate"] for op in model._ops
            if op.kind == "dropout"] == [0.1, 0.75, 0.75]


@pytest.mark.parametrize("kwargs,linears,acts", [
    ({}, 2, ["elu"]),
    ({"heads": 2}, 2, ["elu"]),
    ({"skip": True}, 4, ["elu"]),
    ({"activation": "relu"}, 2, ["relu"]),
])
def test_builder_options(kwargs, linears, acts):
    """Without the new options the model is the one it was: one linear
    a layer, ELU, one dropout rate."""
    model = build_gat([12, 8, 3], **kwargs)
    assert sum(op.kind == "linear" for op in model._ops) == linears
    assert [op.attrs["mode"] for op in model._ops
            if op.kind == "activation"] == acts
    assert {op.attrs["rate"] for op in model._ops
            if op.kind == "dropout"} == {0.5}
    with pytest.raises(ValueError):
        build_gat([12, 8, 3], activation="gelu")


@pytest.mark.parametrize("padding", PADDING)
@pytest.mark.parametrize("layout", LAYOUTS)
def test_logits_match_the_reference(data, plain, layout, padding,
                                    monkeypatch):
    logits, _, _ = _system(data, layout, padding, monkeypatch)
    np.testing.assert_allclose(logits, plain[0], rtol=2e-4, atol=2e-5)
    # the hub attends over 61 rows; the isolated row's attention output
    # is 0 in every layer, so its logits are its skip path alone
    assert np.abs(logits[HUB]).max() > 0


@pytest.mark.parametrize("padding", PADDING)
@pytest.mark.parametrize("layout", LAYOUTS)
def test_loss_matches_the_reference(data, plain, layout, padding,
                                    monkeypatch):
    _, loss, _ = _system(data, layout, padding, monkeypatch)
    assert loss == pytest.approx(plain[1], rel=1e-5)


@pytest.mark.parametrize("name", PARAMS)
@pytest.mark.parametrize("padding", PADDING)
@pytest.mark.parametrize("layout", LAYOUTS)
def test_gradient_matches_the_reference(data, plain, layout, padding,
                                        name, monkeypatch):
    _, _, grads = _system(data, layout, padding, monkeypatch)
    want = plain[2][name]
    assert np.abs(want).max() > 1e-4, "a dead parameter tests nothing"
    np.testing.assert_allclose(grads[name], want, rtol=1e-3,
                               atol=1e-4 * np.abs(want).max())


def test_isolated_row_is_its_skip_path(ref, data, monkeypatch):
    """No stored edge: the attention output is 0 in both, so the row's
    hidden state is ``relu(h R)`` and never NaN."""
    logits, _, _ = _system(data, "ell", "exact", monkeypatch)
    ds, _, params = data
    h = ds.features[ISOLATED]
    for k in (1, 3):
        h = np.maximum(h @ np.asarray(params[f"linear_{k}"]), 0.0)
    want = h @ np.asarray(params["linear_5"])
    np.testing.assert_allclose(logits[ISOLATED], want, rtol=1e-4,
                               atol=1e-5)


@pytest.mark.parametrize("layout", LAYOUTS)
def test_mixed_precision_is_inside_the_cells_tolerances(
        ref, data, plain, layout, monkeypatch):
    reference, _ = ref
    with open(os.path.join(BENCH, "workloads",
                           "gat-arxiv.fullgraph.json")) as f:
        tol = json.load(f)["correct"]
    logits, _, _ = _system(data, layout, "padded", monkeypatch,
                           dtype=jnp.bfloat16)
    got = reference.compare(logits, plain[0])
    assert got["finite"]
    assert got["row_rel_l2_max"] <= tol["row_rel_l2_max"], got
    assert got["row_rel_l2_median"] <= tol["row_rel_l2_median"], got


@pytest.mark.parametrize("layout,rule", [
    ("ell", "autodiff"), ("attn_flat8", "autodiff"), ("ell", "transposed")])
def test_phase_scopes_parse_from_the_compiled_step(data, sym_data, layout,
                                                   rule, monkeypatch):
    """Every attention op of the compiled train step has rows ``(agg,
    op, phase, fwd)`` and ``(agg, op, phase, bwd)`` for each of the
    three phases; the eval step has the forward ones alone.  Of the
    hand-written backward, every instruction sits in a phase or is the
    halo's."""
    ds, model, _ = sym_data if rule == "transposed" else data
    _set_budget(monkeypatch, 700)
    tr = Trainer(model, ds, TrainConfig(verbose=False, aggr_impl=layout))
    assert tr.config.aggr_impl == layout
    assert tr.gctx.symmetric == (rule == "transposed")
    tr.train(epochs=1)
    tr.evaluate()
    ops = {i for i, op in enumerate(tr.model._ops) if op.kind == "gat"}
    assert len(ops) == 3
    want = {(AGG, i, ph, way) for i in ops for ph in ATTN_PHASES
            for way in ("fwd", "bwd")}
    scopes = tr._train_step.instruction_scopes()["scopes"]
    rows = {p for p in map(parse_op_phase, scopes.values()) if p}
    assert rows == want
    if rule == "transposed":
        backward = [s for s in scopes.values()
                    if parse_op_name(s) in {(AGG, i, "bwd") for i in ops}]
        assert len(backward) > 100
        assert all(parse_op_phase(s) for s in backward)
    rows = {p for p in map(parse_op_phase,
                           tr._eval_step.instruction_scopes()[
                               "scopes"].values()) if p}
    assert rows == {r for r in want if r[3] == "fwd"}


@pytest.mark.parametrize("op_name,want", [
    ("jit(step)/jvp(roc.agg.op03)/roc.attn.scores/gather",
     (AGG, 3, "scores", "fwd")),
    ("jit(step)/transpose(jvp(roc.agg.op09))/while/body/checkpoint/"
     "roc.attn.gather/dot_general", (AGG, 9, "gather", "bwd")),
    ("jit(step)/jvp(roc.agg.op03)/roc.attn.scores/roc.attn.stats/exp",
     (AGG, 3, "stats", "fwd")),
    ("jit(step)/jvp(roc.agg.op03)/roc.halo/all_gather", None),
    ("jit(step)/jvp(roc.agg.op03)/while/body/add", None),
    ("jit(step)/jvp(roc.dense.op02.linear)/dot_general", None),
    ("jit(step)/roc.attn.scores/exp", None),
])
def test_parse_op_phase(op_name, want):
    assert parse_op_phase(op_name) == want


@pytest.mark.parametrize("layout,parts", [
    ("ell", 1), ("attn_flat8", 1), ("ell", 4), ("attn_flat8", 4)])
def test_attention_plan_entries(data, layout, parts, monkeypatch):
    """One ``attention`` entry per attention op in the run manifest's
    ``resolved`` (the benchmark's ``plan`` line), from both trainers."""
    from roc_tpu.obs import manifest
    from roc_tpu.parallel.distributed import DistributedTrainer
    ds, model, _ = data
    cfg = TrainConfig(verbose=False, aggr_impl=layout, symmetric=False)
    seen = []
    monkeypatch.setattr(
        manifest, "emit",
        lambda cat, msg, **fields: seen.append((cat, fields)))
    tr = (Trainer(model, ds, cfg) if parts == 1
          else DistributedTrainer(model, ds, parts, cfg))
    (man,) = [fields for cat, fields in seen if cat == "manifest"]
    got = man["resolved"]["attention"]
    assert [(e["op"], e["heads"], e["head_width"]) for e in got] == [
        (3, HEADS, HEAD_W), (9, HEADS, HEAD_W), (15, 1, CLASSES)]
    assert {e["layout"] for e in got} == {layout}
    rows = V if parts == 1 else tr.pg.part_nodes     # padded
    if layout == "ell":
        tables = tr.gctx.ell_idx if parts == 1 else tr.data.ell_idx
        slots = sum(int(np.prod(a.shape[-2:])) for a in tables)
        want = (1, slots, None)
    else:
        table = (tr.gctx.flat8_idx if parts == 1
                 else tr.data.sect_idx[0])
        want = (2, int(np.prod(table.shape[-3:])), rows + 1)
    assert {(e["edge_passes"], e["padded_slots_per_pass"],
             e["carry_rows"]) for e in got} == {want}
    assert want[1] >= ds.graph.num_edges / parts
    assert man["resolved"]["attention_backward"] == [
        {"op": i, "rule": "autodiff", "edge_passes": 2, "scatters": 3}
        for i in (3, 9, 15)]


@pytest.mark.parametrize("parts", [1, 2])
@pytest.mark.parametrize("graph,layout,rule", [
    ("symmetric", "ell", "transposed"), ("directed", "ell", "autodiff"),
    ("symmetric", "attn_flat8", "autodiff")])
def test_attention_backward_entries(data, sym_data, graph, layout, rule,
                                    parts, monkeypatch):
    """The gradient rule each attention op took, read off the graph
    (``symmetric`` unset), in the run manifest's ``resolved`` beside
    ``attention`` — from both trainers."""
    from roc_tpu.obs import manifest
    from roc_tpu.parallel.distributed import DistributedTrainer
    ds, model, _ = sym_data if graph == "symmetric" else data
    cfg = TrainConfig(verbose=False, aggr_impl=layout)
    seen = []
    monkeypatch.setattr(
        manifest, "emit",
        lambda cat, msg, **fields: seen.append((cat, fields)))
    if parts == 1:
        Trainer(model, ds, cfg)
    else:
        DistributedTrainer(model, ds, parts, cfg)
    (man,) = [fields for cat, fields in seen if cat == "manifest"]
    want = ({"rule": "transposed", "edge_passes": 1, "scatters": 0}
            if rule == "transposed" else
            {"rule": "autodiff", "edge_passes": 2, "scatters": 3})
    assert man["resolved"]["attention_backward"] == [
        {"op": i, **want} for i in (3, 9, 15)]
    assert [e["op"] for e in man["resolved"]["attention"]] == [3, 9, 15]


def test_attention_plan_counts_the_sliced_numerator(data, monkeypatch):
    """Past ``resolve_dh_chunk``'s budget the flat layout scans once
    for the row max, once for the denominator and once a slice."""
    from roc_tpu.ops import attention
    ds, model, _ = data
    gctx = make_graph_context(ds, "attn_flat8", symmetric=False)
    monkeypatch.setattr(attention, "resolve_dh_chunk",
                        lambda rows, heads, dh: 4)
    got = gctx.attention_plan(model._ops)["attention"]
    assert [e["edge_passes"] for e in got] == [2 + 3, 2 + 3, 2 + 2]
    back = gctx.attention_plan(model._ops)["attention_backward"]
    # the denominator's scan and one a slice, each recomputed and
    # transposed; the denominator's gathers no features
    assert [(e["rule"], e["edge_passes"], e["scatters"]) for e in back] \
        == [("autodiff", 8, 11), ("autodiff", 8, 11), ("autodiff", 6, 8)]
    sums = make_graph_context(ds, "flat_sum", symmetric=False)
    assert sums.attention_plan([op for op in model._ops
                                if op.kind != "gat"]) == {}


# ---- the symmetric graph's gradient rule against autodiff ----

SYM_CHECKS = ("logits", "loss") + tuple(PARAMS)


@pytest.mark.parametrize("what", SYM_CHECKS)
@pytest.mark.parametrize("padding", PADDING)
def test_symmetric_rule_matches_autodiff(sym_data, padding, what,
                                         monkeypatch):
    """float32, 3 heads x 10 twice and 1 head x 5: the same logits and
    loss (the forward rule's tiles are the forward's) and every
    parameter's gradient, with every bucket segmented into scans that
    meet padding rows and with none segmented."""
    auto = _system(sym_data, "ell", padding, monkeypatch)
    sym = _system(sym_data, "ell", padding, monkeypatch, symmetric=True)
    if what == "logits":
        np.testing.assert_allclose(sym[0], auto[0], rtol=1e-6, atol=1e-6)
        assert np.abs(sym[0][HUB]).max() > 0
    elif what == "loss":
        assert sym[1] == pytest.approx(auto[1], rel=1e-6)
    else:
        want = auto[2][what]
        assert np.abs(want).max() > 1e-4, "a dead parameter tests nothing"
        np.testing.assert_allclose(sym[2][what], want, rtol=2e-5,
                                   atol=5e-6 * np.abs(want).max())


@pytest.mark.parametrize("name", PARAMS)
def test_symmetric_rule_in_mixed_precision(sym_data, name, monkeypatch):
    """bfloat16 features and weights: the rule's gradient is no further
    from the float32 one than autodiff's bfloat16 gradient is (one
    width reduction a row against a cotangent rounded once a segment),
    give or take a rounding."""
    exact = _system(sym_data, "ell", "padded", monkeypatch)[2][name]
    scale = np.linalg.norm(exact)

    def off(symmetric):
        got = _system(sym_data, "ell", "padded", monkeypatch,
                      dtype=jnp.bfloat16, symmetric=symmetric)[2][name]
        return np.linalg.norm(got.astype(np.float32) - exact) / scale

    assert off(True) <= max(1.5 * off(False), 0.01), (off(True), off(False))


def test_symmetric_rule_mixed_logits_inside_the_cells_tolerances(
        ref, sym_data, monkeypatch):
    reference, _ = ref
    with open(os.path.join(BENCH, "workloads",
                           "gat-arxiv.fullgraph.json")) as f:
        tol = json.load(f)["correct"]
    want = _system(sym_data, "ell", "padded", monkeypatch)[0]
    logits = _system(sym_data, "ell", "padded", monkeypatch,
                     dtype=jnp.bfloat16, symmetric=True)[0]
    got = reference.compare(logits, want)
    assert got["finite"]
    assert got["row_rel_l2_max"] <= tol["row_rel_l2_max"], got
    assert got["row_rel_l2_median"] <= tol["row_rel_l2_median"], got


def test_directed_graph_takes_autodiff(data, monkeypatch):
    """``symmetric`` unset on a directed graph resolves to autodiff:
    the gradient program is, to the letter, the one ``symmetric=False``
    gives — which the cases at the top hold to the reference."""
    ds, model, params = data
    _set_budget(monkeypatch, 700)
    texts = []
    for symmetric in (None, False):
        gctx = make_graph_context(ds, "ell", symmetric=symmetric)
        assert gctx.symmetric is False
        texts.append(jax.jit(jax.grad(
            lambda p, gctx=gctx: model.loss_fn(
                p, jnp.asarray(ds.features), jnp.asarray(ds.labels),
                jnp.asarray(ds.mask), gctx, key=None, train=False)[0])
        ).lower(params).as_text())
    assert texts[0] == texts[1] and "stablehlo.scatter" in texts[0]


_parts_cache = {}


@pytest.mark.parametrize("name", PARAMS)
def test_symmetric_rule_through_the_halo(sym_data, name, monkeypatch):
    """Two partitions: the cotangent and the packed row statistics
    reach the other partition's rows through the all-gather the
    forward's features took, and the summed gradients (the step's
    ``psum``, read by standing in for Adam) are autodiff's."""
    from roc_tpu.parallel import distributed
    ds, model, _ = sym_data
    monkeypatch.setattr(distributed, "adam_update",
                        lambda params, grads, state, lr, cfg: (grads, state))
    _set_budget(monkeypatch, 700)
    for symmetric in (None, False):
        if symmetric not in _parts_cache:
            dt = distributed.DistributedTrainer(
                model, ds, 2, TrainConfig(verbose=False, aggr_impl="ell",
                                          symmetric=symmetric,
                                          eval_every=1 << 30))
            assert dt.symmetric == (symmetric is None)
            dt.train(epochs=1)
            _parts_cache[symmetric] = {
                k: np.asarray(v) for k, v in jax.device_get(
                    dt.params).items()}
    want = _parts_cache[False][name]
    assert np.abs(want).max() > 1e-4
    np.testing.assert_allclose(_parts_cache[None][name], want, rtol=2e-5,
                               atol=5e-6 * np.abs(want).max())


def _produced_in_loops(text, rows):
    """The operations of a lowered program that yield a ``[rows, .]``
    value inside a ``while`` — its regions and every function they
    call — as ``{operation name}``."""
    import re
    funcs, name = {}, None
    for line in text.splitlines():
        m = re.match(r"\s*func\.func (?:public |private )?@([\w.]+)", line)
        if m:
            name = m.group(1)
            funcs[name] = []
        elif name:
            funcs[name].append(line)
    shaped = re.compile(rf"-> \(?tensor<{rows}x|: tensor<{rows}x[^)]*$")
    made, inside, seen = set(), [], set()

    def scan(lines, in_loop):
        depth, loops = 0, []
        for line in lines:
            if "stablehlo.while" in line:
                loops.append(depth)
            elif in_loop or loops:
                op = re.search(r"= \"?(?:stablehlo|func)\.([a-z_]+)", line)
                if shaped.search(line):     # "})": an op with a region
                    made.add(op.group(1) if op else "region")
                call = re.search(r"call @([\w.]+)", line)
                if call and call.group(1) not in seen:
                    seen.add(call.group(1))
                    inside.append(call.group(1))
            depth += line.count("{") - line.count("}")
            if loops and depth <= loops[-1] and "}" in line \
                    and "do {" not in line:
                loops.pop()

    for lines in list(funcs.values()):
        scan(lines, False)
    while inside:
        scan(funcs[inside.pop()], True)
    return made


_eval_texts = {}


@pytest.mark.parametrize("rule", ["transposed", "autodiff"])
def test_train_step_program_shape(sym_data, rule, monkeypatch):
    """The symmetric rule's lowered train step scatters nothing under
    an attention phase and makes no ``[G+1, .]`` value inside a loop
    (every bucket is segmented here: a later edit cannot bring the
    once-a-segment whole-array cotangent back unnoticed); autodiff on
    the same graph does both.  The eval step is, to the letter, the
    same program under either."""
    ds, model, _ = sym_data
    _set_budget(monkeypatch, 700)
    tr = Trainer(model, ds, TrainConfig(
        verbose=False, aggr_impl="ell",
        symmetric=None if rule == "transposed" else False))
    tr.train(epochs=1)
    tr.evaluate()
    scatters = [n for n, s in tr._train_step.instruction_scopes()[
        "scopes"].items() if n.startswith("scatter") and "roc.attn" in s]
    made = _produced_in_loops(tr._train_step._lowered.as_text(), V + 1)
    if rule == "transposed":
        assert not scatters and not made, (scatters, made)
    else:
        assert scatters and {"add", "region"} <= made
    text = tr._eval_step._lowered.as_text()
    assert _eval_texts.setdefault("ell", text) == text
