"""Lane-wide rows for the sum chunk scan (ISSUE 32): a ``flat_sum`` sum
aggregation narrower than the 128 lanes zero-pads its feature axis
before the halo and the gather and slices the result; ``sectioned``,
raced on the chip both ways, keeps the model's width.  Held here: the
rule (``core/ell.py agg_lane_width``), bit-identity of outputs and
gradients with the bare scan at P = 1 and on the 4-device CPU rig, the
untouched program text wherever the rule does not engage, and the
``agg_lane_pad`` entries of the ``plan`` line.
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from roc_tpu.core.ell import AGGR_IMPLS, LANE_WIDTH, agg_lane_width
from roc_tpu.core.graph import synthetic_dataset
from roc_tpu.models import builder
from roc_tpu.models.gcn import build_gcn
from roc_tpu.ops.aggregate import aggregate_ell_sect, aggregate_flat_sum
from roc_tpu.parallel.distributed import DistributedTrainer
from roc_tpu.train.trainer import (TrainConfig, Trainer,
                                   make_graph_context)

SCAN_IMPLS = ("sectioned", "flat_sum")


@pytest.fixture(scope="module")
def ds():
    return synthetic_dataset(num_nodes=300, avg_degree=6, in_dim=12,
                             num_classes=5, seed=11)


@pytest.fixture(scope="module")
def gctxs(ds):
    return {(impl, fuse): make_graph_context(ds, impl, fuse=fuse)
            for impl in SCAN_IMPLS for fuse in (False, True)}


def _rule_off(monkeypatch):
    """The parent's program from here on: the rule answers every width
    with itself."""
    monkeypatch.setattr(builder, "agg_lane_width",
                        lambda F, impl, halo="gather": F)


# ------------------------------------------------------------ the rule

@pytest.mark.parametrize("impl", AGGR_IMPLS + ("attn_flat8",))
@pytest.mark.parametrize("F", [1, 41, 47, 127, 128, 129, 256])
def test_agg_lane_width_rule(impl, F):
    """Under 128 lanes ``flat_sum`` runs at 128; every other layout,
    and every width from 128 up, keeps its own."""
    want = LANE_WIDTH if impl == "flat_sum" and F < LANE_WIDTH else F
    assert agg_lane_width(F, impl) == want
    # the ring halo sums per hop through no chunk scan
    assert agg_lane_width(F, impl, "ring") == F


def test_gctx_lane_width_reads_its_own_layout(gctxs, ds):
    g = gctxs["flat_sum", False]
    assert g._lane_width(47) == 128 and g._lane_width(256) == 256
    assert dataclasses.replace(g, halo="ring")._lane_width(47) == 47
    assert gctxs["sectioned", False]._lane_width(47) == 47
    assert make_graph_context(ds, "ell")._lane_width(47) == 47


# ------------------------------------- bit-identity with the bare scan

def _bare_scan(g, x, fused):
    """The unpadded scan, called directly on ``[x; 0]`` with the
    context's own tables."""
    full = jnp.concatenate([x, jnp.zeros((1, x.shape[1]), x.dtype)])
    t = g.tables
    if g.aggr_impl == "flat_sum":
        return aggregate_flat_sum(full, t.idx, t.dst, g.num_rows,
                                  flat_w=t.w if fused else None,
                                  win_rows=t.win_rows)
    return aggregate_ell_sect(full, t.idx, t.sub_dst, t.meta,
                              g.num_rows, sect_w=t.w if fused else None)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("F", [5, 41, 47, 127])
@pytest.mark.parametrize("fused", [False, True],
                         ids=["sum", "fused"])
@pytest.mark.parametrize("impl", SCAN_IMPLS)
def test_padded_scan_is_bit_identical_p1(gctxs, ds, impl, fused, F,
                                         dtype):
    """Outputs AND gradients through ``GraphContext`` (``flat_sum``:
    padded to 128 lanes) equal the bare scan's at the model's own
    width, bit for bit: the spare lanes hold zeros and the real
    columns are summed in the same order.  The symmetric backward is
    the same function on the cotangent, so that is what the bare side
    runs."""
    g = gctxs[impl, fused]
    if fused:
        assert g.tables.weighted
    V = ds.graph.num_nodes
    kx, kc = jax.random.split(jax.random.PRNGKey(F))
    x = jax.random.normal(kx, (V, F), jnp.float32).astype(dtype)
    cot = jax.random.normal(kc, (V, F), jnp.float32).astype(dtype)
    agg = g.aggregate_fused if fused else g.aggregate_sum
    engaged = " pad" in str(jax.make_jaxpr(agg)(x))
    assert engaged == (impl == "flat_sum")
    out, vjp = jax.vjp(jax.jit(agg), x)
    (grad,) = vjp(cot)
    bare = jax.jit(lambda v: _bare_scan(g, v, fused))
    assert out.shape == (V, F) and out.dtype == dtype
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(bare(x), np.float32))
    np.testing.assert_array_equal(np.asarray(grad, np.float32),
                                  np.asarray(bare(cot), np.float32))
    assert float(jnp.abs(out.astype(jnp.float32)).max()) > 0


def test_padded_scan_directed_autodiff_is_bit_identical(ds,
                                                        monkeypatch):
    """``symmetric=False`` differentiates through the pad and the
    slice themselves (exact autodiff of the scan): same logits and
    gradients as with the rule off."""
    g = dataclasses.replace(make_graph_context(ds, "flat_sum"),
                            symmetric=False)
    x = jax.random.normal(jax.random.PRNGKey(2),
                          (ds.graph.num_nodes, 41), jnp.float32)

    def run():
        f = lambda v: (g.aggregate_sum(v) ** 2).sum()
        return g.aggregate_sum(x), jax.grad(f)(x)

    out1, gr1 = run()
    _rule_off(monkeypatch)
    out0, gr0 = run()
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out0))
    np.testing.assert_array_equal(np.asarray(gr1), np.asarray(gr0))


# ----------------------------------------------- the 4-device CPU rig

def _train_p4(ds, fuse):
    cfg = TrainConfig(aggr_impl="flat_sum", aggr_fuse=fuse,
                      memory="manual",
                      compute_dtype=jnp.bfloat16, dropout_rate=0.0,
                      verbose=False, symmetric=True,
                      eval_every=1 << 30)
    tr = DistributedTrainer(build_gcn([12, 16, 5], dropout_rate=0.0),
                            ds, 4, cfg)
    first = np.asarray(tr.predict(), np.float32)
    tr.train(2)
    params = {k: np.asarray(v, np.float32)
              for k, v in tr.params.items()}
    return first, params, np.asarray(tr.predict(), np.float32)


@pytest.mark.parametrize("fuse", ["off", "on"])
def test_padded_scan_is_bit_identical_p4(ds, fuse, monkeypatch):
    """``flat_sum`` over four partitions, bf16 compute, halo
    all-gather of the padded local block: logits before training, the parameters after two
    epochs (the gradients, compounded) and the logits after them equal
    the unpadded program's, bit for bit."""
    got = _train_p4(ds, fuse)
    _rule_off(monkeypatch)
    jax.clear_caches()
    want = _train_p4(ds, fuse)
    np.testing.assert_array_equal(got[0], want[0])
    for k in want[1]:
        np.testing.assert_array_equal(got[1][k], want[1][k], err_msg=k)
    np.testing.assert_array_equal(got[2], want[2])
    assert np.abs(got[2] - got[0]).max() > 0          # it trained


# ------------------------------------------------------ program text

def _agg_text(g, F, fused):
    x = jax.ShapeDtypeStruct((g.num_rows, F), jnp.bfloat16)
    agg = g.aggregate_fused if fused else g.aggregate_sum

    def f(v):
        return jax.vjp(agg, v)[1](v)

    return jax.jit(f).lower(x).as_text()


@pytest.mark.parametrize("fused", [False, True], ids=["sum", "fused"])
@pytest.mark.parametrize("impl", SCAN_IMPLS)
def test_wide_op_lowers_to_the_parents_text(gctxs, impl, fused,
                                            monkeypatch):
    """At 128 lanes and wider the rule adds nothing to the program:
    forward + backward lower to the text they have with the rule off;
    at 47 ``flat_sum`` alone differs."""
    g = gctxs[impl, fused]
    with_rule = {F: _agg_text(g, F, fused) for F in (47, 128, 256)}
    _rule_off(monkeypatch)
    assert _agg_text(g, 256, fused) == with_rule[256]
    assert _agg_text(g, 128, fused) == with_rule[128]
    padded = impl == "flat_sum"
    assert (_agg_text(g, 47, fused) != with_rule[47]) == padded
    assert ("x128xbf16" in with_rule[47]) == padded


def test_other_layouts_lower_to_the_parents_text(ds, monkeypatch):
    """``ell`` and ``segment`` stay unpadded at any width."""
    texts = {impl: _agg_text(make_graph_context(ds, impl), 47, False)
             for impl in ("ell", "segment")}
    _rule_off(monkeypatch)
    for impl, text in texts.items():
        assert _agg_text(make_graph_context(ds, impl), 47,
                         False) == text
        assert "x128xbf16" not in text


# ----------------------------------------------------- the plan line

@pytest.mark.parametrize("impl,parts,fuse", [
    ("sectioned", 1, "on"), ("flat_sum", 1, "off"),
    ("flat_sum", 4, "on"), ("sectioned", 4, "off"), ("ell", 1, "on")])
def test_plan_line_carries_agg_lane_pad(tmp_path, ds, impl, parts,
                                        fuse):
    """``resolved`` (the benchmark's ``plan`` line) lists one
    ``[op, F, Fp]`` per sum-aggregating op, from both trainers.  The
    first layer sums its 12-wide input before its linear
    (``core/relations.py resolve_agg_order``: 12, or 128 lanes, once
    against twice 160)."""
    from roc_tpu.obs.events import configure
    p = str(tmp_path / "ev.jsonl")
    cfg = TrainConfig(aggr_impl=impl, aggr_fuse=fuse, verbose=False,
                      symmetric=True)
    model = build_gcn([12, 160, 5])
    try:
        configure(jsonl_path=p, console=False)
        if parts > 1:
            tr = DistributedTrainer(model, ds, parts, cfg)
        else:
            tr = Trainer(model, ds, cfg)
    finally:
        configure(jsonl_path=None)
    res = [json.loads(line) for line in open(p)
           if json.loads(line)["cat"] == "manifest"][-1]["resolved"]
    kind = "fused_aggregate" if fuse == "on" else "scatter_gather"
    ops = [i for i, op in enumerate(tr.model._ops) if op.kind == kind]
    assert len(ops) == 2
    pad5 = 128 if impl == "flat_sum" else 5
    pad12 = 128 if impl == "flat_sum" else 12
    assert res["agg_lane_pad"] == [[ops[0], 12, pad12],
                                   [ops[1], 5, pad5]]


@pytest.mark.parametrize("impl,parts", [
    ("sectioned", 1), ("sectioned", 4), ("flat_sum", 1),
    ("flat_sum", 4), ("bdense", 1), ("ell", 1)])
def test_plan_line_carries_agg_chunk_rows(tmp_path, ds, impl, parts,
                                          monkeypatch):
    """``resolved`` says how far the chunk fit engaged (ISSUE 34): one
    ``[n_chunks, seg_rows]`` per section of the sum scan's tables —
    ``core/ell.py fit_chunks`` of the section's own sub-row count —
    and the stored edges over the slots a pass gathers; from both
    trainers, nothing for a layout that scans no chunks."""
    import roc_tpu.core.ell as E
    from roc_tpu.obs.events import configure
    # three sections on this 300-node graph
    monkeypatch.setattr(E, "SECTION_ROWS_DEFAULT", 128)
    p = str(tmp_path / "ev.jsonl")
    # bdense: only the diagonal tiles qualify, the rest is residual
    cfg = TrainConfig(aggr_impl=impl, verbose=False, symmetric=True,
                      bdense_min_fill=300)
    model = build_gcn([12, 16, 5])
    try:
        configure(jsonl_path=p, console=False)
        if parts > 1:
            tr = DistributedTrainer(model, ds, parts, cfg)
            t = tr.data.tables
        else:
            tr = Trainer(model, ds, cfg)
            t = tr.gctx.tables
    finally:
        configure(jsonl_path=None)
    # the chunk tables of the sum scan: the sections, the flat
    # layout's one, bdense's residual
    tables = ((t.idx,) if impl == "flat_sum" else
              t.residual.idx if impl == "bdense" else t.idx)
    res = [json.loads(line) for line in open(p)
           if json.loads(line)["cat"] == "manifest"][-1]["resolved"]
    if impl == "ell":
        assert res["agg_chunk_rows"] == []
        assert res["agg_slot_fill"] is None
        return
    assert len(tables) == (1 if impl == "flat_sum" else 3)
    assert res["agg_chunk_rows"] == [list(t.shape[-3:-1])
                                     for t in tables]
    if impl == "bdense":     # the tables hold the residual only
        assert res["agg_slot_fill"] is None
        return
    g = ds.graph
    slots = sum(int(np.prod(t.shape)) for t in tables)
    assert res["agg_slot_fill"] == round(g.num_edges / slots, 4)
    assert 0.2 < res["agg_slot_fill"] <= 1.0
    if parts == 1:
        counts = E.section_sub_counts(
            g.row_ptr, g.col_idx, g.num_nodes, g.num_nodes,
            128 if impl == "sectioned" else g.num_nodes)
        cap = (E.SECT_SEG_ROWS if impl == "sectioned"
               else E.FLAT_SEG_ROWS)
        assert res["agg_chunk_rows"] == [list(E.fit_chunks(c, cap))
                                         for c in counts]


def test_agg_lane_pad_skips_max_and_attention(ds):
    from roc_tpu.models.builder import AGGR_AVG, AGGR_MAX, Model
    m = Model(in_dim=12)
    t = m.input()
    a = m.scatter_gather(t, AGGR_MAX)
    b = m.scatter_gather(t, AGGR_AVG)
    m.softmax_cross_entropy(m.add(a, b))
    g = make_graph_context(ds, "flat_sum")
    assert g.agg_window(m._ops)["agg_lane_pad"] == [[2, 12, 128]]
    assert g.agg_window()["agg_lane_pad"] == []
