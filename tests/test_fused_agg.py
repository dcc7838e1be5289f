"""Fused-normalization aggregation (ISSUE 1): the fusion pass over the
recorded-op graph, fused-vs-unfused forward/gradient equivalence in
fp32 (<= 1e-5 rel) across impl x halo x model, the TrainConfig knob
plumbing, and the round-5 advisor regressions that ride this PR."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from roc_tpu.core.graph import synthetic_dataset
from roc_tpu.models.tables import RingTables, SectionedTables
from roc_tpu.models.builder import Model
from roc_tpu.models.gcn import build_gcn
from roc_tpu.models.gcn2 import build_gcn2
from roc_tpu.models.gin import build_gin
from roc_tpu.models.sgc import build_sgc
from roc_tpu.train.trainer import (TrainConfig, Trainer,
                                   make_graph_context, resolve_fuse)

REL = 1e-5


@pytest.fixture(scope="module")
def dataset():
    return synthetic_dataset(96, 5, in_dim=12, num_classes=4, seed=7)


def _rel_err(a, b):
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return np.max(np.abs(a - b)) / (np.max(np.abs(a)) + 1e-30)


def _logits_and_grads(model, params, ds, gctx):
    feats = jnp.asarray(ds.features)
    labels = jnp.asarray(ds.labels)
    mask = jnp.asarray(ds.mask)
    logits = model.apply(params, feats, gctx, train=False)

    def loss(p):
        l, _ = model.loss_fn(p, feats, labels, mask, gctx,
                             train=False)
        return l

    return logits, jax.grad(loss)(params)


# ---- the fusion pass itself ----

def test_fuse_rewrites_gcn_chains():
    m = build_gcn([12, 16, 4])
    f = m.fuse_norm_aggregate()
    assert f.num_fused_aggregates() == 2
    kinds = [op.kind for op in f._ops]
    assert "indegree_norm" not in kinds
    assert "scatter_gather" not in kinds
    # the hidden layer's relu folded into the fused op; the output
    # layer's (loss-marked, no relu) did not gain one
    acts = [op.attrs["activation"] for op in f._ops
            if op.kind == "fused_aggregate"]
    assert acts == ["relu", "none"]
    # parameter-name compatibility: the chain is parameter-free
    k0 = set(m.init_params(jax.random.PRNGKey(0)))
    k1 = set(f.init_params(jax.random.PRNGKey(0)))
    assert k0 == k1


def test_fuse_deep_gcn_keeps_residual_consumers():
    # n > 3 adds a dense residual consuming the relu output — the
    # chain (incl. relu) still fuses because only INTERMEDIATES need
    # a single consumer
    m = build_gcn([12, 16, 16, 4])
    f = m.fuse_norm_aggregate()
    assert f.num_fused_aggregates() == 3
    assert any(op.kind == "add" for op in f._ops)


def test_fuse_gcn2_and_sgc():
    assert build_gcn2([12, 16, 16, 4]).fuse_norm_aggregate() \
        .num_fused_aggregates() == 2
    # SGC: k norm->agg->norm hops on raw features, no relus between
    f = build_sgc([12, 4], k=3).fuse_norm_aggregate()
    assert f.num_fused_aggregates() == 3
    assert all(op.attrs["activation"] == "none" for op in f._ops
               if op.kind == "fused_aggregate")


def test_fuse_leaves_models_without_chains_alone():
    m = build_gin([12, 16, 4])
    f = m.fuse_norm_aggregate()
    assert f.num_fused_aggregates() == 0
    assert [op.kind for op in f._ops] == [op.kind for op in m._ops]


def test_fuse_respects_loss_marker_on_intermediate():
    # loss marked on the POST-AGGREGATE norm output is fine (it maps
    # to the fused op's output), but a relu past it must NOT fold
    m = Model(in_dim=8)
    t = m.input()
    t = m.indegree_norm(t)
    t = m.scatter_gather(t)
    t = m.indegree_norm(t)
    m.softmax_cross_entropy(t)
    t = m.relu(t)
    f = m.fuse_norm_aggregate()
    assert f.num_fused_aggregates() == 1
    fa = next(op for op in f._ops if op.kind == "fused_aggregate")
    assert fa.attrs["activation"] == "none"
    assert [op.kind for op in f._ops].count("activation") == 1


def test_fuse_skips_multi_consumer_intermediates():
    # the aggregate output feeds BOTH the post-norm and an add — the
    # chain must not fuse (the intermediate would disappear)
    m = Model(in_dim=8)
    t = m.input()
    n = m.indegree_norm(t)
    s = m.scatter_gather(n)
    p = m.indegree_norm(s)
    q = m.add(p, s)
    m.softmax_cross_entropy(q)
    f = m.fuse_norm_aggregate()
    assert f.num_fused_aggregates() == 0


def test_streamable_agg_head_accepts_fused_prefix():
    f = build_sgc([12, 4], k=2).fuse_norm_aggregate()
    head = f.streamable_agg_head()
    assert head is not None
    prefix_ops, rate, param, tail = head
    assert all(op.kind == "fused_aggregate" for op in prefix_ops)


# ---- fused vs unfused equivalence (forward + grads, fp32) ----

@pytest.mark.parametrize("impl", ["segment", "ell", "sectioned",
                                  "bdense", "flat_sum"])
@pytest.mark.parametrize("build", [
    lambda: build_gcn([12, 16, 4]),
    lambda: build_gcn([12, 16, 16, 4]),      # deep: dense residual
    lambda: build_gcn2([12, 16, 16, 4]),
    lambda: build_sgc([12, 4], k=2),
], ids=["gcn", "gcn-residual", "gcn2", "sgc"])
def test_fused_matches_unfused_single_device(dataset, impl, build):
    m = build()
    f = m.fuse_norm_aggregate()
    assert f.num_fused_aggregates() > 0
    params = m.init_params(jax.random.PRNGKey(3))
    g0 = make_graph_context(dataset, impl, chunk=8, bdense_min_fill=1)
    g1 = make_graph_context(dataset, impl, chunk=8, bdense_min_fill=1,
                            fuse=True)
    out0, gr0 = _logits_and_grads(m, params, dataset, g0)
    out1, gr1 = _logits_and_grads(f, params, dataset, g1)
    assert _rel_err(out0, out1) < REL
    for k in gr0:
        assert _rel_err(gr0[k], gr1[k]) < REL, k


def test_fused_weight_tables_present(dataset):
    # the table-baked forms actually engage (not the scaling fallback)
    t = make_graph_context(dataset, "ell", fuse=True).tables
    assert t.w and len(t.w) == len(t.idx)
    t = make_graph_context(dataset, "sectioned", fuse=True).tables
    assert t.w and len(t.w) == len(t.idx)
    t = make_graph_context(dataset, "bdense", bdense_min_fill=1,
                           fuse=True).tables
    assert len(t.scale) == 2
    t = make_graph_context(dataset, "flat_sum", fuse=True).tables
    assert t.w is not None and t.w.shape == t.idx.shape


@pytest.mark.parametrize("halo,impl", [
    ("gather", "ell"), ("gather", "sectioned"), ("gather", "flat_sum"),
    ("ring", "ell")])
def test_fused_matches_unfused_distributed(dataset, halo, impl):
    from roc_tpu.parallel.distributed import DistributedTrainer
    cfg = TrainConfig(aggr_impl=impl, halo=halo, memory="manual",
                      dropout_rate=0.0, verbose=False, epochs=2,
                      eval_every=1 << 30)
    t0 = DistributedTrainer(build_gcn([12, 16, 4], dropout_rate=0.0),
                            dataset, 2,
                            dataclasses.replace(cfg, aggr_fuse="off"))
    t1 = DistributedTrainer(build_gcn([12, 16, 4], dropout_rate=0.0),
                            dataset, 2,
                            dataclasses.replace(cfg, aggr_fuse="on"))
    assert t1.model.num_fused_aggregates() == 2
    if halo == "gather":
        # shard_dataset baked the layout's own weight tables
        assert t1.data.tables.weighted
        assert not t0.data.tables.weighted
    assert _rel_err(t0.predict(), t1.predict()) < REL
    # gradients: two full training epochs must keep params aligned
    t0.train(2)
    t1.train(2)
    for k in t0.params:
        assert _rel_err(t0.params[k], t1.params[k]) < 1e-4, k


@pytest.mark.parametrize("halo", ["gather", "ring"])
def test_fused_ring_weight_tables_bake(dataset, halo):
    # shard_dataset actually bakes the weights for the fused model
    from roc_tpu.parallel.distributed import DistributedTrainer
    cfg = TrainConfig(aggr_impl="sectioned", halo=halo,
                      memory="manual", aggr_fuse="on",
                      verbose=False)
    t = DistributedTrainer(build_gcn([12, 16, 4]), dataset, 2, cfg)
    assert t.data.tables.weighted
    assert isinstance(t.data.tables,
                      RingTables if halo == "ring" else SectionedTables)


def test_trainer_fuse_knob_and_equivalence(dataset):
    base = dict(aggr_impl="ell", dropout_rate=0.0, verbose=False,
                memory="manual")
    t_off = Trainer(build_gcn([12, 16, 4], dropout_rate=0.0), dataset,
                    TrainConfig(aggr_fuse="off", **base))
    t_on = Trainer(build_gcn([12, 16, 4], dropout_rate=0.0), dataset,
                   TrainConfig(aggr_fuse="auto", **base))
    assert t_off.model.num_fused_aggregates() == 0
    assert t_on.model.num_fused_aggregates() == 2
    assert _rel_err(np.asarray(t_off.predict()),
                    np.asarray(t_on.predict())) < REL
    with pytest.raises(ValueError, match="aggr_fuse"):
        resolve_fuse(build_gcn([12, 16, 4]),
                     TrainConfig(aggr_fuse="sometimes"))


def test_fused_sgc_host_streaming_matches(dataset):
    # features='host' + fused model: the parameter-free fused prefix
    # streams through stream_prefix_to_host exactly
    base = dict(aggr_impl="segment", dropout_rate=0.0, verbose=False,
                memory="manual", features="host")
    t_off = Trainer(build_sgc([12, 4], k=2), dataset,
                    TrainConfig(aggr_fuse="off", **base))
    t_on = Trainer(build_sgc([12, 4], k=2), dataset,
                   TrainConfig(aggr_fuse="on", **base))
    assert _rel_err(np.asarray(t_off.predict()),
                    np.asarray(t_on.predict())) < REL


# ---- round-5 advisor regressions ----

def test_autopilot_charges_probed_bdense(dataset, monkeypatch):
    """ADVICE r5: when aggr_impl='auto' probe-resolves to bdense, the
    memory autopilot must see the concrete impl and charge the
    A-table budget (extra_table_bytes > 0)."""
    import roc_tpu.train.trainer as tr
    seen = {}
    real_plan = tr.__dict__["apply_memory_autopilot"]

    def fake_probe(graph, out_rows=None, **kw):
        return "bdense", None

    from roc_tpu.core import memory as mem
    real_choose = mem.choose_memory_plan

    def spy_choose(*a, **kw):
        seen["extra"] = kw.get("extra_table_bytes", 0)
        return real_choose(*a, **kw)

    monkeypatch.setattr(tr, "resolve_auto_impl_probed", fake_probe)
    monkeypatch.setattr(mem, "choose_memory_plan", spy_choose)
    cfg = TrainConfig(aggr_impl="auto", memory="auto", verbose=False,
                      bdense_min_fill=1, aggr_fuse="off")
    Trainer(build_gcn([12, 16, 4]), dataset, cfg)
    assert seen["extra"] == cfg.bdense_a_budget > 0


def test_resolve_dh_chunk_sizes_training_carry():
    """ADVICE r5: the flat8 dh chunk is sized against the TRAINING
    carry (forward + cotangent = 2x), not the forward alone."""
    from roc_tpu.ops.attention import resolve_dh_chunk
    budget = 1 << 20
    heads, dh = 1, 64
    # rows chosen so the forward carry fits the budget but 2x does NOT
    rows = (budget * 3 // 4) // (heads * 4 * dh) - 1
    fwd_bytes = (rows + 1) * heads * 4 * dh
    assert fwd_bytes <= budget < 2 * fwd_bytes
    chunk = resolve_dh_chunk(rows, heads, dh, carry_budget=budget)
    assert chunk is not None
    # the chunk's DOUBLED carry fits the stated budget
    assert 2 * (rows + 1) * heads * 4 * chunk <= budget


def test_reorder_overflow_guard_fails_loudly(monkeypatch):
    """ADVICE r5: past the int64 single-key range the relabel raises
    instead of corrupting the CSR (no fallback CAN help: Graph's
    int32 col_idx already caps V below 2^31, where the single key
    always fits — so the guard marks an unrepresentable input)."""
    import roc_tpu.core.reorder as ro
    from roc_tpu.core.graph import add_self_edges, synthetic_graph
    g = add_self_edges(synthetic_graph(60, 4, seed=2))
    perm = np.random.RandomState(0).permutation(60)
    assert ro.apply_graph_order(g, perm).num_edges == g.num_edges
    assert ro.single_key_fits_int64(60)
    assert ro.single_key_fits_int64((1 << 31) - 1)
    assert not ro.single_key_fits_int64(4_000_000_000)
    monkeypatch.setattr(ro, "single_key_fits_int64", lambda v: False)
    with pytest.raises(ValueError, match="single-key int64"):
        ro.apply_graph_order(g, perm)
