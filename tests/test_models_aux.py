"""SAGE/GIN model families, max aggregator, checkpoint/resume, CLI."""

import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from roc_tpu.core.graph import synthetic_dataset
from roc_tpu.models.builder import AGGR_AVG, AGGR_MAX, AGGR_SUM
from roc_tpu.models.gcn import build_gcn
from roc_tpu.models.gin import build_gin
from roc_tpu.models.sage import build_sage
from roc_tpu.train.trainer import TrainConfig, Trainer, make_graph_context


@pytest.fixture(scope="module")
def dataset():
    return synthetic_dataset(96, 7, in_dim=12, num_classes=3, seed=2)


# GIN's un-normalized sum aggregation amplifies dropout noise on the
# tiny fixture, so it trains without dropout (and needs more epochs).
@pytest.mark.parametrize("build,dropout,epochs",
                         [(build_sage, 0.1, 60), (build_gin, 0.0, 120)])
def test_model_families_converge(dataset, build, dropout, epochs):
    model = build([dataset.in_dim, 24, dataset.num_classes],
                  dropout_rate=dropout)
    cfg = TrainConfig(learning_rate=0.01, weight_decay=1e-4,
                      epochs=epochs, verbose=False)
    t = Trainer(model, dataset, cfg)
    t.train()
    m = t.evaluate()
    assert m["train_acc"] > 0.9, m


@pytest.mark.parametrize("build", [build_sage, build_gin])
def test_model_families_impl_invariance(dataset, build):
    model = build([dataset.in_dim, 16, dataset.num_classes],
                  dropout_rate=0.0)
    params = model.init_params(jax.random.PRNGKey(0))
    feats = jnp.asarray(dataset.features)
    outs = {}
    for impl in ("segment", "ell"):
        gctx = make_graph_context(dataset, aggr_impl=impl)
        outs[impl] = np.asarray(model.apply(params, feats, gctx,
                                            train=False))
    np.testing.assert_allclose(outs["segment"], outs["ell"],
                               rtol=1e-4, atol=1e-4)


def test_appnp_matches_manual_propagation(dataset):
    """build_appnp == the hand-written APPNP recurrence
    Z_{k+1} = (1-a) * S Z_k + a * H computed directly from the CSR
    (S = D^-1/2 A D^-1/2, self edges pre-added by the fixture)."""
    from roc_tpu.models.appnp import build_appnp
    k, alpha = 3, 0.2
    model = build_appnp([dataset.in_dim, 16, dataset.num_classes],
                        k=k, alpha=alpha, dropout_rate=0.0)
    params = model.init_params(jax.random.PRNGKey(0))
    feats = jnp.asarray(dataset.features)
    gctx = make_graph_context(dataset, aggr_impl="segment")
    got = np.asarray(model.apply(params, feats, gctx, train=False))

    # manual: MLP then the propagation recurrence
    g = dataset.graph
    h = np.maximum(
        dataset.features @ np.asarray(params["linear_0"]), 0.0)
    h = h @ np.asarray(params["linear_1"])
    deg = np.asarray(g.in_degree, dtype=np.float64)
    dinv = 1.0 / np.sqrt(np.maximum(deg, 1.0))
    dst = np.repeat(np.arange(g.num_nodes), np.diff(g.row_ptr))
    z = h.astype(np.float64)
    for _ in range(k):
        s = np.zeros_like(z)
        np.add.at(s, dst, (z * dinv[:, None])[g.col_idx])
        z = (1 - alpha) * s * dinv[:, None] + alpha * h
    np.testing.assert_allclose(got, z, rtol=2e-4, atol=2e-4)


def test_appnp_converges_and_cli_validates(dataset):
    """APPNP trains to high accuracy on the homophilous fixture, the
    parameter count is propagation-depth-independent (decoupled
    predict-then-propagate), and bad --alpha values fail fast."""
    from roc_tpu.models.appnp import build_appnp
    m10 = build_appnp([dataset.in_dim, 24, dataset.num_classes],
                      k=10, alpha=0.1, dropout_rate=0.1)
    m2 = build_appnp([dataset.in_dim, 24, dataset.num_classes],
                     k=2, alpha=0.1, dropout_rate=0.1)
    p10 = m10.init_params(jax.random.PRNGKey(0))
    p2 = m2.init_params(jax.random.PRNGKey(0))
    assert {k_: v.shape for k_, v in p10.items()} == \
        {k_: v.shape for k_, v in p2.items()}
    t = Trainer(m10, dataset,
                TrainConfig(learning_rate=0.02, weight_decay=1e-4,
                            epochs=80, verbose=False))
    t.train()
    assert t.evaluate()["train_acc"] > 0.9
    with pytest.raises(ValueError, match="alpha"):
        build_appnp([12, 4], alpha=1.5)


def test_gcn2_deep_stack_converges(dataset):
    """GCNII's raison d'etre: an 8-propagation-layer stack still
    trains to high accuracy (initial residual + identity mapping
    prevent the oversmoothing a plain deep GCN suffers), and
    validation rejects mismatched hidden widths / degenerate knobs."""
    from roc_tpu.models.gcn2 import build_gcn2
    layers = [dataset.in_dim] + [24] * 8 + [dataset.num_classes]
    model = build_gcn2(layers, alpha=0.1, lam=0.5, dropout_rate=0.1)
    t = Trainer(model, dataset,
                TrainConfig(learning_rate=0.02, weight_decay=1e-4,
                            epochs=80, verbose=False))
    t.train()
    assert t.evaluate()["train_acc"] > 0.9
    with pytest.raises(ValueError, match="hidden widths"):
        build_gcn2([12, 16, 24, 3])
    with pytest.raises(ValueError, match="alpha"):
        build_gcn2([12, 16, 3], alpha=-0.1)
    with pytest.raises(ValueError, match="lam"):
        build_gcn2([12, 16, 3], lam=0.0)
    with pytest.raises(ValueError, match="hidden"):
        build_gcn2([12, 3])


def test_gcn2_matches_manual_recurrence(dataset):
    """build_gcn2 == the hand-written GCNII layer math on the CSR."""
    import math as _math
    from roc_tpu.models.gcn2 import build_gcn2
    alpha, lam = 0.2, 0.6
    model = build_gcn2([dataset.in_dim, 16, 16, dataset.num_classes],
                       alpha=alpha, lam=lam, dropout_rate=0.0)
    params = model.init_params(jax.random.PRNGKey(1))
    feats = jnp.asarray(dataset.features)
    gctx = make_graph_context(dataset, aggr_impl="segment")
    got = np.asarray(model.apply(params, feats, gctx, train=False))

    g = dataset.graph
    deg = np.asarray(g.in_degree, dtype=np.float64)
    dinv = 1.0 / np.sqrt(np.maximum(deg, 1.0))
    dst = np.repeat(np.arange(g.num_nodes), np.diff(g.row_ptr))

    def prop(z):
        s = np.zeros_like(z)
        np.add.at(s, dst, (z * dinv[:, None])[g.col_idx])
        return s * dinv[:, None]

    h0 = np.maximum(
        dataset.features @ np.asarray(params["linear_0"]), 0.0)
    t = h0
    for l in (1, 2):
        beta = _math.log(lam / l + 1.0)
        m = (1 - alpha) * prop(t) + alpha * h0
        t = np.maximum(
            (1 - beta) * m
            + beta * (m @ np.asarray(params[f"linear_{l}"])), 0.0)
    z = t @ np.asarray(params["linear_3"])
    np.testing.assert_allclose(got, z, rtol=2e-4, atol=2e-4)


def test_gin_learnable_eps(dataset):
    """learn_eps=True: zero-init scalar (GIN-0), updated by training,
    and at eps == 0 the forward equals plain aggregation (no self
    doubling)."""
    model = build_gin([dataset.in_dim, 16, dataset.num_classes],
                      dropout_rate=0.0, learn_eps=True)
    params = model.init_params(jax.random.PRNGKey(0))
    assert params["eps_0"].shape == ()
    assert float(params["eps_0"]) == 0.0
    # the algebra the docstring claims: at eps == 0 the layer output
    # is EXACTLY the aggregation (no self term) — pin the forward
    # against a hand-built model with the eps layer removed, sharing
    # the same linear params (scale_add consumes no PRNG key, so the
    # param names and values line up)
    from roc_tpu.models.builder import AGGR_SUM, Model
    from roc_tpu.ops.dense import AC_MODE_NONE, AC_MODE_RELU
    ref_model = Model(in_dim=dataset.in_dim)
    rt = ref_model.input()
    for dim in (16, dataset.num_classes):
        rt = ref_model.dropout(rt, 0.0)
        rt = ref_model.scatter_gather(rt, aggr=AGGR_SUM)
        rt = ref_model.linear(rt, dim, AC_MODE_RELU)
        rt = ref_model.linear(rt, dim, AC_MODE_NONE)
        if dim != dataset.num_classes:
            rt = ref_model.relu(rt)
    ref_model.softmax_cross_entropy(rt)
    gctx = make_graph_context(dataset, aggr_impl="ell")
    feats = jnp.asarray(dataset.features)
    got = model.apply(params, feats, gctx, train=False)
    ref = ref_model.apply(params, feats, gctx, train=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-6)
    cfg = TrainConfig(learning_rate=0.01, aggr_impl="ell",
                      verbose=False, eval_every=1 << 30)
    t = Trainer(model, dataset, cfg)
    loss0 = t.evaluate()["train_loss"]
    t.train(epochs=60)
    m = t.evaluate()
    # mechanics, not a convergence bar: GIN-0's zero-init self weight
    # is a much weaker inductive bias than the fixed eps=1 form on
    # this tiny fixture (which test_model_families_converge gates);
    # here we pin that the objective moves and eps is actually trained
    assert m["train_loss"] < 0.75 * loss0, (loss0, m["train_loss"])
    assert float(t.params["eps_0"]) != 0.0  # actually learned


def test_sage_pool_converges_and_validates(dataset):
    """Hamilton et al.'s max-pool aggregator: learned ReLU pre-pool
    transform + neighborhood MAX (the AGGR_MAX path's first real
    model consumer); bad option combos error up front."""
    model = build_sage([dataset.in_dim, 24, dataset.num_classes],
                       dropout_rate=0.0, aggregator="pool")
    # 'auto' must resolve to 'ell' via the shared model-driven impl
    # policy (sectioned/bdense have no MAX form)
    cfg = TrainConfig(learning_rate=0.01, weight_decay=1e-4,
                      aggr_impl="auto", verbose=False,
                      eval_every=1 << 30)
    t = Trainer(model, dataset, cfg)
    assert t.config.aggr_impl == "ell"
    t.train(epochs=80)
    m = t.evaluate()
    assert m["train_acc"] > 0.9, m
    with pytest.raises(ValueError, match="aggregator"):
        build_sage([4, 8, 2], aggregator="median")
    with pytest.raises(ValueError, match="use_norm"):
        build_sage([4, 8, 2], aggregator="pool", use_norm=True)
    # ring + MAX fails fast at trainer setup, before any table build
    from roc_tpu.parallel.distributed import DistributedTrainer
    with pytest.raises(NotImplementedError, match="ring"):
        DistributedTrainer(model, dataset, 4,
                           TrainConfig(aggr_impl="ell", halo="ring",
                                       verbose=False))


def test_max_aggregator_matches_numpy(dataset):
    g = dataset.graph
    feats = dataset.features
    # numpy reference
    want = np.zeros_like(feats)
    for v in range(g.num_nodes):
        srcs = g.col_idx[g.row_ptr[v]:g.row_ptr[v + 1]]
        if len(srcs):
            want[v] = feats[srcs].max(axis=0)
    for impl in ("segment", "ell"):
        gctx = make_graph_context(dataset, aggr_impl=impl)
        got = np.asarray(gctx.aggregate(jnp.asarray(feats), AGGR_MAX))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6,
                                   err_msg=impl)


def test_min_aggregator_matches_numpy(dataset):
    from roc_tpu.models.builder import AGGR_MIN
    g = dataset.graph
    feats = dataset.features
    want = np.zeros_like(feats)
    for v in range(g.num_nodes):
        srcs = g.col_idx[g.row_ptr[v]:g.row_ptr[v + 1]]
        if len(srcs):
            want[v] = feats[srcs].min(axis=0)
    for impl in ("segment", "ell"):
        gctx = make_graph_context(dataset, aggr_impl=impl)
        got = np.asarray(gctx.aggregate(jnp.asarray(feats), AGGR_MIN))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6,
                                   err_msg=impl)


@pytest.mark.parametrize("impl", ["sectioned", "bdense"])
def test_max_has_no_sectioned_or_bdense_form(dataset, impl):
    """A context built on ``sectioned`` / ``bdense`` tables refuses a
    MAX reduction by name: its edge list is a one-element stub, so
    falling through to the segment path would answer from one fake
    edge (and at scale materialize ``[E, F]``)."""
    gctx = make_graph_context(dataset, aggr_impl=impl,
                              bdense_min_fill=1)
    assert gctx.edge_src.shape == (1,)
    feats = jnp.asarray(dataset.features)
    for aggr in (AGGR_MAX, "min"):
        with pytest.raises(NotImplementedError, match=impl):
            gctx.aggregate(feats, aggr)
    assert gctx.aggregate(feats, "sum").shape == feats.shape


def test_checkpoint_roundtrip(dataset, tmp_path):
    from roc_tpu.utils.checkpoint import (checkpoint_trainer,
                                          restore_trainer)
    model = build_gcn([dataset.in_dim, 16, dataset.num_classes],
                      dropout_rate=0.0)
    cfg = TrainConfig(epochs=10, verbose=False, weight_decay=1e-4)
    t1 = Trainer(model, dataset, cfg)
    t1.train(epochs=6)
    path = str(tmp_path / "ckpt.npz")
    checkpoint_trainer(t1, path)
    t1.train(epochs=4)

    t2 = Trainer(model, dataset, cfg)
    restore_trainer(t2, path)
    assert t2.epoch == 6
    t2.train(epochs=4)
    # identical continuation (same PRNG key restored)
    for k in t1.params:
        np.testing.assert_allclose(np.asarray(t1.params[k]),
                                   np.asarray(t2.params[k]),
                                   rtol=1e-6, atol=1e-7)


def test_checkpoint_shape_mismatch_rejected(dataset, tmp_path):
    # a mismatched model raises the DISTINCT CheckpointCorrupt error
    # (resilience PR: the strict config fingerprint catches it before
    # any leaf is even compared)
    from roc_tpu.utils.checkpoint import (CheckpointCorrupt,
                                          checkpoint_trainer,
                                          restore_trainer)
    cfg = TrainConfig(epochs=1, verbose=False)
    t1 = Trainer(build_gcn([dataset.in_dim, 16, dataset.num_classes]),
                 dataset, cfg)
    path = str(tmp_path / "ckpt.npz")
    checkpoint_trainer(t1, path)
    t2 = Trainer(build_gcn([dataset.in_dim, 32, dataset.num_classes]),
                 dataset, cfg)
    with pytest.raises(CheckpointCorrupt, match="mismatch"):
        restore_trainer(t2, path)


def test_cli_smoke(tmp_path):
    """End-to-end CLI run on a synthetic dataset (CPU)."""
    ckpt = str(tmp_path / "cli_ckpt.npz")
    res = subprocess.run(
        [sys.executable, "-m", "roc_tpu.train.cli", "--cpu",
         "-layers", "12-8-3", "-e", "6", "-lr", "0.01", "-dropout", "0.2",
         "-decay", "0.0001", "--impl", "ell", "--checkpoint", ckpt],
        capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stderr
    assert "[INFER]" in res.stdout
    assert "checkpoint saved" in res.stderr
    # resume from the checkpoint
    res2 = subprocess.run(
        [sys.executable, "-m", "roc_tpu.train.cli", "--cpu",
         "-layers", "12-8-3", "-e", "10", "--resume", ckpt],
        capture_output=True, text=True, timeout=300)
    assert res2.returncode == 0, res2.stderr
    assert "resumed" in res2.stderr


def test_cli_bad_layers():
    res = subprocess.run(
        [sys.executable, "-m", "roc_tpu.train.cli", "--cpu",
         "-layers", "602"],
        capture_output=True, text=True, timeout=120)
    assert res.returncode == 2
    assert "layers" in res.stderr


def test_ell_max_budget_segmenting_exact(dataset):
    """aggregate_ell_max under a tiny transient budget (forcing the
    lax.scan row-segmented path on every bucket) must be exact — the
    MAX path honors the same memory bound as the sum path."""
    from roc_tpu.core.ell import ell_from_graph
    from roc_tpu.ops.aggregate import aggregate_ell_max
    g = dataset.graph
    feats = dataset.features
    table = ell_from_graph(g.row_ptr, g.col_idx, g.num_nodes)
    idx = tuple(jnp.asarray(a[0]) for a in table.idx)
    pos = jnp.asarray(table.row_pos[0])
    full = jnp.concatenate(
        [jnp.asarray(feats), jnp.zeros((1, feats.shape[1]))], axis=0)
    want = np.asarray(aggregate_ell_max(full, idx, pos, g.num_nodes))
    got = np.asarray(aggregate_ell_max(full, idx, pos, g.num_nodes,
                                       budget_elems=64))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


def test_explicit_segment_survives_max_model_resolution(dataset):
    """resolve_attention_impl must not override an explicitly requested
    aggr_impl='segment' for MAX/MIN models — _max_fwd has a real
    segment path (jax.ops.segment_max); only the chunked-sum impls are
    rerouted (ADVICE r3)."""
    from roc_tpu.train.trainer import resolve_attention_impl
    model = build_sage([dataset.in_dim, 8, dataset.num_classes],
                       dropout_rate=0.0, aggregator="pool")
    cfg = resolve_attention_impl(
        model, TrainConfig(aggr_impl="segment", verbose=False))
    assert cfg.aggr_impl == "segment"
    # the chunked-sum impls still reroute (they have no MAX form) and
    # the override is echoed even with verbose=False
    cfg = resolve_attention_impl(
        model, TrainConfig(aggr_impl="sectioned", verbose=False))
    assert cfg.aggr_impl == "ell"
    # and the segment path actually trains end to end
    t = Trainer(model, dataset,
                TrainConfig(aggr_impl="segment", verbose=False,
                            eval_every=1 << 30))
    assert t.config.aggr_impl == "segment"
    t.train(epochs=2)
