"""Model builder + single-device training: convergence on the synthetic
fixture (the reference's correctness-by-convergence strategy, SURVEY §4),
plus parity checks on the layer stack and aggregation-impl invariance."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from roc_tpu.core.graph import synthetic_dataset
from roc_tpu.models.gcn import build_gcn
from roc_tpu.train.trainer import TrainConfig, Trainer, make_graph_context


@pytest.fixture(scope="module")
def dataset():
    return synthetic_dataset(128, 8, in_dim=16, num_classes=4, seed=0)


def test_gcn_forward_shapes(dataset):
    model = build_gcn([dataset.in_dim, 32, dataset.num_classes])
    gctx = make_graph_context(dataset)
    params = model.init_params(jax.random.PRNGKey(0))
    logits = model.apply(params, jnp.asarray(dataset.features), gctx,
                         train=False)
    assert logits.shape == (dataset.graph.num_nodes, dataset.num_classes)
    assert np.isfinite(np.asarray(logits)).all()


def test_glorot_init_range(dataset):
    model = build_gcn([dataset.in_dim, 32, dataset.num_classes])
    params = model.init_params(jax.random.PRNGKey(0))
    w = np.asarray(params["linear_0"])
    s = np.sqrt(6.0 / (dataset.in_dim + 32))
    assert w.shape == (dataset.in_dim, 32)
    assert (np.abs(w) <= s).all()
    assert w.std() > 0.3 * s  # actually uniform, not degenerate


def test_residual_path_built():
    # >3 layer entries => residual linears are added (gnn.cc:86-90)
    m_small = build_gcn([8, 4, 3])
    m_deep = build_gcn([8, 16, 16, 3])
    n_lin_small = sum(1 for op in m_small._ops if op.kind == "linear")
    n_lin_deep = sum(1 for op in m_deep._ops if op.kind == "linear")
    assert n_lin_small == 2
    assert n_lin_deep == 6  # 3 main + 3 residual projections


def test_training_converges(dataset):
    model = build_gcn([dataset.in_dim, 32, dataset.num_classes],
                      dropout_rate=0.1)
    cfg = TrainConfig(learning_rate=0.01, weight_decay=1e-4,
                      epochs=60, verbose=False, eval_every=5)
    trainer = Trainer(model, dataset, cfg)
    history = trainer.train()
    first, last = history[0], history[-1]
    assert last["train_acc"] > 0.9
    assert last["test_acc"] > 0.75
    assert last["train_loss"] < first["train_loss"]


def test_aggr_impl_invariance(dataset):
    """segment, sectioned and ell produce the same logits (same
    weights, no dropout)."""
    model = build_gcn([dataset.in_dim, 32, dataset.num_classes])
    params = model.init_params(jax.random.PRNGKey(1))
    feats = jnp.asarray(dataset.features)
    logits = {}
    for impl in ("segment", "sectioned", "ell"):
        gctx = make_graph_context(dataset, aggr_impl=impl, chunk=256)
        logits[impl] = np.asarray(
            model.apply(params, feats, gctx, train=False))
    np.testing.assert_allclose(logits["segment"], logits["sectioned"],
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(logits["segment"], logits["ell"],
                               rtol=1e-4, atol=1e-4)


def test_symmetric_vjp_matches_autodiff(dataset):
    """The custom backward (reference kernel-reuse, valid for symmetric
    graphs) must equal exact autodiff through the forward."""
    import dataclasses
    model = build_gcn([dataset.in_dim, 16, dataset.num_classes])
    params = model.init_params(jax.random.PRNGKey(3))
    feats = jnp.asarray(dataset.features)
    labels = jnp.asarray(dataset.labels)
    mask = jnp.asarray(dataset.mask)
    gctx_sym = make_graph_context(dataset)
    gctx_exact = dataclasses.replace(gctx_sym, symmetric=False)

    def loss(p, gctx):
        l, _ = model.loss_fn(p, feats, labels, mask, gctx, train=False)
        return l

    g_sym = jax.grad(loss)(params, gctx_sym)
    g_exact = jax.grad(loss)(params, gctx_exact)
    for k in g_sym:
        np.testing.assert_allclose(np.asarray(g_sym[k]),
                                   np.asarray(g_exact[k]),
                                   rtol=1e-4, atol=1e-5)


def test_deterministic_training(dataset):
    model = build_gcn([dataset.in_dim, 16, dataset.num_classes])
    cfg = TrainConfig(epochs=5, verbose=False, seed=7)
    t1 = Trainer(model, dataset, cfg)
    t2 = Trainer(model, dataset, cfg)
    t1.train()
    t2.train()
    for k in t1.params:
        np.testing.assert_array_equal(np.asarray(t1.params[k]),
                                      np.asarray(t2.params[k]))


def test_lr_decay_schedule():
    from roc_tpu.train.optimizer import decayed_lr
    lr0 = float(decayed_lr(0.01, jnp.asarray(0), 0.97, 100))
    lr100 = float(decayed_lr(0.01, jnp.asarray(100), 0.97, 100))
    lr250 = float(decayed_lr(0.01, jnp.asarray(250), 0.97, 100))
    assert lr0 == pytest.approx(0.01, rel=1e-5)
    assert lr100 == pytest.approx(0.01 * 0.97, rel=1e-5)
    assert lr250 == pytest.approx(0.01 * 0.97 ** 2, rel=1e-5)


def test_adam_matches_reference_formula():
    """One Adam step on a scalar parameter, hand-computed with the
    reference recurrence (optimizer_kernel.cu:52-62, optimizer.cc:79-85)."""
    from roc_tpu.train.optimizer import AdamConfig, adam_init, adam_update
    params = {"w": jnp.asarray([2.0], dtype=jnp.float32)}
    grads = {"w": jnp.asarray([0.5], dtype=jnp.float32)}
    cfg = AdamConfig(weight_decay=0.1)
    st = adam_init(params)
    new_p, st2 = adam_update(params, grads, st, jnp.asarray(0.01), cfg)

    gt = 0.5 + 0.1 * 2.0
    mt = 0.1 * gt
    vt = 0.001 * gt * gt
    alpha_t = 0.01 * np.sqrt(1 - 0.999) / (1 - 0.9)
    want = 2.0 - alpha_t * mt / (np.sqrt(vt) + 1e-8)
    np.testing.assert_allclose(np.asarray(new_p["w"])[0], want, rtol=1e-6)


@pytest.mark.parametrize("family", ["gcn", "gcn_deep", "gcn2star"])
def test_remat_trains_identically(family):
    """remat must produce the same parameters as no-remat
    (checkpointing a run of ops changes memory, not math) — dropout on,
    so the recomputed runs draw the forward's own masks."""
    from roc_tpu.core.graph import synthetic_dataset
    from roc_tpu.models.gcn import build_gcn
    from roc_tpu.models.gcn2 import build_gcn2
    from roc_tpu.train.trainer import TrainConfig, Trainer
    ds = synthetic_dataset(200, 6, in_dim=12, num_classes=3, seed=9)
    build = {"gcn": lambda: build_gcn([12, 8, 3], dropout_rate=0.3),
             "gcn_deep": lambda: build_gcn([12, 8, 8, 8, 3],
                                           dropout_rate=0.3),
             "gcn2star": lambda: build_gcn2([12, 8, 8, 8, 3],
                                            dropout_rate=0.3,
                                            star=True)}[family]
    results = {}
    for remat in (False, True):
        cfg = TrainConfig(learning_rate=0.05, epochs=3,
                          eval_every=1 << 30, verbose=False,
                          symmetric=True, remat=remat)
        tr = Trainer(build(), ds, cfg)
        tr.train()
        results[remat] = tr.params
    for k in results[False]:
        np.testing.assert_allclose(np.asarray(results[False][k]),
                                   np.asarray(results[True][k]),
                                   rtol=1e-5, atol=1e-5)
