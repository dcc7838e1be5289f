"""Uniform-scan flat_sum consolidation + chunked output head
(ISSUE 7): forward+grad parity of the single-scan layout against the
ell/sectioned references across impl x halo rig configs, the MAX and
fused-weight variants, the resolve pass's edge-count auto-route (and
its idempotency), and the chunked classification head (values and dX
bit-identical; dW to fp32 roundoff).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from roc_tpu.core.graph import synthetic_dataset
from roc_tpu.models.builder import AGGR_MAX, Model
from roc_tpu.models.gcn import build_gcn
from roc_tpu.models.gin import build_gin
from roc_tpu.parallel.distributed import DistributedTrainer
from roc_tpu.train.trainer import (HEAD_CHUNK_ROWS, TrainConfig,
                                   Trainer, resolve_config,
                                   resolve_head_chunk)

REL = 1e-5


@pytest.fixture(scope="module")
def ds():
    return synthetic_dataset(num_nodes=256, avg_degree=6, in_dim=24,
                             num_classes=5, seed=3)


@pytest.fixture(scope="module", autouse=True)
def _release_executables():
    """This module compiles ~60 distinct trainer programs (parity
    matrices across impl x halo x parts); release the in-process
    executable/trace caches afterwards so the accumulated native JIT
    state doesn't destabilize the rest of a long single-process
    suite run."""
    yield
    jax.clear_caches()


def _rel_err(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b))
                 / max(1.0, np.max(np.abs(b))))


def _train(ds, model_fn, impl, parts=1, epochs=3, **cfg_kw):
    cfg = TrainConfig(verbose=False, symmetric=True, aggr_impl=impl,
                      dropout_rate=0.0, **cfg_kw)
    if parts > 1:
        tr = DistributedTrainer(model_fn(), ds, parts, cfg)
    else:
        tr = Trainer(model_fn(), ds, cfg)
    tr.train(epochs)
    m = tr.evaluate()
    return tr, m, np.asarray(tr.predict())


# ----------------------------------------------------- fwd+grad parity

@pytest.mark.parametrize("ref_impl", ["segment", "ell", "sectioned"])
def test_flat_sum_parity_single_device(ds, ref_impl):
    """3 trained epochs (forward AND gradients compound into the
    params) + logits: flat_sum vs each reference impl <= 1e-5."""
    mk = lambda: build_gcn([24, 16, 5], dropout_rate=0.0)
    t0, m0, p0 = _train(ds, mk, ref_impl)
    t1, m1, p1 = _train(ds, mk, "flat_sum")
    assert _rel_err(p1, p0) < REL
    for k in t0.params:
        assert _rel_err(t1.params[k], t0.params[k]) < REL, k
    assert abs(m1["train_loss"] - m0["train_loss"]) < 1e-3


@pytest.mark.parametrize("parts,halo", [(2, "gather"), (4, "gather"),
                                        (2, "ring")])
def test_flat_sum_parity_distributed(ds, parts, halo):
    """Across the halo axis: gather shards the flat tables; ring
    uploads empty sect stubs and the flat8 fields must stay None so
    the builder routes to ring_aggregate.  Either way P-part flat_sum
    training matches the single-device segment reference <= 1e-5 —
    params and original-order logits."""
    mk = lambda: build_gcn([24, 16, 5], dropout_rate=0.0)
    t0, _, p0 = _train(ds, mk, "segment")
    t1, _, p1 = _train(ds, mk, "flat_sum", parts=parts, halo=halo)
    assert _rel_err(p1, p0) < REL
    for k in t0.params:
        assert _rel_err(t1.params[k], t0.params[k]) < REL, k


def test_flat_sum_fused_weight_parity(ds):
    """aggr_fuse='on' bakes the D^-1/2 A D^-1/2 entries into the flat
    tables (FlatTables.w): fused flat_sum == fused sectioned == UNfused
    flat_sum (exact linear algebra), single-device and P=2."""
    mk = lambda: build_gcn([24, 16, 5], dropout_rate=0.0)
    _, _, p_sect = _train(ds, mk, "sectioned", aggr_fuse="on")
    t_f, _, p_f = _train(ds, mk, "flat_sum", aggr_fuse="on")
    _, _, p_off = _train(ds, mk, "flat_sum", aggr_fuse="off")
    # the fused model really did fuse, and the tables really exist
    assert t_f.model.num_fused_aggregates() > 0
    assert t_f.gctx.tables.w is not None
    assert _rel_err(p_f, p_sect) < REL
    assert _rel_err(p_f, p_off) < REL
    _, _, p_d = _train(ds, mk, "flat_sum", parts=2, aggr_fuse="on")
    assert _rel_err(p_d, p_f) < REL


def _build_max(dims):
    m = Model(dims[0])
    t = m.input()
    t = m.scatter_gather(t, AGGR_MAX)
    t = m.linear(t, dims[1])
    m.softmax_cross_entropy(t)
    return m


def test_flat_max_parity(ds):
    """The MAX variant (aggregate_flat_max: masked width-max + sorted
    scatter-max): matches the ELL MAX reference through training."""
    t0, _, p0 = _train(ds, lambda: _build_max([24, 5]), "ell")
    t1, _, p1 = _train(ds, lambda: _build_max([24, 5]), "flat_sum")
    assert _rel_err(p1, p0) < REL
    for k in t0.params:
        assert _rel_err(t1.params[k], t0.params[k]) < REL, k


def test_flat_sum_op_grad_parity(ds):
    """Direct op-level vjp: cotangents through aggregate_flat_sum ==
    through aggregate_segment (the exact-autodiff reference,
    symmetric=False so the custom vjp is NOT in play)."""
    mk = lambda: build_gcn([24, 16, 5], dropout_rate=0.0)
    outs = {}
    for impl in ("segment", "flat_sum"):
        cfg = TrainConfig(verbose=False, symmetric=False,
                          aggr_impl=impl, dropout_rate=0.0)
        tr = Trainer(mk(), ds, cfg)
        x = jnp.asarray(np.random.RandomState(0).rand(256, 24),
                        jnp.float32)
        g = jax.grad(lambda v: tr.gctx.aggregate_sum(v).sum() ** 2)(x)
        outs[impl] = np.asarray(g)
    assert _rel_err(outs["flat_sum"], outs["segment"]) < REL


# ------------------------------------------------- resolve auto-route

def test_auto_route_past_sectioned_window(monkeypatch):
    """resolve_auto_impl: sectioned keeps its measured window; the
    ell-bound region routes to flat_sum once num_edges crosses
    FLAT_SUM_MIN_EDGES (and never without edge information)."""
    from roc_tpu.core import ell as E
    lo, hi = E.SECTION_ROWS_DEFAULT, E.SECTIONED_MAX_ROWS
    monkeypatch.setenv("ROC_TPU_DEVICE_KIND", "TPU v5 lite")
    # inside the sectioned window: unchanged
    assert E.resolve_auto_impl(233_000, num_edges=10 ** 9) == \
        "sectioned"
    # past the window's out_rows bound with huge E: flat_sum
    assert E.resolve_auto_impl(2_450_000,
                               num_edges=E.FLAT_SUM_MIN_EDGES) == \
        "flat_sum"
    # past the window, small E: the per-bucket unroll is cheap — ell
    assert E.resolve_auto_impl(2_450_000, num_edges=10 ** 6) == "ell"
    # no edge info (legacy callers): the old sectioned/ell split
    assert E.resolve_auto_impl(2_450_000) == "ell"
    assert lo < hi  # window sanity (the constants the cases rely on)


def test_auto_route_resolves_in_config_and_is_idempotent(
        ds, monkeypatch):
    """With the threshold lowered to rig scale, aggr_impl='auto'
    resolves to flat_sum through THE resolve pass, and re-resolving
    the resolved config is a fixpoint (the auditor's idempotency
    contract holds with the new route)."""
    from roc_tpu.core import ell as E
    monkeypatch.setattr(E, "FLAT_SUM_MIN_EDGES", 100)
    cfg = TrainConfig(verbose=False, symmetric=True,
                      aggr_impl="auto", dropout_rate=0.0)
    model = build_gin([24, 16, 5], dropout_rate=0.0)
    m1, c1, _ = resolve_config(model, ds, cfg)
    assert c1.aggr_impl == "flat_sum"
    m2, c2, _ = resolve_config(m1, ds, c1)
    assert c2 == c1 and m2 is m1
    # MAX models route through resolve_attention_impl to flat_sum too
    cfg_max = TrainConfig(verbose=False, symmetric=True,
                          aggr_impl="auto", dropout_rate=0.0)
    _, c3, _ = resolve_config(_build_max([24, 5]), ds, cfg_max)
    assert c3.aggr_impl == "flat_sum"
    _, c4, _ = resolve_config(_build_max([24, 5]), ds, c3)
    assert c4 == c3


# ----------------------------------------------- chunked output head

def test_resolve_head_chunk():
    c = lambda v: TrainConfig(head_chunk=v)
    # auto: off below the threshold, HEAD_CHUNK_ROWS past it
    assert resolve_head_chunk(c("auto"), 1000) == 0
    assert resolve_head_chunk(c("auto"), 1 << 22) == HEAD_CHUNK_ROWS
    # explicit: literal, 0 = off, >= rows degenerates to off
    assert resolve_head_chunk(c(4096), 1 << 20) == 4096
    assert resolve_head_chunk(c(0), 1 << 20) == 0
    assert resolve_head_chunk(c(1 << 21), 1 << 20) == 0
    with pytest.raises(ValueError):
        resolve_head_chunk(c(-1), 1 << 20)
    with pytest.raises(ValueError):
        resolve_head_chunk(c("banana"), 1 << 20)


def test_linear_chunked_bit_identical():
    """ops/dense.linear_chunked == linear exactly (each output row's
    dot product is unchanged), including a ragged tail block and the
    fused activation, values AND gradients."""
    from roc_tpu.ops.dense import linear, linear_chunked
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(300, 24), jnp.float32)
    w = jnp.asarray(rng.randn(24, 7), jnp.float32)
    for act in ("none", "relu"):
        y0 = linear(x, w, act)
        y1 = linear_chunked(x, w, act, block=128)
        np.testing.assert_array_equal(np.asarray(y0), np.asarray(y1))
    # dW sums the row axis blockwise — a different (but equally
    # valid) fp reduction order than the one-matmul reference
    g0 = jax.grad(lambda ww: linear(x, ww, "none").sum())(w)
    g1 = jax.grad(lambda ww: linear_chunked(
        x, ww, "none", block=128).sum())(w)
    np.testing.assert_allclose(np.asarray(g0), np.asarray(g1),
                               rtol=1e-5)
    # block >= rows short-circuits to the plain matmul
    y2 = linear_chunked(x, w, "none", block=512)
    np.testing.assert_array_equal(np.asarray(y2),
                                  np.asarray(linear(x, w, "none")))


def test_head_chunk_training_parity(ds):
    """End-to-end: a forced head_chunk trains to the SAME params and
    logits as the unchunked head (dropout on — the RNG stream is
    untouched because chunking only rewrites the loss-op linear)."""
    def run(hc):
        cfg = TrainConfig(verbose=False, symmetric=True,
                          aggr_impl="segment", dropout_rate=0.5,
                          head_chunk=hc)
        tr = Trainer(build_gcn([24, 16, 5], dropout_rate=0.5), ds,
                     cfg)
        tr.train(3)
        return tr, np.asarray(tr.predict())
    t0, p0 = run(0)
    t1, p1 = run(64)
    assert t1.gctx.head_chunk == 64
    np.testing.assert_allclose(p1, p0, rtol=0, atol=1e-5)
    for k in t0.params:
        np.testing.assert_allclose(np.asarray(t1.params[k]),
                                   np.asarray(t0.params[k]),
                                   rtol=0, atol=1e-6, err_msg=k)


def test_head_chunk_distributed_parity(ds):
    """The distributed step carries head_chunk through _gctx: a P=2
    run with a forced chunk matches the unchunked P=2 run exactly."""
    def run(hc):
        cfg = TrainConfig(verbose=False, symmetric=True,
                          aggr_impl="flat_sum", dropout_rate=0.0,
                          head_chunk=hc)
        tr = DistributedTrainer(
            build_gcn([24, 16, 5], dropout_rate=0.0), ds, 2, cfg)
        tr.train(2)
        return np.asarray(tr.predict())
    p0 = run(0)
    p1 = run(32)
    np.testing.assert_allclose(p1, p0, rtol=0, atol=1e-5)


def test_head_chunk_compiles_scan_program(ds):
    """The chunked head really is a scan in the step: the chunked
    config's train-step jaxpr gains exactly the head's forward scan
    (the [block, H] @ [H, C] body) plus its grad-transpose scan
    (value_and_grad differentiates through lax.scan), while the
    unchunked segment-impl step contains no scans at all."""
    from test_programspace import _scan_shapes

    def shapes(hc):
        cfg = TrainConfig(verbose=False, symmetric=True,
                          aggr_impl="segment", dropout_rate=0.0,
                          head_chunk=hc)
        tr = Trainer(build_gcn([24, 16, 5], dropout_rate=0.0), ds,
                     cfg)
        lr = jnp.asarray(0.01, jnp.float32)
        return _scan_shapes(jax.make_jaxpr(tr._train_step._jit)(
            tr.params, tr.opt_state, tr.key, lr, tr.feats,
            tr.labels, tr.mask, tr.gctx))
    assert not shapes(0)
    assert len(shapes(64)) == 2
