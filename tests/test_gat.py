"""GAT attention aggregation + model family.

The reference has no attention model (sum-only aggregation,
``scattergather_kernel.cu:20-76``); GAT is the framework extension.
Tests: the ELL edge softmax against a dense numpy reference, padding /
zero-degree handling, the budget-segmented path, convergence (SURVEY
§4's correctness-by-convergence standard), the SPMD step, and the
trainer's forced-ell override.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from roc_tpu.core.ell import ell_from_graph
from roc_tpu.core.graph import synthetic_dataset
from roc_tpu.models.gat import build_gat
from roc_tpu.ops.attention import gat_aggregate_ell
from roc_tpu.train.trainer import TrainConfig, Trainer


@pytest.fixture(scope="module")
def dataset():
    return synthetic_dataset(128, 6, in_dim=16, num_classes=4, seed=0)


def dense_gat_reference(adj, h, a_src, a_dst, neg_slope=0.2):
    """O(V^2) numpy reference: exact additive-attention aggregation."""
    V, F = h.shape
    s = h @ a_src
    d = h @ a_dst
    out = np.zeros_like(h)
    for i in range(V):
        nbrs = np.flatnonzero(adj[:, i])  # adj[src, dst]
        if nbrs.size == 0:
            continue
        e = s[nbrs] + d[i]
        e = np.where(e > 0, e, neg_slope * e)
        e = e - e.max()
        w = np.exp(e)
        alpha = w / w.sum()
        out[i] = (alpha[:, None] * h[nbrs]).sum(axis=0)
    return out


def _adj_from_graph(g):
    V = g.num_nodes
    adj = np.zeros((V, V), dtype=bool)
    dst = np.repeat(np.arange(V), np.diff(g.row_ptr))
    adj[g.col_idx, dst] = True
    return adj


@pytest.mark.parametrize("budget", [1 << 24, 512])
def test_gat_aggregate_matches_dense_reference(dataset, budget):
    """ELL edge softmax == the dense O(V^2) computation, including
    with the scan-segmented path forced via a tiny budget."""
    g = dataset.graph
    V, F = g.num_nodes, 8
    rng = np.random.RandomState(0)
    h = rng.randn(V, F).astype(np.float32)
    a_src = rng.randn(F).astype(np.float32) * 0.3
    a_dst = rng.randn(F).astype(np.float32) * 0.3

    table = ell_from_graph(g.row_ptr, g.col_idx, V)
    idx = tuple(jnp.asarray(a[0]) for a in table.idx)
    rid = tuple(jnp.asarray(a[0]) for a in table.row_id)
    pos = jnp.asarray(table.row_pos[0])

    full = jnp.concatenate(
        [jnp.asarray(h), jnp.zeros((1, F), jnp.float32)])
    s_full = (full @ jnp.asarray(a_src))[:, None]
    d_local = jnp.concatenate(
        [jnp.asarray(h @ a_dst), jnp.zeros((1,), jnp.float32)])[:, None]
    out = gat_aggregate_ell(full, s_full, d_local, idx, rid, pos, V,
                            budget_elems=budget)
    ref = dense_gat_reference(_adj_from_graph(g), h, a_src, a_dst)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4,
                               atol=2e-5)


_COTANGENTS = {}


@pytest.mark.parametrize("which", ["out", "x", "a_src", "a_dst"])
@pytest.mark.parametrize("budget", [1 << 24, 512])
@pytest.mark.parametrize("heads,dh", [(1, 6), (4, 6), (2, 120)])
def test_symmetric_backward_matches_autodiff(dataset, heads, dh, budget,
                                             which, monkeypatch):
    """One attention op through ``GraphContext.gat_attention``: on a
    symmetric graph the hand-written backward (a second pass over the
    forward's tables) returns autodiff's cotangents, whole buckets and
    scan-segmented ones; 2 x 120 runs its tiles at head width 128."""
    import functools
    from roc_tpu.ops import attention
    from roc_tpu.train.trainer import make_graph_context
    key = (heads, dh, budget)
    if key not in _COTANGENTS:
        assert dataset.graph.is_symmetric()
        assert attention._lane_head_width(heads, dh) == (
            128 if dh == 120 else dh)
        V = dataset.graph.num_nodes
        rng = np.random.RandomState(heads)
        x = jnp.asarray(rng.randn(V, heads * dh), jnp.float32)
        a_src = jnp.asarray(rng.randn(heads, dh), jnp.float32)
        a_dst = jnp.asarray(rng.randn(heads, dh), jnp.float32)
        g = jnp.asarray(rng.randn(V, heads * dh), jnp.float32)
        for name in ("gat_aggregate_ell", "gat_ell_forward",
                     "gat_ell_backward"):
            monkeypatch.setattr(attention, name, functools.partial(
                getattr(attention, name), budget_elems=budget))
        got = {}
        for symmetric in (True, False):
            gctx = make_graph_context(dataset, "ell", symmetric=symmetric)
            out, vjp = jax.vjp(gctx.gat_attention, x, a_src, a_dst)
            got[symmetric] = dict(zip(("out", "x", "a_src", "a_dst"),
                                      (out,) + vjp(g)))
        _COTANGENTS[key] = got
    got = _COTANGENTS[key]
    want = np.asarray(got[False][which])
    np.testing.assert_allclose(np.asarray(got[True][which]), want,
                               rtol=2e-5, atol=2e-6 * np.abs(want).max())


@pytest.mark.parametrize("heads,dh,want", [
    (3, 250, 256), (2, 120, 128), (4, 128, 128), (1, 40, 40), (1, 250, 250),
    (8, 8, 8), (3, 10, 10), (4, 100, 128), (4, 90, 90)])
def test_lane_head_width(heads, dh, want):
    """A head is widened to a lane multiple only where the row's heads
    then take the lane tiles the row already took."""
    from roc_tpu.ops.attention import _lane_head_width
    assert _lane_head_width(heads, dh) == want


def test_gat_zero_degree_rows_are_zero():
    """A row with no in-edges aggregates to exactly 0 (the sum path's
    convention), not NaN from an empty softmax."""
    from roc_tpu.core.graph import from_edge_list
    # node 2 has no in-edges
    g = from_edge_list(np.array([0, 1]), np.array([1, 0]), 3)
    table = ell_from_graph(g.row_ptr, g.col_idx, 3)
    idx = tuple(jnp.asarray(a[0]) for a in table.idx)
    rid = tuple(jnp.asarray(a[0]) for a in table.row_id)
    pos = jnp.asarray(table.row_pos[0])
    h = jnp.asarray(np.random.RandomState(0).randn(3, 4),
                    dtype=jnp.float32)
    full = jnp.concatenate([h, jnp.zeros((1, 4), jnp.float32)])
    s_full = (jnp.ones((4,), jnp.float32) @ full.T)[:, None]
    d_local = jnp.zeros((4, 1), jnp.float32)
    out = gat_aggregate_ell(full, s_full, d_local, idx, rid, pos, 3)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_array_equal(np.asarray(out)[2], 0.0)


def test_multihead_equals_per_slice_single_head(dataset):
    """K-head attention == K independent single-head attentions on the
    K feature slices, concatenated — the defining property of the
    concat form."""
    g = dataset.graph
    V, K, dh = g.num_nodes, 4, 5
    F = K * dh
    rng = np.random.RandomState(1)
    h = rng.randn(V, F).astype(np.float32)
    a_src = rng.randn(K, dh).astype(np.float32) * 0.3
    a_dst = rng.randn(K, dh).astype(np.float32) * 0.3

    table = ell_from_graph(g.row_ptr, g.col_idx, V)
    idx = tuple(jnp.asarray(a[0]) for a in table.idx)
    rid = tuple(jnp.asarray(a[0]) for a in table.row_id)
    pos = jnp.asarray(table.row_pos[0])

    def run(hh, asrc, adst):
        k = asrc.shape[0]
        full = jnp.concatenate(
            [jnp.asarray(hh),
             jnp.zeros((1, hh.shape[1]), jnp.float32)])
        fr = full.reshape(full.shape[0], k, -1)
        s = jnp.einsum("gkd,kd->gk", fr, jnp.asarray(asrc))
        d = jnp.einsum("vkd,kd->vk",
                       jnp.asarray(hh).reshape(V, k, -1),
                       jnp.asarray(adst))
        dl = jnp.concatenate([d, jnp.zeros((1, k), jnp.float32)])
        return np.asarray(gat_aggregate_ell(full, s, dl, idx, rid,
                                            pos, V))

    multi = run(h, a_src, a_dst)
    for k in range(K):
        sl = slice(k * dh, (k + 1) * dh)
        single = run(h[:, sl], a_src[k:k + 1], a_dst[k:k + 1])
        np.testing.assert_allclose(multi[:, sl], single, rtol=1e-5,
                                   atol=1e-6)


def test_multihead_model_converges(dataset):
    model = build_gat([dataset.in_dim, 16, dataset.num_classes],
                      dropout_rate=0.0, heads=4)
    assert model.init_params(
        jax.random.PRNGKey(0))["gat_0_src"].shape == (4, 4)
    cfg = TrainConfig(aggr_impl="ell", verbose=False,
                      eval_every=1 << 30)
    tr = Trainer(model, dataset, cfg)
    tr.train(epochs=60)
    assert tr.evaluate()["train_acc"] > 0.9


def test_gat_heads_must_divide_dim():
    from roc_tpu.models.builder import Model
    m = Model(in_dim=8)
    t = m.input()
    t = m.linear(t, 10)
    with pytest.raises(ValueError, match="divisible"):
        m.gat_attention(t, heads=4)


def test_gat_model_converges(dataset):
    """Correctness by convergence on the synthetic fixture; also pins
    the trainer's attention override (segment -> ell) and that grads
    reach the attention vectors."""
    model = build_gat([dataset.in_dim, 16, dataset.num_classes],
                      dropout_rate=0.0)
    assert model.uses_attention()
    cfg = TrainConfig(aggr_impl="segment", verbose=False,
                      eval_every=1 << 30, learning_rate=0.01)
    tr = Trainer(model, dataset, cfg)
    assert tr.config.aggr_impl == "ell"       # forced for attention
    p0 = np.asarray(tr.params["gat_0_src"]).copy()
    tr.train(epochs=60)
    m = tr.evaluate()
    assert m["train_acc"] > 0.9, m
    assert not np.allclose(np.asarray(tr.params["gat_0_src"]), p0)


def test_gat_distributed_matches_single(dataset):
    """SPMD GAT: 4-part shard_map step converges and its eval agrees
    with a single-device trainer given the same params."""
    from roc_tpu.parallel.distributed import DistributedTrainer
    # heads=4: the multi-head reshape/einsum must agree with the
    # padded-part row order under shard_map, not just single-device
    model = build_gat([dataset.in_dim, 16, dataset.num_classes],
                      dropout_rate=0.0, heads=4)
    cfg = TrainConfig(aggr_impl="ell", verbose=False, chunk=64,
                      eval_every=1 << 30)
    dt = DistributedTrainer(model, dataset, 4, cfg)
    tr = Trainer(model, dataset, cfg)
    tr.params = jax.device_get(dt.params)
    md = dt.evaluate()
    ms = tr.evaluate()
    assert md["train_loss"] == pytest.approx(ms["train_loss"],
                                             rel=1e-4)
    dt.train(epochs=60)
    assert dt.evaluate()["train_acc"] > 0.9


def test_gat_mixed_precision(dataset):
    """Mixed mode: bf16 compute with the fp32 softmax inside the
    attention op — finite, converging."""
    model = build_gat([dataset.in_dim, 16, dataset.num_classes],
                      dropout_rate=0.0)
    cfg = TrainConfig(aggr_impl="ell", verbose=False,
                      eval_every=1 << 30,
                      compute_dtype=jnp.bfloat16)
    tr = Trainer(model, dataset, cfg)
    tr.train(epochs=60)
    m = tr.evaluate()
    assert np.isfinite(m["train_loss"])
    assert m["train_acc"] > 0.85, m


def test_gat_streamable_head(dataset):
    """GAT's first layer (input -> dropout -> linear) qualifies for
    the host-feature streaming tier; training must work with the
    features never device-resident."""
    model = build_gat([dataset.in_dim, 16, dataset.num_classes],
                      dropout_rate=0.5)
    assert model.streamable_head() is not None
    tr = Trainer(model, dataset,
                 TrainConfig(aggr_impl="ell", verbose=False,
                             eval_every=1 << 30, features="host"))
    assert tr.feats is None          # never uploaded whole
    tr.train(epochs=3)
    m = tr.evaluate()
    assert np.isfinite(m["train_loss"])


def test_gat_ring_rejected_at_setup(dataset):
    """halo='ring' + attention fails fast at trainer construction,
    before any ring-table build."""
    from roc_tpu.parallel.distributed import DistributedTrainer
    model = build_gat([dataset.in_dim, 16, dataset.num_classes])
    cfg = TrainConfig(aggr_impl="ell", halo="ring", verbose=False)
    with pytest.raises(NotImplementedError, match="ring"):
        DistributedTrainer(model, dataset, 4, cfg)


def test_gat_rejects_sectioned_tables():
    """A GraphContext without ELL tables raises the actionable error
    rather than silently mis-aggregating."""
    from roc_tpu.models.tables import SectionedTables
    from roc_tpu.models.builder import GraphContext
    gctx = GraphContext(tables=SectionedTables(idx=(), sub_dst=()),
                        in_degree=jnp.zeros(4, jnp.int32),
                        num_rows=4, gathered_rows=4,
                        aggr_impl="sectioned")
    with pytest.raises(NotImplementedError, match="ELL"):
        gctx.gat_attention(jnp.zeros((4, 2)), jnp.zeros(2),
                           jnp.zeros(2))


# ---------------------------------------------------------------- flat8

def _flat8_tables(g, seg_rows=64):
    from roc_tpu.core.ell import sectioned_from_graph
    sect = sectioned_from_graph(g.row_ptr, g.col_idx, g.num_nodes,
                                src_rows=g.num_nodes,
                                section_rows=g.num_nodes,
                                seg_rows=seg_rows)
    assert len(sect.idx) == 1
    return jnp.asarray(sect.idx[0]), jnp.asarray(sect.sub_dst[0])


def test_flat8_matches_dense_reference(dataset):
    """The uniform width-8 attention layout (the large-graph compile
    path) == the dense O(V^2) computation, with several scan chunks
    forced via a small seg_rows."""
    from roc_tpu.ops.attention import gat_aggregate_flat8
    g = dataset.graph
    V, F = g.num_nodes, 8
    rng = np.random.RandomState(0)
    h = rng.randn(V, F).astype(np.float32)
    a_src = rng.randn(F).astype(np.float32) * 0.3
    a_dst = rng.randn(F).astype(np.float32) * 0.3
    f8i, f8d = _flat8_tables(g, seg_rows=64)
    assert f8i.shape[0] > 1, "need multiple chunks to test the scan"
    full = jnp.concatenate(
        [jnp.asarray(h), jnp.zeros((1, F), jnp.float32)])
    s_full = (full @ jnp.asarray(a_src))[:, None]
    d_local = jnp.concatenate(
        [jnp.asarray(h @ a_dst), jnp.zeros((1,), jnp.float32)])[:, None]
    out = gat_aggregate_flat8(full, s_full, d_local, f8i, f8d, V)
    ref = dense_gat_reference(_adj_from_graph(g), h, a_src, a_dst)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4,
                               atol=2e-5)


def test_flat8_multihead_matches_bucket_path(dataset):
    """flat8 == the bucket path on multi-head inputs (same numerics,
    different reduction structure), and its gradients match too."""
    from roc_tpu.ops.attention import (gat_aggregate_ell,
                                       gat_aggregate_flat8)
    g = dataset.graph
    V, K, dh = g.num_nodes, 4, 5
    F = K * dh
    rng = np.random.RandomState(3)
    h = rng.randn(V, F).astype(np.float32)
    a_src = rng.randn(K, dh).astype(np.float32) * 0.3
    a_dst = rng.randn(K, dh).astype(np.float32) * 0.3
    table = ell_from_graph(g.row_ptr, g.col_idx, V)
    idx = tuple(jnp.asarray(a[0]) for a in table.idx)
    rid = tuple(jnp.asarray(a[0]) for a in table.row_id)
    pos = jnp.asarray(table.row_pos[0])
    f8i, f8d = _flat8_tables(g, seg_rows=64)

    def prep(hh):
        full = jnp.concatenate(
            [hh, jnp.zeros((1, F), jnp.float32)])
        fr = full.reshape(full.shape[0], K, dh)
        s = jnp.einsum("gkd,kd->gk", fr, jnp.asarray(a_src))
        d = jnp.einsum("vkd,kd->vk", hh.reshape(V, K, dh),
                       jnp.asarray(a_dst))
        dl = jnp.concatenate([d, jnp.zeros((1, K), jnp.float32)])
        return full, s, dl

    def via_ell(hh):
        full, s, dl = prep(hh)
        return gat_aggregate_ell(full, s, dl, idx, rid, pos, V)

    def via_flat8(hh):
        full, s, dl = prep(hh)
        return gat_aggregate_flat8(full, s, dl, f8i, f8d, V)

    hj = jnp.asarray(h)
    np.testing.assert_allclose(np.asarray(via_flat8(hj)),
                               np.asarray(via_ell(hj)),
                               rtol=2e-4, atol=2e-5)
    g_ell = jax.grad(lambda x: jnp.sum(via_ell(x) ** 2))(hj)
    g_f8 = jax.grad(lambda x: jnp.sum(via_flat8(x) ** 2))(hj)
    np.testing.assert_allclose(np.asarray(g_f8), np.asarray(g_ell),
                               rtol=2e-3, atol=2e-4)


def test_flat8_dh_chunked_matches_fused(dataset):
    """The dh-chunked numerator (the products-scale OOM fix:
    resolve_dh_chunk) is element-for-element the SAME math as the
    fused pass2 — identical w, identical per-slice einsum reduction
    order, identical scatter-add order — so values match exactly and
    gradients match to fp32 tolerance.  (Values are NOT asserted
    bit-exact: XLA lowers the per-slice einsum differently for
    non-dividing widths — measured <=3e-7 drift.)"""
    from roc_tpu.ops.attention import (gat_aggregate_flat8,
                                       resolve_dh_chunk)
    g = dataset.graph
    V, K, dh = g.num_nodes, 2, 6
    F = K * dh
    rng = np.random.RandomState(7)
    h = rng.randn(V, F).astype(np.float32)
    a_src = rng.randn(K, dh).astype(np.float32) * 0.3
    a_dst = rng.randn(K, dh).astype(np.float32) * 0.3
    f8i, f8d = _flat8_tables(g, seg_rows=64)

    def run(hh, dh_chunk):
        full = jnp.concatenate([hh, jnp.zeros((1, F), jnp.float32)])
        fr = full.reshape(full.shape[0], K, dh)
        s = jnp.einsum("gkd,kd->gk", fr, jnp.asarray(a_src))
        d = jnp.einsum("vkd,kd->vk", hh.reshape(V, K, dh),
                       jnp.asarray(a_dst))
        dl = jnp.concatenate([d, jnp.zeros((1, K), jnp.float32)])
        return gat_aggregate_flat8(full, s, dl, f8i, f8d, V,
                                   dh_chunk=dh_chunk)

    hj = jnp.asarray(h)
    fused = run(hj, None)
    for dc in (1, 4, 5, dh):  # incl. a non-dividing width and ==dh
        np.testing.assert_allclose(np.asarray(run(hj, dc)),
                                   np.asarray(fused),
                                   rtol=1e-6, atol=1e-6)
    g_fused = jax.grad(lambda x: jnp.sum(run(x, None) ** 2))(hj)
    g_chunk = jax.grad(lambda x: jnp.sum(run(x, 4) ** 2))(hj)
    np.testing.assert_allclose(np.asarray(g_chunk),
                               np.asarray(g_fused),
                               rtol=1e-6, atol=1e-6)
    # the resolver: small graphs stay fused; at products scale the
    # per-chunk carry must actually fit the budget (not just split)
    assert resolve_dh_chunk(1000, 1, 256) is None
    dc = resolve_dh_chunk(2_449_029, 1, 256)
    assert dc is not None and dc < 256
    assert (2_449_030 * 1 * dc * 4) <= (768 << 20)


def test_flat8_zero_degree_rows_are_zero():
    from roc_tpu.core.graph import from_edge_list
    from roc_tpu.ops.attention import gat_aggregate_flat8
    g = from_edge_list(np.array([0, 1]), np.array([1, 0]), 3)
    f8i, f8d = _flat8_tables(g, seg_rows=8)
    h = jnp.asarray(np.random.RandomState(0).randn(3, 4),
                    dtype=jnp.float32)
    full = jnp.concatenate([h, jnp.zeros((1, 4), jnp.float32)])
    s_full = (jnp.ones((4,), jnp.float32) @ full.T)[:, None]
    d_local = jnp.zeros((4, 1), jnp.float32)
    out = gat_aggregate_flat8(full, s_full, d_local, f8i, f8d, 3)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_array_equal(np.asarray(out)[2], 0.0)


def test_flat8_end_to_end_and_resolver(dataset):
    """aggr_impl='attn_flat8' trains a GAT end to end to the same
    params as 'ell' (dropout 0 => identical RNG-free paths), and the
    resolver routes big-E attention configs to it automatically."""
    from roc_tpu.train.trainer import (ATTN_FLAT8_MIN_EDGES,
                                       resolve_attention_impl)
    params = {}
    for impl in ("ell", "attn_flat8"):
        model = build_gat([dataset.in_dim, 8, dataset.num_classes],
                          dropout_rate=0.0)
        cfg = TrainConfig(learning_rate=0.02, aggr_impl=impl,
                          verbose=False, eval_every=1 << 30)
        tr = Trainer(model, dataset, cfg)
        tr.train(epochs=3)
        params[impl] = tr.params
    for k in params["ell"]:
        np.testing.assert_allclose(np.asarray(params["ell"][k]),
                                   np.asarray(params["attn_flat8"][k]),
                                   rtol=2e-3, atol=2e-4)

    model = build_gat([dataset.in_dim, 8, dataset.num_classes])
    # small graph: stays on the bucket path
    cfg = resolve_attention_impl(
        model, TrainConfig(aggr_impl="auto", verbose=False), dataset)
    assert cfg.aggr_impl == "ell"
    # big-E graph: routed to flat8 (threshold patched to the fixture)
    import roc_tpu.train.trainer as trmod
    orig = trmod.ATTN_FLAT8_MIN_EDGES
    try:
        trmod.ATTN_FLAT8_MIN_EDGES = dataset.graph.num_edges
        cfg = resolve_attention_impl(
            model, TrainConfig(aggr_impl="auto", verbose=False),
            dataset)
        assert cfg.aggr_impl == "attn_flat8"
    finally:
        trmod.ATTN_FLAT8_MIN_EDGES = orig
    # MAX/MIN models must refuse the attention-only layout
    from roc_tpu.models.sage import build_sage
    pool = build_sage([dataset.in_dim, 8, dataset.num_classes],
                      aggregator="pool")
    with pytest.raises(NotImplementedError, match="attention-only"):
        resolve_attention_impl(
            pool, TrainConfig(aggr_impl="attn_flat8"), dataset)


def test_attn_flat8_rejected_for_sum_models(dataset):
    """A sum-only model with aggr_impl='attn_flat8' fails at resolve
    time, before any table build."""
    from roc_tpu.models.gcn import build_gcn
    from roc_tpu.train.trainer import resolve_attention_impl
    gcn = build_gcn([dataset.in_dim, 8, dataset.num_classes])
    with pytest.raises(NotImplementedError, match="attention-only"):
        resolve_attention_impl(
            gcn, TrainConfig(aggr_impl="attn_flat8"), dataset)


def test_gat_distributed_flat8_matches_ell(dataset):
    """Distributed attn_flat8 (single-section uniform tables over
    gathered coordinates, VERDICT r4 weak #3) must reproduce the
    distributed ELL-bucket attention exactly — same model, same seed,
    table layout is the only difference."""
    from roc_tpu.parallel.distributed import DistributedTrainer
    model = build_gat([dataset.in_dim, 16, dataset.num_classes],
                      dropout_rate=0.0, heads=2)
    kw = dict(verbose=False, chunk=64, eval_every=1 << 30,
              learning_rate=0.05)
    te = DistributedTrainer(model, dataset, 4,
                            TrainConfig(aggr_impl="ell", **kw))
    tf = DistributedTrainer(model, dataset, 4,
                            TrainConfig(aggr_impl="attn_flat8", **kw))
    me, mf = te.evaluate(), tf.evaluate()
    assert mf["train_loss"] == pytest.approx(me["train_loss"],
                                             rel=1e-5)
    te.train(epochs=5)
    tf.train(epochs=5)
    for k in te.params:
        np.testing.assert_allclose(np.asarray(tf.params[k]),
                                   np.asarray(te.params[k]),
                                   rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(tf.predict(), te.predict(),
                               rtol=2e-4, atol=2e-4)
