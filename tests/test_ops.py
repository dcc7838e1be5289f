"""Per-op unit tests vs dense numpy references + gradient checks
(the test strategy SURVEY.md §4 says the reference lacks)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from roc_tpu.core.graph import add_self_edges, synthetic_graph
from roc_tpu.core.partition import padded_edge_list
from roc_tpu.ops.aggregate import aggregate_segment
from roc_tpu.ops.dense import (AC_MODE_NONE, AC_MODE_RELU, dropout, linear)
from roc_tpu.ops.loss import (masked_softmax_cross_entropy, perf_metrics,
                              summarize_metrics)
from roc_tpu.ops.norm import indegree_norm
from roc_tpu.core.graph import MASK_NONE, MASK_TRAIN, MASK_VAL, MASK_TEST


def dense_adjacency(g):
    A = np.zeros((g.num_nodes, g.num_nodes), dtype=np.float32)
    dst = g.edge_dst()
    for d, s in zip(dst, g.col_idx):
        A[d, s] += 1.0
    return A


@pytest.fixture(scope="module")
def graph():
    return add_self_edges(synthetic_graph(60, 5, seed=0, power_law=True))


@pytest.fixture(scope="module")
def feats(graph):
    rng = np.random.RandomState(0)
    return rng.randn(graph.num_nodes, 12).astype(np.float32)


def _padded(graph, chunk=64):
    src, dst = padded_edge_list(graph, multiple=chunk)
    return jnp.asarray(src), jnp.asarray(dst)


def test_aggregate_segment_matches_dense(graph, feats):
    A = dense_adjacency(graph)
    want = A @ feats
    src, dst = _padded(graph)
    x = jnp.concatenate([jnp.asarray(feats),
                         jnp.zeros((1, feats.shape[1]))], axis=0)
    got = aggregate_segment(x, src, dst, graph.num_nodes)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)


def test_aggregate_grad_is_transpose(graph, feats):
    """d(sum(A@X * G))/dX == A^T @ G — JAX must produce the exact
    transpose (the reference reuses A, valid only because A == A^T;
    our symmetric fixture satisfies both)."""
    A = dense_adjacency(graph)
    rng = np.random.RandomState(1)
    G = rng.randn(*feats.shape).astype(np.float32)
    src, dst = _padded(graph)

    def f(x):
        x_ext = jnp.concatenate([x, jnp.zeros((1, x.shape[1]))], axis=0)
        out = aggregate_segment(x_ext, src, dst, graph.num_nodes)
        return jnp.sum(out * G)

    got = jax.grad(f)(jnp.asarray(feats))
    want = A.T @ G
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)


def test_indegree_norm(graph, feats):
    deg = graph.in_degree.astype(np.float32)
    want = feats / np.sqrt(deg)[:, None]
    got = indegree_norm(jnp.asarray(feats), jnp.asarray(graph.in_degree))
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6)


def test_indegree_norm_zero_degree():
    x = jnp.ones((4, 3))
    deg = jnp.array([1, 4, 0, 9], dtype=jnp.int32)
    out = indegree_norm(x, deg)
    np.testing.assert_allclose(np.asarray(out[2]), 0.0)
    np.testing.assert_allclose(np.asarray(out[3]), 1.0 / 3.0, rtol=1e-6)


def test_linear_fused_relu():
    rng = np.random.RandomState(0)
    x = rng.randn(10, 8).astype(np.float32)
    w = rng.randn(8, 6).astype(np.float32)
    want = np.maximum(x @ w, 0.0)
    got = linear(jnp.asarray(x), jnp.asarray(w), AC_MODE_RELU)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)


def test_dropout_train_and_infer():
    key = jax.random.PRNGKey(0)
    x = jnp.ones((1000, 4))
    y = dropout(x, 0.5, key, train=True)
    # inverted dropout: survivors scaled by 2, mean preserved
    kept = np.asarray(y) > 0
    assert 0.4 < kept.mean() < 0.6
    np.testing.assert_allclose(np.asarray(y)[kept], 2.0)
    y_inf = dropout(x, 0.5, None, train=False)
    np.testing.assert_array_equal(np.asarray(y_inf), np.asarray(x))


def test_loss_grad_is_masked_softmax_minus_onehot():
    """The defining parity property (softmax_kernel.cu:19-33)."""
    rng = np.random.RandomState(0)
    V, C = 20, 5
    logits = rng.randn(V, C).astype(np.float32)
    labels = rng.randint(0, C, size=V).astype(np.int32)
    mask = rng.choice([MASK_NONE, MASK_TRAIN, MASK_VAL, MASK_TEST],
                      size=V).astype(np.int32)

    g = jax.grad(lambda l: masked_softmax_cross_entropy(
        l, jnp.asarray(labels), jnp.asarray(mask)))(jnp.asarray(logits))

    p = np.exp(logits - logits.max(axis=1, keepdims=True))
    p /= p.sum(axis=1, keepdims=True)
    onehot = np.eye(C, dtype=np.float32)[labels]
    want = (p - onehot) * (mask == MASK_TRAIN)[:, None]
    np.testing.assert_allclose(np.asarray(g), want, rtol=1e-5, atol=1e-6)


def test_perf_metrics_definitions():
    logits = jnp.asarray(np.array([[2.0, 1.0], [0.0, 3.0], [5.0, 0.0],
                                   [0.0, 1.0]], dtype=np.float32))
    labels = jnp.asarray(np.array([0, 1, 1, 1], dtype=np.int32))
    mask = jnp.asarray(np.array([MASK_TRAIN, MASK_TRAIN, MASK_VAL,
                                 MASK_TEST], dtype=np.int32))
    m = summarize_metrics(jax.device_get(perf_metrics(logits, labels, mask)))
    assert m["train_cnt"] == 2 and m["train_correct"] == 2
    assert m["val_cnt"] == 1 and m["val_correct"] == 0
    assert m["test_cnt"] == 1 and m["test_correct"] == 1
    # train_loss = sum over train of (1 - p_true)
    p0 = np.exp(2.0) / (np.exp(2.0) + np.exp(1.0))
    p1 = np.exp(3.0) / (np.exp(0.0) + np.exp(3.0))
    np.testing.assert_allclose(m["train_loss"], (1 - p0) + (1 - p1),
                               rtol=1e-5)


def test_aggregate_ell_matches_dense(graph, feats):
    from roc_tpu.core.ell import ell_from_graph
    from roc_tpu.ops.aggregate import aggregate_ell
    A = dense_adjacency(graph)
    want = A @ feats
    table = ell_from_graph(graph.row_ptr, graph.col_idx, graph.num_nodes)
    x = jnp.concatenate([jnp.asarray(feats),
                         jnp.zeros((1, feats.shape[1]))], axis=0)
    got = aggregate_ell(x, tuple(jnp.asarray(a[0]) for a in table.idx),
                        jnp.asarray(table.row_pos[0]), graph.num_nodes)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)


def test_aggregate_ell_chunked_budget(graph, feats):
    """Tiny budget forces the segmented-scan path; results identical."""
    from roc_tpu.core.ell import ell_from_graph
    from roc_tpu.ops.aggregate import aggregate_ell
    table = ell_from_graph(graph.row_ptr, graph.col_idx, graph.num_nodes)
    x = jnp.concatenate([jnp.asarray(feats),
                         jnp.zeros((1, feats.shape[1]))], axis=0)
    idx = tuple(jnp.asarray(a[0]) for a in table.idx)
    pos = jnp.asarray(table.row_pos[0])
    a = aggregate_ell(x, idx, pos, graph.num_nodes)
    b = aggregate_ell(x, idx, pos, graph.num_nodes, budget_elems=256)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-5)


def test_aggregate_ell_hub_node():
    """A hub row far above the old width clamp must aggregate exactly
    (regression: widths are unbounded powers of two, never clamped)."""
    from roc_tpu.core.graph import from_edge_list, add_self_edges
    from roc_tpu.core.ell import ell_from_graph, row_widths
    from roc_tpu.ops.aggregate import aggregate_ell
    assert row_widths(np.array([70_000]), 8)[0] == 131072
    V = 300
    hub_src = np.arange(V, dtype=np.int64)
    hub_dst = np.zeros(V, dtype=np.int64)
    g = add_self_edges(from_edge_list(hub_src, hub_dst, V))
    rng = np.random.RandomState(0)
    feats = rng.randn(V, 5).astype(np.float32)
    table = ell_from_graph(g.row_ptr, g.col_idx, V)
    x = jnp.concatenate([jnp.asarray(feats), jnp.zeros((1, 5))], axis=0)
    got = aggregate_ell(x, tuple(jnp.asarray(a[0]) for a in table.idx),
                        jnp.asarray(table.row_pos[0]), V)
    # row 0 sums every node's features (+ its self edge already counted)
    np.testing.assert_allclose(np.asarray(got)[0], feats.sum(axis=0),
                               rtol=1e-4, atol=1e-4)


# ---- sectioned aggregation (core/ell.py SectionedEll) ----

def test_sectioned_matches_segment():
    """The fast-gather sectioned layout must be exact vs segment-sum,
    across section boundaries and with multi-section tables."""
    import jax.numpy as jnp
    from roc_tpu.core.graph import add_self_edges, synthetic_graph
    from roc_tpu.core.ell import sectioned_from_graph
    from roc_tpu.core.partition import padded_edge_list
    from roc_tpu.ops.aggregate import aggregate_ell_sect, aggregate_segment
    g = add_self_edges(synthetic_graph(500, 9, seed=11, power_law=True))
    F = 12
    feats = np.random.RandomState(0).rand(g.num_nodes + 1, F).astype(
        np.float32)
    feats[-1] = 0
    x = jnp.asarray(feats)
    src, dst = padded_edge_list(g, multiple=64)
    want = aggregate_segment(x, jnp.asarray(src), jnp.asarray(dst),
                             g.num_nodes)
    # force several sections and several chunks
    sect = sectioned_from_graph(g.row_ptr, g.col_idx, g.num_nodes,
                                section_rows=128, seg_rows=64)
    got = aggregate_ell_sect(
        x, tuple(jnp.asarray(a) for a in sect.idx),
        tuple(jnp.asarray(a) for a in sect.sub_dst),
        tuple(zip(sect.sec_starts, sect.sec_sizes)), g.num_nodes)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_sectioned_end_to_end_training():
    """aggr_impl='sectioned' trains the GCN to the same result as
    'segment' (rate-0 dropout => identical RNG-free paths)."""
    import jax
    from roc_tpu.core.graph import synthetic_dataset
    from roc_tpu.models.gcn import build_gcn
    from roc_tpu.train.trainer import TrainConfig, Trainer
    ds = synthetic_dataset(300, 6, in_dim=12, num_classes=3, seed=5)
    params = {}
    for impl in ("segment", "sectioned"):
        model = build_gcn([12, 8, 3], dropout_rate=0.0)
        cfg = TrainConfig(learning_rate=0.05, epochs=3, aggr_impl=impl,
                          eval_every=1 << 30, verbose=False,
                          symmetric=True)
        tr = Trainer(model, ds, cfg)
        tr.train()
        params[impl] = tr.params
    for k in params["segment"]:
        np.testing.assert_allclose(np.asarray(params["segment"][k]),
                                   np.asarray(params["sectioned"][k]),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("sub_w", [8, 16, 32])
def test_sectioned_width_variants_match_segment(sub_w):
    """Width-parameterized sub-rows (VERDICT r4 gather levers): any
    sub_w must be exact vs segment-sum, native and numpy builders
    agreeing."""
    import jax.numpy as jnp
    from roc_tpu.core.graph import add_self_edges, synthetic_graph
    from roc_tpu.core.ell import sectioned_from_graph
    from roc_tpu.core.partition import padded_edge_list
    from roc_tpu.ops.aggregate import (aggregate_ell_sect,
                                       aggregate_segment)
    g = add_self_edges(synthetic_graph(500, 9, seed=7, power_law=True))
    F = 12
    feats = np.random.RandomState(1).rand(g.num_nodes + 1, F).astype(
        np.float32)
    feats[-1] = 0
    x = jnp.asarray(feats)
    src, dst = padded_edge_list(g, multiple=64)
    want = aggregate_segment(x, jnp.asarray(src), jnp.asarray(dst),
                             g.num_nodes)
    sect = sectioned_from_graph(g.row_ptr, g.col_idx, g.num_nodes,
                                section_rows=128, seg_rows=64,
                                sub_w=sub_w)
    assert sect.idx[0].shape[-1] == sub_w
    sidx, sdst, meta = sect.as_jax()
    got = aggregate_ell_sect(x, sidx, sdst, meta, g.num_nodes)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_sectioned_uint16_matches_int32():
    """uint16 section-local indices are numerics-identical to the
    int32 form."""
    import jax.numpy as jnp
    from roc_tpu.core.graph import add_self_edges, synthetic_graph
    from roc_tpu.core.ell import sectioned_from_graph
    from roc_tpu.ops.aggregate import aggregate_ell_sect
    g = add_self_edges(synthetic_graph(400, 7, seed=3, power_law=True))
    F = 9
    feats = np.random.RandomState(2).rand(g.num_nodes + 1, F).astype(
        np.float32)
    feats[-1] = 0
    x = jnp.asarray(feats)
    sect = sectioned_from_graph(g.row_ptr, g.col_idx, g.num_nodes,
                                section_rows=128, seg_rows=64)
    sidx, sdst, meta = sect.as_jax()
    want = np.asarray(aggregate_ell_sect(x, sidx, sdst, meta,
                                         g.num_nodes))
    u16 = sect.with_idx_dtype(np.uint16)
    assert all(a.dtype == np.uint16 for a in u16.idx)
    uidx, udst, umeta = u16.as_jax()
    got16 = np.asarray(aggregate_ell_sect(x, uidx, udst, umeta,
                                          g.num_nodes))
    np.testing.assert_array_equal(got16, want)
    # a section size past the dtype's range must refuse loudly
    import pytest as _pytest
    big = sectioned_from_graph(g.row_ptr, g.col_idx, g.num_nodes,
                               section_rows=4096, seg_rows=64)
    if max(big.sec_sizes) <= 255:
        _pytest.skip("graph too small to overflow uint8 sections")
    with _pytest.raises(ValueError, match="does not fit"):
        big.with_idx_dtype(np.uint8)


# ---- the windowed chunk scan (ops/aggregate.py _scan_window_sum) ----
# Values are small multiples of 1/4 so every partial sum is exact in
# fp32 AND bf16: the scan and the segment reference must then agree to
# rounding whatever order they add in.

def _csr(rows):
    """dst-major CSR from a list of per-row neighbour arrays."""
    row_ptr = np.zeros(len(rows) + 1, dtype=np.int64)
    np.cumsum([len(r) for r in rows], out=row_ptr[1:])
    col = (np.concatenate([np.asarray(r, np.int64) for r in rows])
           if row_ptr[-1] else np.zeros(0, np.int64))
    return row_ptr, col.astype(np.int32)


def _win_graph(case):
    """(row_ptr, col_idx, num_rows, build kwargs) per window case —
    each tall enough that its 128-row windows stay under half the
    carry, where the scan really takes the window."""
    rng = np.random.RandomState(7)
    if case in ("hub", "hub_short"):
        # row 17 gathers from every node: its sub-rows fill four or
        # more seg_rows=4 chunks of each 128-source section.  The
        # short twin's 128-row window is past half of its 193-row
        # carry: the scan must fall back to the whole carry
        n = 640 if case == "hub" else 192
        rows = [np.unique(np.r_[v, rng.randint(0, n, 3)])
                for v in range(n)]
        rows[17] = np.arange(n)
        return _csr(rows) + (n, dict(section_rows=128, seg_rows=4))
    if case == "gaps":
        # block-local edges only, every 7th row empty and rows
        # 400..519 too: inside a section the destinations jump over
        # rows with no neighbour there, once by more than 100 rows
        n = 1200
        rows = [(np.unique(rng.randint(v // 300 * 300,
                                       v // 300 * 300 + 300, 5))
                 if v % 7 and not 400 <= v < 520
                 else np.zeros(0, np.int64)) for v in range(n)]
        return _csr(rows) + (n, dict(section_rows=300, seg_rows=16))
    if case == "trailing_pad":
        n = 600
        rows = [np.unique(np.r_[v, rng.randint(0, n, 4)])
                for v in range(n)]
        return _csr(rows) + (n, dict(section_rows=256,
                                     chunks_plan=[(120, 16)] * 3))
    if case == "fit":
        # four 300-source sections holding 3600 / 1200 / 300 / 40
        # sub-rows: under a cap of 256 each gets a height of its own
        # (core/ell.py fit_chunks), the last a single short chunk
        n = 1200
        per_row = [(20, range(n)), (5, range(n)), (3, range(300)),
                   (2, range(500, 540))]
        rows = [np.concatenate(
            [s * 300 + rng.choice(300, k, replace=False)
             for s, (k, who) in enumerate(per_row) if v in who])
            for v in range(n)]
        return _csr(rows) + (n, dict(section_rows=300, seg_rows=256))
    raise AssertionError(case)


_FIT_CHUNKS = [(15, 240), (5, 240), (2, 160), (1, 40)]


def _spans(sub_dst, num_rows):
    """Per chunk: last real destination - first + 1 (0: all padding)."""
    real = np.where(sub_dst < num_rows, sub_dst, -1).max(axis=-1)
    return np.maximum(real - sub_dst[..., 0] + 1, 0)


def _win_inputs(num_src, F, dtype, seed=0):
    rng = np.random.RandomState(seed)
    feats = rng.randint(-4, 5, (num_src + 1, F)).astype(np.float32) / 4
    feats[-1] = 0
    return jnp.asarray(feats, dtype=dtype)


def _edge_weights(sect, seed=1):
    """Per-destination x per-source scales in {1/2, 1, 2} laid out
    like the fused tables (SectionedEll.weight_tables)."""
    rng = np.random.RandomState(seed)
    d_dst = rng.choice([0.5, 1.0, 2.0], sect.num_rows)
    d_src = rng.choice([0.5, 1.0, 2.0], sect.src_rows)
    return d_dst, d_src, sect.weight_tables(d_dst, d_src)


def _segment_ref(x, row_ptr, col, num_rows, d_dst=None, d_src=None):
    dst = np.repeat(np.arange(num_rows), np.diff(row_ptr))
    g = np.asarray(x, np.float32)[col]
    if d_dst is not None:
        g = g * (d_dst[dst] * d_src[col])[:, None]
    out = np.zeros((num_rows, x.shape[1]), np.float32)
    np.add.at(out, dst, g)
    return out


_TOL = {"float32": 1e-6, "bfloat16": 1e-2}


@pytest.mark.parametrize("weighted", [False, True],
                         ids=["plain", "weighted"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", ["hub", "gaps", "trailing_pad",
                                  "full_window", "flat_hub",
                                  "short_carry", "fit"])
def test_windowed_scan_matches_segment(case, dtype, weighted):
    """The windowed scan == the segment reference, for every way a
    chunk's destination run can look: (a) a hub row over three or more
    chunks, (b) destinations that jump, (c) a trailing all-padding
    chunk, (d) a window as tall as the carry, the flat layout's
    single section, a window past half a short carry (the scan
    takes the whole carry), and sections whose chunks are fitted to
    different heights."""
    from roc_tpu.core.ell import flat_sum_from_graph, sectioned_from_graph
    from roc_tpu.ops.aggregate import (aggregate_ell_sect,
                                       aggregate_flat_sum,
                                       scan_window_rows)
    flat = case == "flat_hub"
    row_ptr, col, n, kw = _win_graph(
        {"flat_hub": "hub", "full_window": "hub",
         "short_carry": "hub_short"}.get(case, case))
    if flat:
        sect = flat_sum_from_graph(row_ptr, col, n, seg_rows=8)
    else:
        sect = sectioned_from_graph(row_ptr, col, n, **kw)
    spans = [_spans(d, n) for d in sect.sub_dst]
    carry = n + 1
    if case in ("hub", "flat_hub", "short_carry"):
        # the hub's sub-rows really do cross three or more chunks
        assert max(int((d == 17).any(axis=1).sum())
                   for d in sect.sub_dst) >= 3
    if case == "gaps":
        assert max(int(np.diff(d[d < n]).max())
                   for d in sect.sub_dst) > 100
    if case == "trailing_pad":
        assert all(s[-1] == 0 for s in spans)
    if case == "fit":
        assert [d.shape for d in sect.sub_dst] == _FIT_CHUNKS
    # the windowed path is what runs — or, on the short carry, not
    assert all(scan_window_rows(w, carry) ==
               (carry if case == "short_carry" else w)
               for w in sect.win_rows)
    x = _win_inputs(sect.src_rows, 5, dtype)
    d_dst = d_src = w = None
    if weighted:
        d_dst, d_src, w = _edge_weights(sect)
        w = tuple(jnp.asarray(a) for a in w)
    sidx, sdst, meta = sect.as_jax()
    if case == "full_window":
        # a bare (start, size) and an explicit carry-high window are
        # the same whole-carry scatter
        got = aggregate_ell_sect(x, sidx, sdst,
                                 tuple(m[:2] for m in meta), n, sect_w=w)
        same = aggregate_ell_sect(
            x, sidx, sdst, tuple(m[:2] + (carry,) for m in meta), n,
            sect_w=w)
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(same, np.float32))
    elif flat:
        got = aggregate_flat_sum(x, sidx[0], sdst[0], n,
                                 flat_w=w[0] if weighted else None,
                                 win_rows=sect.win_rows[0])
    else:
        got = aggregate_ell_sect(x, sidx, sdst, meta, n, sect_w=w)
    assert got.dtype == x.dtype
    want = _segment_ref(x, row_ptr, col, n, d_dst, d_src)
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               rtol=_TOL[dtype], atol=_TOL[dtype])


def _local_graph(n):
    """Every row gathers itself and six of the first 200 nodes: past
    those 200 a block of sources is read by its own rows alone, so a
    part's tables hold nothing in the sections of the other parts'
    rows, and the busy first sections need taller chunks than the
    rest."""
    from roc_tpu.core.graph import from_edge_list
    rng = np.random.RandomState(13)
    dst = np.repeat(np.arange(n), 7)
    src = np.c_[np.arange(n), rng.randint(0, 200, (n, 6))].reshape(-1)
    return from_edge_list(src, dst, n)


@pytest.mark.parametrize("weighted", [False, True],
                         ids=["plain", "weighted"])
@pytest.mark.parametrize("layout", ["sectioned", "flat_sum",
                                    "sectioned_fit"])
@pytest.mark.parametrize("parts", [2, 4])
def test_windowed_scan_stacked_parts(parts, layout, weighted):
    """(e) stacked [P, ...] tables over unequal parts: every part scans
    with the one window all parts agreed on, and still equals its own
    segment reference — also where the sections' chunks are fitted to
    different heights and a part holds nothing in a section."""
    from roc_tpu.core.ell import (flat_sum_from_padded_parts,
                                  sectioned_from_padded_parts)
    from roc_tpu.core.partition import partition_graph
    from roc_tpu.ops.aggregate import (aggregate_ell_sect,
                                       aggregate_flat_sum)
    from roc_tpu.parallel.distributed import remap_to_padded
    fit = layout == "sectioned_fit"
    g = (_local_graph(1400) if fit else add_self_edges(
        synthetic_graph(1400, 9, seed=5, power_law=True)))
    pg = partition_graph(g, parts, node_multiple=8, edge_multiple=8)
    assert len(set(int(r) for r in pg.real_nodes)) > 1  # unequal parts
    cols = remap_to_padded(pg)
    src_rows = parts * pg.part_nodes
    if layout == "flat_sum":
        sect = flat_sum_from_padded_parts(
            pg.part_row_ptr, cols, pg.real_nodes, pg.part_nodes,
            src_rows=src_rows, seg_rows=16)
    else:
        sect = sectioned_from_padded_parts(
            pg.part_row_ptr, cols, pg.real_nodes, pg.part_nodes,
            src_rows=src_rows, section_rows=100 if fit else 128,
            seg_rows=64 if fit else 16)
    if fit:
        assert len({d.shape[2] for d in sect.sub_dst}) > 1
        assert any((d[p] == pg.part_nodes).all()
                   for d in sect.sub_dst for p in range(parts))
    assert all(2 * w <= pg.part_nodes + 1 for w in sect.win_rows)
    x = _win_inputs(src_rows, 4, "float32")
    w = None
    if weighted:
        rng = np.random.RandomState(3)
        d_dst = rng.choice([0.5, 1.0, 2.0], (parts, pg.part_nodes))
        d_src = rng.choice([0.5, 1.0, 2.0], src_rows)
        w = sect.weight_tables(d_dst, d_src)
    for p in range(parts):
        n_real = int(pg.real_nodes[p])
        ptr = pg.part_row_ptr[p, :n_real + 1].astype(np.int64)
        want = np.zeros((pg.part_nodes, 4), np.float32)
        want[:n_real] = _segment_ref(
            x, ptr, cols[p][:ptr[-1]], n_real,
            d_dst[p] if weighted else None,
            d_src if weighted else None)
        idx_p = tuple(jnp.asarray(a[p]) for a in sect.idx)
        dst_p = tuple(jnp.asarray(a[p]) for a in sect.sub_dst)
        w_p = tuple(jnp.asarray(a[p]) for a in w) if weighted else None
        if layout == "flat_sum":
            got = aggregate_flat_sum(
                x, idx_p[0], dst_p[0], pg.part_nodes,
                flat_w=w_p[0] if weighted else None,
                win_rows=sect.win_rows[0])
        else:
            got = aggregate_ell_sect(x, idx_p, dst_p, sect.meta,
                                     pg.part_nodes, sect_w=w_p)
        np.testing.assert_allclose(np.asarray(got), want,
                                   rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("weighted", [False, True],
                         ids=["plain", "weighted"])
@pytest.mark.parametrize("layout", ["sectioned", "flat_sum",
                                    "sectioned_fit"])
def test_windowed_scan_grad_matches_segment(layout, weighted):
    """Exact autodiff THROUGH the scan (what symmetric=False runs): the
    slice / scatter-add / update-slice body transposes to the segment
    reference's gradient — at one chunk height, and at a height per
    section."""
    from roc_tpu.core.ell import flat_sum_from_graph, sectioned_from_graph
    from roc_tpu.ops.aggregate import (aggregate_ell_sect,
                                       aggregate_flat_sum)
    fit = layout == "sectioned_fit"
    row_ptr, col, n, kw = _win_graph("fit" if fit else "hub")
    if layout == "flat_sum":
        sect = flat_sum_from_graph(row_ptr, col, n, seg_rows=8)
    else:
        sect = sectioned_from_graph(row_ptr, col, n, **kw)
    assert all(2 * w <= n + 1 for w in sect.win_rows)
    assert len({d.shape[1] for d in sect.sub_dst}) == (3 if fit else 1)
    x = _win_inputs(n, 3, "float32")
    d_dst = d_src = w = None
    if weighted:
        d_dst, d_src, w = _edge_weights(sect)
        w = tuple(jnp.asarray(a) for a in w)
    sidx, sdst, meta = sect.as_jax()
    cot = jnp.asarray(np.random.RandomState(5).randint(
        -3, 4, (n, 3)).astype(np.float32))

    def through_scan(v):
        if layout == "flat_sum":
            out = aggregate_flat_sum(v, sidx[0], sdst[0], n,
                                     flat_w=w[0] if weighted else None,
                                     win_rows=sect.win_rows[0])
        else:
            out = aggregate_ell_sect(v, sidx, sdst, meta, n, sect_w=w)
        return (out * cot).sum()

    got = np.asarray(jax.grad(through_scan)(x))
    # d/dx sum(cot * A x) = A^T cot, the dummy row included (zero)
    dst = np.repeat(np.arange(n), np.diff(row_ptr))
    scale = (d_dst[dst] * d_src[col])[:, None] if weighted else 1.0
    want = np.zeros((n + 1, 3), np.float32)
    np.add.at(want, col, np.asarray(cot)[dst] * scale)
    np.testing.assert_allclose(got[:n], want[:n], rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("impl", ["ell", "bdense"])
def test_directed_grad_matches_segment(impl):
    """``symmetric=False`` on a directed graph: exact autodiff through
    the layout's tables gives the edge-list reference's gradient, A^T
    times the cotangent (``sectioned`` and ``flat_sum`` have theirs
    in test_windowed_scan_grad_matches_segment)."""
    from roc_tpu.core.graph import Dataset, from_edge_list
    from roc_tpu.train.trainer import make_graph_context
    rng = np.random.RandomState(11)
    n, e = 200, 1500
    # sources crowd the first 40 ids, so some [128, 128] tile fills
    g = add_self_edges(from_edge_list(rng.randint(0, 40, e),
                                      rng.randint(0, n, e), n))
    ds = Dataset(graph=g, features=np.zeros((n, 3), np.float32),
                 labels=np.zeros(n, np.int32),
                 mask=np.ones(n, np.int32), num_classes=2,
                 name="directed")
    x = jnp.asarray(rng.randint(-3, 4, (n, 3)).astype(np.float32))
    cot = jnp.asarray(rng.randint(-3, 4, (n, 3)).astype(np.float32))

    def grad_of(aggr_impl):
        gctx = make_graph_context(ds, aggr_impl, symmetric=False,
                                  bdense_min_fill=1)
        assert not gctx.symmetric
        if aggr_impl == "bdense":
            assert gctx.bd_a is not None
        return np.asarray(jax.grad(
            lambda v: (gctx.aggregate_sum(v) * cot).sum())(x))

    want = np.zeros((n, 3), np.float32)
    np.add.at(want, g.col_idx, np.asarray(cot)[g.edge_dst()])
    np.testing.assert_array_equal(grad_of("segment"), want)
    assert not np.array_equal(want, np.asarray(
        jax.grad(lambda v: (make_graph_context(
            ds, "segment", symmetric=True).aggregate_sum(v)
            * cot).sum())(x)))       # the graph IS directed
    np.testing.assert_allclose(grad_of(impl), want, rtol=1e-6,
                               atol=1e-6)


# ---- a chunk's height (core/ell.py fit_chunks) ----

_H, _HF = 131_072, 8_192     # SECT_SEG_ROWS, FLAT_SEG_ROWS


@pytest.mark.parametrize("sub_rows, cap, want", [
    # gcn2-arxiv.fullgraph's three sections (graph_seed 22)
    (209_069, _H, (2, 106_496)), (208_477, _H, (2, 106_496)),
    (153_460, _H, (2, 81_920)),
    # gcn-reddit.fullgraph's four: the cap, as before the fit
    (4_154_254, _H, (32, _H)), (4_096_082, _H, (32, _H)),
    (4_149_388, _H, (32, _H)), (2_350_688, _H, (18, _H)),
    # a products partition's single flat_sum section
    (4_215_000, _HF, (515, _HF)),
    (0, _H, (1, 8)), (1, _H, (1, 8)), (_H, _H, (1, _H)),
    (_H + 1, _H, (2, 73_728)), (_H - 9, _H, (1, _H - 8)),
    (0, _HF, (1, 8)), (_HF, _HF, (1, _HF)), (_HF + 1, _HF, (2, 4_608)),
    # caps the CPU rigs use, some under the 8-row tile
    (3, 4, (1, 4)), (9, 4, (3, 4)), (100, 64, (2, 56)),
    (40, 64, (1, 40)), (65, 16, (5, 16)),
])
def test_fit_chunks_rule(sub_rows, cap, want):
    from roc_tpu.core.ell import (FLAT_SEG_ROWS, SECT_SEG_ROWS,
                                  fit_chunks)
    assert (_H, _HF) == (SECT_SEG_ROWS, FLAT_SEG_ROWS)
    assert fit_chunks(sub_rows, cap) == want


@pytest.mark.parametrize("cap", [_H, _HF, 4096, 64, 16, 4])
def test_fit_chunks_bounds(cap):
    """Over a sweep of counts: the chunks hold the section, as many
    scan steps as a fixed height of ``cap`` takes and never more, no
    chunk past the cap, heights on the 8-row tile, never more slots
    than the fixed height gave, and from sixteen chunks on the cap
    itself — so a large section's tables are what they were."""
    from roc_tpu.core.ell import fit_chunks
    rng = np.random.RandomState(cap)
    counts = np.unique(np.r_[
        0, 1, cap - 1, cap, cap + 1, 15 * cap, 15 * cap + 1, 16 * cap,
        rng.randint(0, 40 * cap, 400), rng.randint(0, 3 * cap, 200)])
    for c in counts:
        n, seg = fit_chunks(int(c), cap)
        assert n == max(1, -(-int(c) // cap))
        assert c <= n * seg <= n * cap and 1 <= seg <= cap
        assert seg % 8 == 0 or seg == cap
        if n >= 16 and cap % 16 == 0:
            assert seg == cap
        if n == 1:
            assert seg == min(cap, max(8, -(-int(c) // 8) * 8))


@pytest.mark.parametrize("counts_max", [
    (209_069, 208_477, 153_460), (70, 3, 0), (5000, 12, 130_000),
    (4_154_254, 2_350_688)])
def test_sectioned_plan_fits_each_section(counts_max):
    """The SPMD plan is the same rule on each section's largest part,
    and no section is taller than under the one shared height the
    plan used to return (``ceil8`` of the largest count, capped)."""
    from roc_tpu.core.ell import SECT_SEG_ROWS, fit_chunks, sectioned_plan
    plan = sectioned_plan(np.asarray(counts_max))
    assert plan == [fit_chunks(c, SECT_SEG_ROWS) for c in counts_max]
    shared = max(8, min(SECT_SEG_ROWS, -(-max(counts_max) // 8) * 8))
    for c, (n, seg) in zip(counts_max, plan):
        assert seg <= shared and n <= max(1, -(-c // shared))


# ---- the table's window (core/ell.py SectionedEll.win_rows) ----

@pytest.mark.parametrize("case", ["hub", "gaps", "trailing_pad", "fit"])
@pytest.mark.parametrize("builder", ["native", "numpy"])
def test_win_rows_bounds_every_chunk(case, builder, monkeypatch):
    """win_rows covers every chunk's real span and stays below the
    next rounding step above the largest; native and numpy builders
    agree on it; narrowing the index dtype keeps it."""
    import roc_tpu.core.ell as E
    from roc_tpu import native
    if builder == "native" and not native.available():
        pytest.skip("native library unavailable")
    row_ptr, col, n, kw = _win_graph(case)
    other = E.sectioned_from_graph(row_ptr, col, n, **kw)
    if builder == "numpy":
        monkeypatch.setattr(native, "available", lambda: False)
    sect = E.sectioned_from_graph(row_ptr, col, n, **kw)
    assert sect.win_rows == other.win_rows
    assert len(sect.win_rows) == len(sect.sub_dst)
    if case == "fit":
        assert [d.shape for d in sect.sub_dst] == _FIT_CHUNKS
    for w, d in zip(sect.win_rows, sect.sub_dst):
        largest = int(_spans(d, n).max())
        assert largest <= w < largest + E.WIN_ROWS_MULTIPLE
        assert w % E.WIN_ROWS_MULTIPLE == 0
    assert sect.meta == tuple(zip(sect.sec_starts, sect.sec_sizes,
                                  sect.win_rows, sect.bands))
    assert sect.with_idx_dtype(np.uint16).win_rows == sect.win_rows
    assert sect.with_idx_dtype(np.uint16).bands == sect.bands


@pytest.mark.parametrize("parts", [2, 4])
def test_win_rows_stacked_is_max_over_parts(parts):
    """SPMD shapes must agree: the stacked table's window is the
    largest any part needs, per section."""
    from roc_tpu.core.ell import (chunk_window_rows,
                                  sectioned_from_padded_parts)
    from roc_tpu.core.partition import partition_graph
    from roc_tpu.parallel.distributed import remap_to_padded
    g = add_self_edges(synthetic_graph(900, 8, seed=9, power_law=True))
    pg = partition_graph(g, parts, node_multiple=8, edge_multiple=8)
    sect = sectioned_from_padded_parts(
        pg.part_row_ptr, remap_to_padded(pg), pg.real_nodes,
        pg.part_nodes, src_rows=parts * pg.part_nodes,
        section_rows=256, seg_rows=16)
    for s, d in enumerate(sect.sub_dst):
        per_part = [chunk_window_rows(d[p], pg.part_nodes)
                    for p in range(parts)]
        assert sect.win_rows[s] == max(per_part)
        assert d.shape[0] == parts
    # not vacuous: at least one section's parts differ
    assert any(len({chunk_window_rows(d[p], pg.part_nodes)
                    for p in range(parts)}) > 1
               for d in sect.sub_dst)


# ---- the program's shape: the carry is only sliced and updated ----

def test_scan_window_rows_rule():
    """The one rule for when the window pays (measured on the v5e):
    up to half the carry's height; none known, or taller, is the
    whole carry."""
    from roc_tpu.ops.aggregate import scan_window_rows
    assert scan_window_rows(0, 1000) == 1000
    assert scan_window_rows(128, 1000) == 128
    assert scan_window_rows(500, 1000) == 500
    assert scan_window_rows(512, 1000) == 1000
    assert scan_window_rows(4096, 1000) == 1000


def _outside_kernel(text: str, root: str = "_gather_sum_call") -> str:
    """The lowered module ``text`` without the functions the Pallas
    call ``root`` (ops/aggregate.py) is lowered into, one a kernel
    shape, or that they call."""
    import re
    funcs = {}
    for chunk in re.split(r"(?m)^\s*func\.func ", text)[1:]:
        funcs[re.search(r"@([\w.$-]+)\(", chunk).group(1)] = chunk
    # one function a kernel shape: root, root_1, ...
    todo = [f for f in funcs if re.fullmatch(rf"{root}(_\d+)?", f)]
    kernel = set()
    while todo:
        name = todo.pop()
        if name in kernel or name not in funcs:
            continue
        kernel.add(name)
        todo.extend(re.findall(r"call @([\w.$-]+)\(", funcs[name]))
    assert kernel, "the scan no longer calls the kernel"
    return "\n".join(c for f, c in funcs.items() if f not in kernel)


@pytest.mark.parametrize("layout", ["sectioned", "flat_sum",
                                    "sectioned_short"])
def test_scan_program_touches_carry_only_by_slices(layout):
    """In the lowered program the scatter's operand is the
    [win_rows, F] window and nothing but dynamic_slice /
    dynamic_update_slice (and the scan's own plumbing) produces or
    consumes a carry-shaped tensor inside the loop — a later edit
    cannot bring the whole-carry scatter back unnoticed.  On the short
    carry (window past half of it) the same body IS the whole-carry
    scatter: the slice and the write-back are of the carry itself."""
    import re
    from roc_tpu.core.ell import flat_sum_from_graph, sectioned_from_graph
    from roc_tpu.ops.aggregate import (aggregate_ell_sect,
                                       aggregate_flat_sum,
                                       scan_window_rows)
    short = layout == "sectioned_short"
    row_ptr, col, n, kw = _win_graph("hub_short" if short else "hub")
    F = 6
    if layout == "flat_sum":
        sect = flat_sum_from_graph(row_ptr, col, n, seg_rows=8)
        sidx, sdst, _ = sect.as_jax()
        fn = lambda v: aggregate_flat_sum(v, sidx[0], sdst[0], n,
                                          win_rows=sect.win_rows[0])
    else:
        sect = sectioned_from_graph(row_ptr, col, n, **kw)
        sidx, sdst, meta = sect.as_jax()
        fn = lambda v: aggregate_ell_sect(v, sidx, sdst, meta, n)
    wins = {scan_window_rows(w, n + 1) for w in sect.win_rows}
    assert wins == ({n + 1} if short else set(sect.win_rows))
    text = jax.jit(fn).lower(_win_inputs(n, F, "float32")).as_text()
    # the gather-sum kernel's own operands and blocks (interpreted off
    # the chip) are not the scan's: its functions are left out
    text = _outside_kernel(text)
    carry = f"tensor<{n + 1}x{F}xf32>"
    scatters = re.findall(
        r'"stablehlo\.scatter"\((%\w+),.*?\}\) : \(([^,]*),[^)]*\) -> '
        r'(tensor<[^>]*>)', text, flags=re.S)
    assert len(scatters) == len(sect.idx)
    for _, operand, result in scatters:
        assert operand.strip() == result
        assert (result == carry) == short
        rows = int(re.match(r"tensor<(\d+)x", result).group(1))
        assert rows in wins and result.endswith(f"x{F}xf32>")
    # which ops yield a carry-shaped value anywhere in the program:
    # its zero init, the loop and its outlined body, and the in-place
    # window write-back — nothing that computes on all of it
    makers = {re.search(r"(stablehlo|func)\.([a-z_]+)", ln).group(2)
              for ln in text.splitlines()
              if re.search(r"= \"?(stablehlo|func)\.", ln)
              and ln.rstrip().endswith(carry)}
    plumbing = {"broadcast_in_dim", "while", "call",
                "dynamic_update_slice"}
    assert makers == (plumbing | {"dynamic_slice"}
                      if short else plumbing), makers
    windows = re.findall(r"stablehlo\.dynamic_slice %\w+.*-> "
                         rf"tensor<(\d+)x{F}xf32>", text)
    assert {int(w) for w in windows} == wins
