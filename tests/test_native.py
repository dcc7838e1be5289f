"""Parity tests: native C++ data layer (native/rocio.cc via ctypes)
vs the pure-numpy reference implementations."""

import os
import tempfile

import numpy as np
import pytest

from roc_tpu import native
from roc_tpu.core import graph as G
from roc_tpu.core import partition as P

pytestmark = pytest.mark.skipif(
    not native.available(), reason="native librocio.so not built")


@pytest.fixture(scope="module")
def graph():
    return G.synthetic_graph(500, 12, seed=3, power_law=True)


def test_lux_roundtrip(graph, tmp_path):
    p = str(tmp_path / "t.lux")
    G.save_lux(graph, p)
    row_ptr, col_idx = native.load_lux(p)
    assert np.array_equal(row_ptr, graph.row_ptr)
    assert np.array_equal(col_idx, graph.col_idx)
    p2 = str(tmp_path / "t2.lux")
    native.save_lux(p2, graph.row_ptr, graph.col_idx)
    g2 = G.load_lux(p2)
    assert np.array_equal(g2.row_ptr, graph.row_ptr)
    assert np.array_equal(g2.col_idx, graph.col_idx)


def test_lux_read_rejects_corrupt(tmp_path):
    p = str(tmp_path / "bad.lux")
    with open(p, "wb") as f:
        f.write(b"\x05\x00\x00\x00")  # header truncated
    with pytest.raises(IOError):
        native.load_lux(p)


def test_features_csv(tmp_path):
    feats = np.random.RandomState(0).randn(50, 7).astype(np.float32)
    p = str(tmp_path / "x.feats.csv")
    np.savetxt(p, feats, delimiter=",", fmt="%.6e")
    got = native.load_features_csv(p, 50, 7)
    np.testing.assert_allclose(got, feats, atol=1e-5)


def test_features_csv_shape_mismatch_raises(tmp_path):
    """A wrong column count must raise, not silently mis-align rows
    (parity with the numpy fallback's reshape error)."""
    feats = np.arange(16, dtype=np.float32).reshape(4, 4)
    p = str(tmp_path / "x.feats.csv")
    np.savetxt(p, feats, delimiter=",", fmt="%.1f")
    with pytest.raises(IOError):
        native.load_features_csv(p, 4, 2)   # under-declared cols
    with pytest.raises(IOError):
        native.load_features_csv(p, 4, 8)   # over-declared cols


def test_mask_parser(tmp_path):
    names = ["Train", "Val", "Test", "None"]
    vals = np.random.RandomState(1).randint(0, 4, size=200)
    p = str(tmp_path / "m.mask")
    with open(p, "w") as f:
        f.write("\n".join(names[v] for v in vals) + "\n")
    got = native.load_mask(p, 200)
    want = np.array([[1, 2, 3, 0][v] for v in vals], dtype=np.int32)
    assert np.array_equal(got, want)


@pytest.mark.parametrize("num_parts", [1, 2, 4, 7])
def test_bounds_parity(graph, num_parts, monkeypatch):
    nb = [tuple(b) for b in
          native.edge_balanced_bounds(graph.row_ptr, num_parts)]
    # force the pure-python sweep for comparison
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_tried", True)
    pb = P.edge_balanced_bounds(graph.row_ptr, num_parts)
    assert nb == pb


def test_add_self_edges_parity(monkeypatch):
    base = G.from_edge_list(np.array([0, 1, 2, 4, 2]),
                            np.array([1, 2, 3, 4, 2]), 6)
    row_ptr, col_idx = native.add_self_edges(base.row_ptr, base.col_idx)
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_tried", True)
    ref = G.add_self_edges(base)
    assert np.array_equal(row_ptr, ref.row_ptr)
    assert np.array_equal(col_idx, ref.col_idx)


def test_ell_widths(graph):
    w = native.ell_widths(graph.row_ptr, 8)
    deg = np.diff(graph.row_ptr)
    for d, got in zip(deg, w):
        if d == 0:
            assert got == 0
        else:
            want = 8
            while want < d:
                want *= 2
            assert got == want


@pytest.mark.parametrize("plan", [None, "fitted", "roomy"])
def test_sectioned_native_matches_numpy_per_section_heights(
        plan, monkeypatch):
    """Each section's chunks have a height of their own
    (core/ell.py fit_chunks): native and numpy builders agree on every
    shape and byte — fitted from the section's own count, or held to an
    SPMD plan whose entries differ (``roomy``: a chunk more than
    needed, all padding)."""
    import roc_tpu.core.ell as ell_mod
    from roc_tpu import native
    from roc_tpu.core.graph import from_edge_list
    if not native.available():
        pytest.skip("native library unavailable")
    # sources crowd the low ids: the sections' sub-row counts fall off
    rng = np.random.RandomState(21)
    n, e = 500, 6000
    src = np.minimum(rng.exponential(90, e).astype(np.int64), n - 1)
    g = from_edge_list(src, rng.randint(0, n, e), n)
    counts = native.sectioned_counts(g.row_ptr, g.col_idx, n, 100, 5)
    kw = dict(section_rows=100, seg_rows=256)
    if plan is not None:
        fitted = ell_mod.sectioned_plan(counts, 256)
        kw["chunks_plan"] = (fitted if plan == "fitted" else
                             [(c + 1, seg) for c, seg in fitted])

    def build():
        return ell_mod.sectioned_from_graph(g.row_ptr, g.col_idx, n,
                                            **kw)

    got = build()
    monkeypatch.setattr(native, "available", lambda: False)
    want = build()
    heights = [a.shape[1] for a in got.idx]
    assert len(set(heights)) >= 3 and all(h % 8 == 0 for h in heights)
    assert [a.shape[:2] for a in got.idx] == [
        (c + (plan == "roomy"), seg)
        for c, seg in ell_mod.sectioned_plan(counts, 256)]
    assert got.win_rows == want.win_rows
    for a, b in zip(got.idx + got.sub_dst, want.idx + want.sub_dst):
        assert a.shape == b.shape and a.dtype == b.dtype
        assert a.tobytes() == b.tobytes()


def test_sectioned_native_matches_numpy():
    """The native sectioned prep (counts + fill) must produce
    byte-identical tables to the numpy builder across multi-section,
    multi-chunk, plan-forced shapes."""
    import roc_tpu.core.ell as ell_mod
    from roc_tpu import native
    from roc_tpu.core.graph import add_self_edges, synthetic_graph
    if not native.available():
        pytest.skip("native library unavailable")
    g = add_self_edges(synthetic_graph(400, 9, seed=13, power_law=True))

    def build():
        return ell_mod.sectioned_from_graph(
            g.row_ptr, g.col_idx, g.num_nodes, section_rows=64,
            seg_rows=32)

    got = build()
    # force the numpy fallback
    orig = native.available
    try:
        native.available = lambda: False
        want = build()
    finally:
        native.available = orig
    assert got.sec_sizes == want.sec_sizes
    assert len(got.idx) == len(want.idx)
    for a, b in zip(got.idx, want.idx):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(got.sub_dst, want.sub_dst):
        np.testing.assert_array_equal(a, b)
    # counts pass parity too
    nc = native.sectioned_counts(g.row_ptr, g.col_idx, g.num_nodes,
                                 64, -(-g.num_nodes // 64))
    try:
        native.available = lambda: False
        pc = ell_mod.section_sub_counts(g.row_ptr, g.col_idx,
                                        g.num_nodes, g.num_nodes, 64)
    finally:
        native.available = orig
    np.testing.assert_array_equal(nc, pc)


def test_sectioned_native_rejects_out_of_range_cols():
    """Out-of-range columns must be a clean error, not a silent heap
    write (other native entry points validate the same way)."""
    from roc_tpu import native
    if not native.available():
        pytest.skip("native library unavailable")
    row_ptr = np.array([0, 2], dtype=np.int64)
    col_bad = np.array([0, 64], dtype=np.int32)  # 64 == src_rows: OOB
    with pytest.raises(ValueError, match="roc_sectioned_counts"):
        native.sectioned_counts(row_ptr, col_bad, 1, 64, 1)
    with pytest.raises(ValueError, match="roc_sectioned_fill"):
        native.sectioned_fill(row_ptr, col_bad, 1, 64,
                              np.array([64], dtype=np.int64),
                              np.array([8], dtype=np.int64))


def test_block_plan_native_matches_numpy():
    """Native census+fill must produce a byte-identical BlockPlan to
    the numpy pipeline (dense tables, key order, residual CSR,
    saturation behavior)."""
    if not native.available():
        pytest.skip("librocio not built")
    import roc_tpu.native as native_mod
    from roc_tpu.core.graph import Graph, planted_community_csr
    from roc_tpu.ops import blockdense as bd

    g = planted_community_csr(700, 10_000, community_rows=128,
                              intra_frac=0.85, shuffle=False, seed=9)
    # inject heavy duplicates to exercise the saturation path
    row_ptr = np.concatenate([[0], g.row_ptr[1:] + 300])
    col = np.concatenate([np.full(300, 5, dtype=np.int32), g.col_idx])
    g2 = Graph(row_ptr=row_ptr.astype(np.int64), col_idx=col)
    for min_fill, budget in ((8, None), (16, 3 * 128 * 128), (1, None)):
        pn = bd.plan_blocks(g2.row_ptr, g2.col_idx, g2.num_nodes,
                            min_fill=min_fill, a_budget_bytes=budget)
        avail = native_mod.available
        native_mod.available = lambda: False
        try:
            pp = bd.plan_blocks(g2.row_ptr, g2.col_idx, g2.num_nodes,
                                min_fill=min_fill,
                                a_budget_bytes=budget)
        finally:
            native_mod.available = avail
        np.testing.assert_array_equal(pn.a_blocks, pp.a_blocks)
        np.testing.assert_array_equal(pn.src_blk, pp.src_blk)
        np.testing.assert_array_equal(pn.dst_blk, pp.dst_blk)
        np.testing.assert_array_equal(pn.res_row_ptr, pp.res_row_ptr)
        np.testing.assert_array_equal(pn.res_col, pp.res_col)
        assert pn.dense_edges == pp.dense_edges


def test_block_plan_rectangular_native_matches_numpy():
    """num_cols > num_rows (the distributed local-rows x gathered-
    coords plan): native and numpy paths agree byte-for-byte, and
    src tiles index the WIDE space."""
    if not native.available():
        pytest.skip("librocio not built")
    import roc_tpu.native as native_mod
    from roc_tpu.ops import blockdense as bd

    rng = np.random.RandomState(7)
    num_rows, num_cols, E = 200, 900, 4000
    # concentrate sources high so src tiles beyond the square range
    # are exercised
    col = np.sort(rng.randint(500, num_cols, size=E)).astype(np.int32)
    rng.shuffle(col)
    deg = rng.multinomial(E, np.ones(num_rows) / num_rows)
    row_ptr = np.zeros(num_rows + 1, dtype=np.int64)
    np.cumsum(deg, out=row_ptr[1:])
    col.sort()  # per-row order irrelevant; global sort is fine
    pn = bd.plan_blocks(row_ptr, col, num_rows, min_fill=8,
                        num_cols=num_cols)
    avail = native_mod.available
    native_mod.available = lambda: False
    try:
        pp = bd.plan_blocks(row_ptr, col, num_rows, min_fill=8,
                            num_cols=num_cols)
    finally:
        native_mod.available = avail
    assert pn.src_vpad == -(-num_cols // bd.BLOCK) * bd.BLOCK
    assert pn.src_blk.max() >= num_rows // bd.BLOCK  # wide space hit
    for a, b in ((pn.a_blocks, pp.a_blocks), (pn.src_blk, pp.src_blk),
                 (pn.dst_blk, pp.dst_blk),
                 (pn.res_row_ptr, pp.res_row_ptr),
                 (pn.res_col, pp.res_col)):
        np.testing.assert_array_equal(a, b)
    assert pn.dense_edges == pp.dense_edges


def test_unavailable_library_is_reported_not_silent(tmp_path):
    """A library that cannot load still falls back to numpy — but says
    why, once on stderr and in ``status()`` (every run manifest carries
    it), because it decides what aggr_impl='auto' can choose."""
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, ROC_TPU_NATIVE=str(tmp_path / "absent.so"),
               PYTHONPATH=repo, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "-c",
         "from roc_tpu import native; s = native.status(); "
         "assert not native.available(); "
         "assert s['loaded'] is False and 'absent.so' in s['reason'], s"],
        env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    assert r.stderr.count("native librocio.so unavailable") == 1
