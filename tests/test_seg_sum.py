"""The chunk scan's segmented sum (ops/aggregate.py ``scan_seg_sum`` /
``_seg_sum``; the table's bands, core/ell.py ``chunk_band_rows``): a
destination row's sub-rows are summed on the MXU, one banded one-hot
product a tile of sorted partials, before they reach the carry — where
the table's own band says it pays, and nowhere else.  It runs on the
``two_pass`` tables alone (``gather_sum_form``): a ``fused`` table's
kernel adds its own sums into their rows, whatever the bands say.  The
tables here are small enough for the kernel, so the tests of the
segmented sum take the ``two_pass`` form (a VMEM bound no table fits)."""

import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import roc_tpu.core.ell as E
import roc_tpu.ops.aggregate as A
from roc_tpu.ops.aggregate import (_scan_window_sum, aggregate_ell_sect,
                                   aggregate_flat_sum, scan_seg_sum,
                                   scan_window_rows, seg_sum_updates)

N = 99            # num_rows of the hand-made tables: chunk padding


@pytest.fixture
def two_pass(monkeypatch):
    """Every table of the test in the ``two_pass`` form, where the
    segmented sum runs."""
    monkeypatch.setattr(A, "GATHER_SUM_VMEM_BYTES", 0)


# ---- the table's band (core/ell.py chunk_band_rows / chunk_bands) ----

@pytest.mark.parametrize("name, sub_dst, tile, want", [
    # row 1 is a hub: its sub-rows fill the second tile and reach into
    # both neighbours; the widest tile is the last (rows 1..3)
    ("hub_over_three_tiles",
     [[0, 0, 1, 1, 1, 1, 1, 1, 1, 1, 2, 3]], 4, 3),
    # one sub-row a row: a tile spans as many rows as it has sub-rows
    ("one_sub_row_a_row", [[10, 11, 12, 13, 14, 15, 16, 17]], 4, 4),
    # ... and more where the rows jump
    ("rows_jump", [[10, 11, 12, 40, 41, 42, 43, 44]], 4, 31),
    # padding is no destination: the tile [7, 8, N, N] spans two rows
    ("padding_in_a_tile", [[5, 5, 6, 7, 7, 8, N, N]], 4, 3),
    ("padding_fills_a_tile", [[5, 5, 6, 7, N, N, N, N]], 4, 3),
    # an all-padding chunk spans nothing (the rounding's floor is 1)
    ("all_padding_chunk", [[N] * 8], 4, 1),
    ("all_padding_beside_real", [[N] * 8, [0, 0, 0, 9, 9, 9, 9, 9]], 4, 10),
    # stacked [parts, n_chunks, seg]: the widest band of any part
    ("stacked_parts", [[[0, 0, 0, 1, 1, 2, 2, 2]],
                       [[0, 6, 6, 6, 7, 7, 7, 7]]], 4, 7),
    ("tile_is_the_chunk", [[0, 0, 1, 1, 1, 1, 1, 1, 1, 1, 2, 3]], 12, 4),
])
def test_chunk_band_rows_hand_made(monkeypatch, name, sub_dst, tile, want):
    monkeypatch.setattr(E, "BAND_ROWS_MULTIPLE", 1)
    sub_dst = np.asarray(sub_dst, np.int32)
    assert E.chunk_band_rows(sub_dst, N, tile) == want
    # rounded up as the product wants it
    monkeypatch.undo()
    m = E.BAND_ROWS_MULTIPLE
    assert E.chunk_band_rows(sub_dst, N, tile) == -(-want // m) * m


def _ascending_table(rng, n_chunks, seg, num_rows, per_row, pad):
    """[n_chunks, seg] ascending destinations, ~per_row sub-rows a
    row, the last ``pad`` slots of every chunk padding."""
    out = np.full((n_chunks, seg), num_rows, np.int32)
    lo = 0
    for c in range(n_chunks):
        steps = rng.random(seg - pad) < 1.0 / per_row
        out[c, :seg - pad] = lo + np.cumsum(steps)
        lo = out[c, seg - pad - 1]
    assert out[out < num_rows].max() < num_rows
    return out


@pytest.mark.parametrize("seg", [256, 768, 2048, 8192, 24])
def test_chunk_bands_is_the_ladder_of_chunk_band_rows(seg):
    """One pass over the table gives what a pass a tile gives, for the
    tiles that divide the chunk height and for no other."""
    rng = np.random.RandomState(seg)
    d = _ascending_table(rng, 3, seg, 50_000, per_row=6.0, pad=seg // 5)
    bands = E.chunk_bands(d, 50_000)
    assert [t for t, _ in bands] == [t for t in E.SEG_SUM_TILES
                                     if seg % t == 0]
    assert bands == tuple((t, E.chunk_band_rows(d, 50_000, t))
                          for t, _ in bands)
    # stacked: each tile takes the widest band over the parts
    two = np.stack([d, _ascending_table(rng, 3, seg, 50_000, 2.0, 0)])
    assert E.chunk_bands(two, 50_000) == E.max_bands(
        [E.chunk_bands(two[0], 50_000), E.chunk_bands(two[1], 50_000)])


@pytest.mark.parametrize("builder", ["single", "stacked"])
def test_sectioned_ell_carries_its_bands(builder):
    from roc_tpu.core.graph import add_self_edges, synthetic_graph
    g = add_self_edges(synthetic_graph(900, 300, seed=3, power_law=True))
    if builder == "single":
        sect = E.sectioned_from_graph(g.row_ptr, g.col_idx, g.num_nodes,
                                      section_rows=512, seg_rows=4096)
        subs = sect.sub_dst
    else:
        from roc_tpu.core.partition import partition_graph
        from roc_tpu.parallel.distributed import remap_to_padded
        pg = partition_graph(g, 2, node_multiple=8, edge_multiple=8)
        sect = E.sectioned_from_padded_parts(
            pg.part_row_ptr, remap_to_padded(pg), pg.real_nodes,
            pg.part_nodes, src_rows=2 * pg.part_nodes, section_rows=632,
            seg_rows=4096)
        subs = sect.sub_dst
        assert subs[0].shape[0] == 2
    assert len(sect.bands) == len(subs)
    assert any(sect.bands)
    for tb, d in zip(sect.bands, subs):
        assert tb == E.chunk_bands(d, sect.num_rows)
    assert [m[3] for m in sect.meta] == list(sect.bands)


# ---- the rule (ops/aggregate.py scan_seg_sum) ----

def test_rule_keeps_the_scatter_where_a_row_has_one_sub_row():
    """ogbn-arxiv's shape: a tile spans about as many rows as it has
    sub-rows — nothing to reduce, whatever the width."""
    rng = np.random.RandomState(0)
    d = _ascending_table(rng, 2, 8192, 40_000, per_row=1.15, pad=300)
    bands = E.chunk_bands(d, 40_000)
    assert all(b > 0.7 * t for t, b in bands)
    for F in (41, 128, 256, 602):
        assert scan_seg_sum(8192, 20_000, bands, F) is None
    # no bands known (a chunk height no tile divides): the scatter
    assert scan_seg_sum(8200, 20_000, (), 256) is None
    assert seg_sum_updates(2, 8192, None) == [16384, 16384]


@pytest.mark.parametrize("per_row", [6.0, 17.7])
def test_rule_engages_on_a_dense_table(per_row):
    """Products' and Reddit's shapes: the pick is one of the table's
    own (tile, band) pairs, so ``B`` covers every tile's band, and the
    rows a step still adds are a fraction of its sub-rows."""
    rng = np.random.RandomState(1)
    d = _ascending_table(rng, 4, 8192, 40_000, per_row, pad=700)
    bands = E.chunk_bands(d, 40_000)
    win = E.chunk_window_rows(d, 40_000)
    T, B = scan_seg_sum(8192, win, bands, 256)
    assert (T, B) in bands and 8192 % T == 0
    assert B >= E.chunk_band_rows(d, 40_000, T) and B <= win
    before, after = seg_sum_updates(4, 8192, (T, B))
    assert before == 4 * 8192 and after == 4 * (8192 // T) * B
    assert after < before / 3
    # a band taller than the window has no slab to place
    assert scan_seg_sum(8192, 128, ((8192, 1024),), 256) is None


@pytest.mark.parametrize("cap", [E.SECT_SEG_ROWS, E.FLAT_SEG_ROWS])
def test_rule_tile_divides_every_height_fit_chunks_returns(cap):
    rng = np.random.RandomState(cap)
    counts = np.unique(np.r_[1, cap - 1, cap, cap + 1, 16 * cap,
                             rng.randint(0, 20 * cap, 300)])
    engaged = 0
    for c in counts:
        _, seg = E.fit_chunks(int(c), cap)
        # the densest bands a table of this height could have
        bands = tuple((t, E.BAND_ROWS_MULTIPLE) for t in E.SEG_SUM_TILES
                      if seg % t == 0)
        got = scan_seg_sum(seg, 4096, bands, 256)
        if got is not None:
            engaged += 1
            assert seg % got[0] == 0 and got in bands
    assert engaged > len(counts) // 2


# ---- the engaged scan against the segment reference ----

def _dense_graph(n=1200, seed=5):
    """Every row gathers 60-200 sources (8-25 sub-rows a section);
    rows 40 and n - 3 are hubs that gather every source 8 times over
    (a CSR may hold an edge more than once): 608 sub-rows a section
    each, 1,200 in the flat layout — across tile boundaries whatever
    the tile, the second inside the window that is clamped at the
    carry's last rows."""
    rng = np.random.RandomState(seed)
    rows = [rng.choice(n, rng.randint(60, 200), replace=False)
            for _ in range(n)]
    rows[40] = rows[n - 3] = np.tile(np.arange(n), 8)
    row_ptr = np.zeros(n + 1, np.int64)
    np.cumsum([len(r) for r in rows], out=row_ptr[1:])
    return row_ptr, np.concatenate(rows).astype(np.int32), n


def _tables(layout, max_tile=None):
    """The tables and, per section, the bands handed to the scan: the
    table's own, or those of its tiles up to ``max_tile`` (the rule
    then picks a short tile, and a chunk has many)."""
    row_ptr, col, n = _dense_graph()
    if layout == "flat_sum":
        sect = E.flat_sum_from_graph(row_ptr, col, n, seg_rows=2048)
    else:
        sect = E.sectioned_from_graph(row_ptr, col, n, section_rows=608,
                                      seg_rows=4096)
    bands = [tuple(tb for tb in b if not max_tile or tb[0] <= max_tile)
             for b in sect.bands]
    return row_ptr, col, n, sect, bands


def _engaged(sect, F, bands):
    carry = sect.num_rows + 1
    return [scan_seg_sum(d.shape[-1], scan_window_rows(w, carry), b, F)
            for d, w, b in zip(sect.sub_dst, sect.win_rows, bands)]


def _segment_ref(x, row_ptr, col, n, scale=None):
    dst = np.repeat(np.arange(n), np.diff(row_ptr))
    g = np.asarray(x, np.float32)[col]
    if scale is not None:
        g = g * scale[:, None]
    out = np.zeros((n, x.shape[1]), np.float32)
    np.add.at(out, dst, g)
    return out


def _run(layout, sect, x, w=None, bands=None):
    """``bands``: per section, what the scan is handed (None: nothing,
    the scatter path)."""
    sidx, sdst, meta = sect.as_jax()
    n = sect.num_rows
    if bands is None:
        bands = [()] * len(meta)
    if layout == "flat_sum":
        return aggregate_flat_sum(
            x, sidx[0], sdst[0], n, flat_w=None if w is None else w[0],
            win_rows=sect.win_rows[0], bands=bands[0])
    meta = tuple(m[:3] + (b,) for m, b in zip(meta, bands))
    return aggregate_ell_sect(x, sidx, sdst, meta, n, sect_w=w)


_TOL = {"float32": 1e-5, "bfloat16": 1e-2}     # tests/test_ops.py's


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("layout, weighted, max_tile", [
    ("sectioned", False, None), ("sectioned", True, 256),
    ("flat_sum", False, 512), ("flat_sum", True, None)])
def test_engaged_scan_matches_segment(layout, weighted, max_tile, dtype,
                                      two_pass):
    row_ptr, col, n, sect, bands = _tables(layout, max_tile)
    F = 5
    segs = _engaged(sect, F, bands)
    assert all(segs) and len(segs) == (1 if layout == "flat_sum" else 2)
    if max_tile:
        assert all(T <= max_tile for T, _ in segs)
    # the cases the body has to get right are all in these tables
    for d, w, (T, B) in zip(sect.sub_dst, sect.win_rows, segs):
        tiles = d.reshape(d.shape[0], -1, T)
        if T <= 512:
            # a hub's sub-rows fill a tile and reach into its neighbours
            assert ((tiles == 40).any(-1).sum(-1) >= 3).any()
        assert (d[:-1, -1] == d[1:, 0]).any()        # a row over two chunks
        assert (d[-1] == n).any() and d[-1, 0] < n   # padding at the tail
        win = scan_window_rows(w, n + 1)
        assert win < n + 1 and d[-1, 0] > n + 1 - win     # clamped window
        assert B <= win
    rng = np.random.RandomState(2)
    feats = rng.randint(-4, 5, (n + 1, F)).astype(np.float32) / 4
    feats[-1] = 0
    x = jnp.asarray(feats, dtype=dtype)
    w = scale = None
    if weighted:
        d_dst = rng.choice([0.5, 1.0, 2.0], n)
        d_src = rng.choice([0.5, 1.0, 2.0], n)
        w = tuple(jnp.asarray(a)
                  for a in sect.weight_tables(d_dst, d_src))
        dst = np.repeat(np.arange(n), np.diff(row_ptr))
        scale = d_dst[dst] * d_src[col]
    got = _run(layout, sect, x, w, bands)
    assert got.dtype == x.dtype
    want = _segment_ref(x, row_ptr, col, n, scale)
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               rtol=_TOL[dtype], atol=_TOL[dtype])
    if dtype == "float32":
        # and the scatter path's own answer, to rounding
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(_run(layout, sect, x, w)),
            rtol=1e-5, atol=1e-5)


def _row_rel_l2(got, want):
    err = np.linalg.norm(np.asarray(got, np.float32) - want, axis=1)
    return err / np.maximum(np.linalg.norm(want, axis=1), 1e-9)


@pytest.mark.parametrize("layout", ["sectioned", "flat_sum"])
def test_bfloat16_rows_are_no_worse_than_the_scatter_paths(layout,
                                                           two_pass):
    """A tile's sum is float32 and rounded once; the serial scatter
    rounds after every sub-row.  Per row, against the float32
    reference, the engaged path's error is not above the scatter's."""
    row_ptr, col, n, sect, bands = _tables(layout, 512)
    assert all(_engaged(sect, 16, bands))
    rng = np.random.RandomState(3)
    feats = np.r_[rng.standard_normal((n, 16)), np.zeros((1, 16))]
    x = jnp.asarray(feats, dtype=jnp.bfloat16)
    want = _segment_ref(np.asarray(x, np.float32), row_ptr, col, n)
    seg = _row_rel_l2(_run(layout, sect, x, bands=bands), want)
    scat = _row_rel_l2(_run(layout, sect, x), want)
    assert np.median(seg) <= np.median(scat)
    assert seg.max() <= scat.max()
    assert seg.max() < 0.02


def test_flat_sum_engaged_under_the_lane_pad(two_pass):
    """Through ``GraphContext``: a 41-wide ``flat_sum`` sum runs
    zero-padded at 128 lanes (``agg_lane_width``), the rule engaged,
    and the real columns are the reference's; the ``plan`` line's two
    counters say what the scan runs."""
    from roc_tpu.core.graph import Dataset, Graph
    from roc_tpu.train.trainer import make_graph_context
    rng = np.random.RandomState(4)
    n = 1500
    rows = [np.unique(np.r_[v, rng.choice(n, 90, replace=False)])
            for v in range(n)]
    row_ptr = np.zeros(n + 1, np.int64)
    np.cumsum([len(r) for r in rows], out=row_ptr[1:])
    col = np.concatenate(rows).astype(np.int32)
    sym = np.zeros((n, n), bool)
    sym[np.repeat(np.arange(n), np.diff(row_ptr)), col] = True
    sym |= sym.T
    dst, col = np.nonzero(sym)
    row_ptr = np.r_[0, np.cumsum(np.bincount(dst, minlength=n))]
    g = Graph(row_ptr=row_ptr, col_idx=col)
    ds = Dataset(graph=g, features=np.zeros((n, 41), np.float32),
                 labels=np.zeros(n, np.int32), mask=np.ones(n, np.int32),
                 num_classes=2, name="dense")
    gctx = make_graph_context(ds, "flat_sum")
    t = gctx.tables
    assert t.bands and t.idx.shape[0] >= 2
    plan = gctx.agg_window()
    (seg,), ((before, after),) = plan["agg_seg_sum"], \
        plan["agg_carry_updates"]
    n_chunks, seg_rows = plan["agg_chunk_rows"][0]
    assert seg == list(scan_seg_sum(seg_rows, t.win_rows, t.bands, 128))
    assert before == n_chunks * seg_rows
    assert after == n_chunks * (seg_rows // seg[0]) * seg[1] < before / 2
    x = jnp.asarray(rng.randint(-4, 5, (n, 41)).astype(np.float32) / 4)
    want = _segment_ref(np.asarray(x), row_ptr, col, n)
    np.testing.assert_allclose(np.asarray(gctx.aggregate_sum(x)), want,
                               rtol=1e-5, atol=1e-5)
    text = jax.jit(gctx.aggregate_sum).lower(x).as_text()
    assert re.search(r"dot_general.*x128xf32>", text)


# ---- the program's shape ----

def _scatter_update_rows(text):
    return [int(m) for m in re.findall(
        r'"stablehlo\.scatter"\(%[\w#]+, %[\w#]+, %[\w#]+\).*?\}\) : '
        r'\(tensor<[^>]*>, tensor<[^>]*>, tensor<(\d+)x', text, flags=re.S)]


@pytest.mark.parametrize("layout", ["sectioned", "flat_sum"])
def test_lowering_holds_the_product_only_where_engaged(layout,
                                                       monkeypatch):
    _, _, n, sect, bands = _tables(layout, 512)
    x = jnp.zeros((n + 1, 6), jnp.float32)
    segs = _engaged(sect, 6, bands)
    assert all(segs)
    # a fused table: the kernel adds its own sums whatever the bands
    # say — no product, and no scatter outside the kernel
    for b in (bands, None):
        fused = jax.jit(lambda v: _run(layout, sect, v, bands=b)
                        ).lower(x).as_text()
        assert "stablehlo.dot_general" not in fused
        assert _scatter_update_rows(fused) == []
    monkeypatch.setattr(A, "GATHER_SUM_VMEM_BYTES", 0)
    on = jax.jit(lambda v: _run(layout, sect, v, bands=bands)
                 ).lower(x).as_text()
    off = jax.jit(lambda v: _run(layout, sect, v)).lower(x).as_text()
    seg_rows = [d.shape[-1] for d in sect.sub_dst]
    assert on.count("stablehlo.dot_general") == len(segs)
    assert "precision = [HIGHEST, HIGHEST]" in on        # fp32 operands
    # the scatter that is left adds S / T * B rows, not S
    assert _scatter_update_rows(on) == [
        s // T * B for s, (T, B) in zip(seg_rows, segs)]
    assert "stablehlo.dot_general" not in off
    assert _scatter_update_rows(off) == seg_rows
    # bfloat16 operands multiply in the single default pass
    on16 = jax.jit(lambda v: _run(layout, sect, v, bands=bands)).lower(
        x.astype(jnp.bfloat16)).as_text()
    assert on16.count("stablehlo.dot_general") == len(segs)
    assert "HIGHEST" not in on16
    assert re.search(r"dot_general.*xbf16>, tensor<[^>]*xbf16>\) -> "
                     r"tensor<[^>]*xf32>", on16)


def test_unengaged_body_is_the_scatter_body(monkeypatch):
    """No bands, or bands the rule turns down: the same program, token
    for token.  A fused table's program does not read the bands at
    all."""
    _, _, n, sect, _ = _tables("flat_sum")
    sidx, sdst, _ = sect.as_jax()
    out = jnp.zeros((n + 1, 6), jnp.float32)
    x = jnp.zeros((n + 1, 6), jnp.float32)

    def lowered(bands):
        return jax.jit(lambda o, v: _scan_window_sum(
            o, v, (sidx[0], sdst[0]), sect.win_rows[0], bands)
        ).lower(out, x).as_text()

    sparse = tuple((t, t) for t, _ in sect.bands[0])
    assert scan_seg_sum(sdst[0].shape[-1], sect.win_rows[0], sparse,
                        6) is None
    assert lowered(()) == lowered(sparse) == lowered(sect.bands[0])
    monkeypatch.setattr(A, "GATHER_SUM_VMEM_BYTES", 0)
    assert lowered(()) == lowered(sparse)
    assert lowered(()) != lowered(sect.bands[0])


def test_directed_grad_through_the_engaged_scan(two_pass):
    """Autodiff through the engaged scan on a directed graph (the
    fallback where the backward is not the forward on the cotangent):
    A^T times the cotangent, as the segment reference gives it."""
    from roc_tpu.ops.aggregate import aggregate_segment
    row_ptr, col, n, sect, bands = _tables("sectioned", 512)
    assert all(_engaged(sect, 3, bands))
    rng = np.random.RandomState(6)
    x = jnp.asarray(np.r_[rng.randint(-3, 4, (n, 3)),
                          np.zeros((1, 3))].astype(np.float32))
    cot = jnp.asarray(rng.randint(-3, 4, (n, 3)).astype(np.float32))
    dst = jnp.asarray(np.repeat(np.arange(n), np.diff(row_ptr)))
    # the graph IS directed
    a = np.zeros((n, n), bool)
    a[np.asarray(dst), col] = True
    assert (a != a.T).any()
    got = jax.grad(lambda v: (_run("sectioned", sect, v, bands=bands)
                              * cot).sum())(x)
    want = jax.grad(lambda v: (aggregate_segment(
        v, jnp.asarray(col), dst, n) * cot).sum())(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# ---- the plan line ----

@pytest.mark.parametrize("impl", ["sectioned", "flat_sum"])
def test_plan_line_carries_the_counters_through_cli(impl, tmp_path):
    _plan_line_counters(impl, tmp_path, "fused")


@pytest.mark.parametrize("impl", ["sectioned", "flat_sum"])
def test_plan_line_counts_two_pass_carry_updates_through_cli(
        impl, tmp_path, two_pass):
    _plan_line_counters(impl, tmp_path, "two_pass")


def _plan_line_counters(impl, tmp_path, form):
    from roc_tpu.obs import events
    from roc_tpu.train import cli
    ev = str(tmp_path / "events.jsonl")
    try:
        assert cli.main(["--cpu", "--no-compile-cache", "--model", "gcn",
                         "-layers", "16-8-4", "-e", "1", "--impl", impl,
                         "--events", ev]) == 0
    finally:
        events.get_bus().close()
        events.configure(console=False)
    with open(ev) as f:
        res = [json.loads(line) for line in f]
    res = [r for r in res if r["cat"] == "manifest"][-1]["resolved"]
    assert res["aggr_impl"] == impl
    chunks = res["agg_chunk_rows"]
    assert {f for f, _ in res["agg_gather_sum"]} == {form}
    assert len(chunks) == len(res["agg_seg_sum"]) \
        == len(res["agg_carry_updates"]) == len(res["agg_window_rows"]) >= 1
    # a 512-vertex graph of degree ~10 has nothing to reduce
    assert res["agg_seg_sum"] == [None] * len(chunks)
    if form == "fused":
        # the kernel adds its own sums: nothing left for the carry
        assert res["agg_carry_updates"] == [[n * s, 0] for n, s in chunks]
    else:
        assert res["agg_carry_updates"] == [[n * s, n * s]
                                            for n, s in chunks]


@pytest.mark.parametrize("impl, dtype", [("sectioned", "mixed"),
                                         ("flat_sum", "float32")])
def test_plan_line_carries_agg_gather_sum_through_cli(impl, dtype,
                                                      tmp_path):
    """The ``plan`` line says how each scanned table's chunk steps make
    their partials (ops/aggregate.py ``gather_sum_form``): a small
    graph's tables fit the kernel's VMEM, every slot of a pass fused."""
    from roc_tpu.obs import events
    from roc_tpu.ops.aggregate import gather_sum_slots
    from roc_tpu.train import cli
    ev = str(tmp_path / "events.jsonl")
    try:
        assert cli.main(["--cpu", "--no-compile-cache", "--model", "gcn",
                         "-layers", "16-8-4", "-e", "1", "--impl", impl,
                         "--dtype", dtype, "--events", ev]) == 0
    finally:
        events.get_bus().close()
        events.configure(console=False)
    with open(ev) as f:
        res = [json.loads(line) for line in f]
    res = [r for r in res if r["cat"] == "manifest"][-1]["resolved"]
    chunks = res["agg_chunk_rows"]
    assert res["agg_gather_sum"] == [["fused", n * s * 8]
                                     for n, s in chunks]
    # read at the widest sum op's lanes and the compute dtype
    assert gather_sum_slots(*chunks[0], 513, 128, jnp.bfloat16) \
        == res["agg_gather_sum"][0]
