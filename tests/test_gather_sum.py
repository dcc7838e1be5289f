"""How a chunk step of the sum scan makes its partials (ops/aggregate.py
``gather_sum_form`` / ``_gather_sum``): a table whose words fit the
kernel's VMEM is summed by the Pallas kernel — a sub-row's 8 slots in
float32, rounded once, nothing gathered written to HBM (interpreted
here on the CPU) — any other is gathered slot-major and summed over the
leading axis.  Both forms against the edge-list reference, forward and
grad, in every table shape the scan meets."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import roc_tpu.core.ell as E
import roc_tpu.ops.aggregate as A
from roc_tpu.ops.aggregate import (aggregate_ell_sect, aggregate_flat_sum,
                                   gather_sum_form, gather_sum_slots,
                                   scan_seg_sum, scan_window_rows)

MiB = 1 << 20


@pytest.fixture(params=["fused", "two_pass"])
def form(request, monkeypatch):
    """The form every table of the test takes: the kernel (the rule's
    bound as it is: these tables are small) or the slot-major XLA
    gather (a bound no table fits)."""
    if request.param == "two_pass":
        monkeypatch.setattr(A, "GATHER_SUM_VMEM_BYTES", 0)
    return request.param


def _graph(n=600, seed=7):
    """8-60 sources a row, row 11 a hub that gathers every source
    twice (a CSR may hold an edge more than once); directed."""
    rng = np.random.RandomState(seed)
    rows = [rng.choice(n, rng.randint(8, 60), replace=False)
            for _ in range(n)]
    rows[11] = np.tile(np.arange(n), 2)
    row_ptr = np.zeros(n + 1, np.int64)
    np.cumsum([len(r) for r in rows], out=row_ptr[1:])
    return row_ptr, np.concatenate(rows).astype(np.int32), n


def _tables(layout, row_ptr, col, n):
    if layout == "flat_sum":
        return E.flat_sum_from_graph(row_ptr, col, n, seg_rows=1024)
    return E.sectioned_from_graph(row_ptr, col, n, section_rows=256,
                                  seg_rows=1024)


def _pad_chunk(sect, n):
    """The tables with one all-padding chunk appended to each section:
    dummy sources, destination ``num_rows``, weight 0."""
    idx, dst = [], []
    for i, d in zip(sect.idx, sect.sub_dst):
        dummy = i[d == n].reshape(-1)[0]
        idx.append(np.concatenate([i, np.full((1,) + i.shape[1:], dummy,
                                              i.dtype)]))
        dst.append(np.concatenate([d, np.full((1, d.shape[1]), n,
                                              d.dtype)]))
    return idx, dst


def _run(layout, sect, x, n, w=None, bands=None, weights_fp32=False,
         slot_major=False, pad_chunk=False):
    idx, dst = list(sect.idx), list(sect.sub_dst)
    if pad_chunk:
        idx, dst = _pad_chunk(sect, n)
        if w is not None:
            w = [np.concatenate([a, np.zeros((1,) + a.shape[1:], a.dtype)])
                 for a in w]
    bands = bands or [()] * len(idx)
    if layout == "flat_sum":
        i0, w0 = idx[0], None if w is None else w[0]
        if slot_major:
            nc = i0.shape[0]
            i0 = i0.transpose(0, 2, 1).reshape(nc, -1)
            if w0 is not None:
                w0 = w0.transpose(0, 2, 1).reshape(nc, -1)
        return aggregate_flat_sum(
            x, jnp.asarray(i0), jnp.asarray(dst[0]), n,
            flat_w=None if w0 is None else jnp.asarray(w0),
            win_rows=sect.win_rows[0], bands=bands[0],
            weights_fp32=weights_fp32, slot_major=slot_major)
    meta = tuple(m[:3] + (b,) for m, b in zip(sect.meta, bands))
    return aggregate_ell_sect(
        x, tuple(map(jnp.asarray, idx)), tuple(map(jnp.asarray, dst)),
        meta, n, sect_w=None if w is None else tuple(map(jnp.asarray, w)))


# layout, weights, F, dtype, segmented sum, slot-major, padding chunk
CASES = [
    ("sectioned", None, 41, "float32", False, False, False),
    ("sectioned", None, 128, "bfloat16", True, False, False),
    ("sectioned", "table", 256, "bfloat16", False, False, True),
    ("sectioned", "table", 41, "bfloat16", True, False, False),
    ("sectioned", "table", 256, "float32", True, False, False),
    ("sectioned", None, 256, "bfloat16", False, False, False),
    ("flat_sum", None, 256, "float32", False, False, True),
    ("flat_sum", "table", 128, "bfloat16", True, False, False),
    ("flat_sum", "table", 41, "float32", False, True, False),
    ("flat_sum", "fp32", 128, "bfloat16", False, True, True),
    ("flat_sum", "fp32", 256, "bfloat16", True, True, False),
    ("flat_sum", "fp32", 41, "float32", True, False, False),
]


@pytest.mark.parametrize(
    "layout, weights, F, dtype, seg_sum, slot_major, pad_chunk", CASES)
def test_kept_body_matches_segment_forward_and_grad(
        form, layout, weights, F, dtype, seg_sum, slot_major, pad_chunk):
    row_ptr, col, n = _graph()
    sect = _tables(layout, row_ptr, col, n)
    assert {gather_sum_form(m[1] + 1, F, dtype)
            for m in sect.meta} == {form}
    bands = list(sect.bands) if seg_sum else None
    if seg_sum:
        carry = n + 1
        assert any(scan_seg_sum(d.shape[-1], scan_window_rows(w, carry),
                                b, F)
                   for d, w, b in zip(sect.sub_dst, sect.win_rows,
                                      sect.bands))
    rng = np.random.RandomState(F)
    feats = rng.randint(-4, 5, (n + 1, F)).astype(np.float32) / 4
    feats[-1] = 0
    x = jnp.asarray(feats, dtype=dtype)
    dst = np.repeat(np.arange(n), np.diff(row_ptr))
    w, scale = None, np.ones(len(col), np.float32)
    if weights:
        d = rng.choice([0.5, 1.0, 2.0], n)
        w = [np.asarray(a, np.float32) for a in sect.weight_tables(d, d)]
        scale = (d[dst] * d[col]).astype(np.float32)
    run = lambda v: _run(layout, sect, v, n, w, bands,   # noqa: E731
                         weights == "fp32", slot_major, pad_chunk)
    got = run(x)
    assert got.dtype == x.dtype and got.shape == (n, F)

    def ref(v):
        return jax.ops.segment_sum(
            v.astype(jnp.float32)[jnp.asarray(col)]
            * jnp.asarray(scale)[:, None], jnp.asarray(dst),
            num_segments=n)

    _close(got, ref(x), dtype)
    # the graph is directed: autodiff through the scan is A^T
    cot = jnp.asarray(rng.randint(-3, 4, (n, F)).astype(np.float32) / 2)
    g = jax.grad(lambda v: (run(v).astype(jnp.float32) * cot).sum())(x)
    want = jax.grad(lambda v: (ref(v) * cot).sum())(x)
    # (the trailing dummy row takes the padding's cotangent: no vertex)
    _close(g[:n], want[:n], dtype)


def _close(got, want, dtype):
    """float32: elementwise to rounding; bfloat16: each row to 3% of
    its norm, the benchmark's row limit (the hub's row sums 1,200
    weighted rows into a bfloat16 carry, rounded a chunk: 1.7%)."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if dtype == "float32":
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        return
    err = np.linalg.norm(got - want, axis=1)
    assert (err <= 3e-2 * np.maximum(np.linalg.norm(want, axis=1), 1)
            ).all()


def _row_err(got, want):
    err = np.linalg.norm(np.asarray(got, np.float64) - want, axis=1)
    return err / np.maximum(np.linalg.norm(want, axis=1), 1e-30)


def test_bf16_partials_are_no_worse_than_the_two_pass_bodies():
    """A sub-row's partial, against its exact sum: the kernel sums the
    8 products in float32 and rounds once, so per row it is no worse
    than the slot-major two-pass body nor than the gather + bfloat16
    reduce the scan had before (each product rounded to bfloat16)."""
    rng = np.random.RandomState(8)
    table = jnp.asarray(rng.standard_normal((500, 256)),
                        jnp.bfloat16).at[-1].set(0)
    idx = jnp.asarray(rng.randint(0, 500, (2048, 8)), jnp.int32)
    w = jnp.asarray(rng.uniform(0.01, 1, (2048, 8)), jnp.bfloat16)
    t64, w64 = np.asarray(table, np.float64), np.asarray(w, np.float64)
    exact = (t64[np.asarray(idx)] * w64[:, :, None]).sum(axis=1)
    kernel = _row_err(A._gather_sum(table, A._vmem_words(table), idx.T,
                                    w.T, jnp.bfloat16), exact)
    two = _row_err((table[idx.T].astype(jnp.float32)
                    * w.T.astype(jnp.float32)[:, :, None]).sum(axis=0)
                   .astype(jnp.bfloat16), exact)
    parent = _row_err((table[idx] * w[:, :, None]).sum(axis=1), exact)
    for other in (two, parent):
        assert np.median(kernel) <= np.median(other) * (1 + 1e-6)
        assert kernel.max() <= other.max() * (1 + 1e-6)
    assert kernel.max() <= 2.0 ** -8          # half an ulp, and a bit


@pytest.mark.parametrize("layout", ["sectioned", "flat_sum"])
def test_bf16_rows_through_the_scan_are_no_worse_than_two_pass(
        layout, monkeypatch):
    """Through the whole scan, per row against the float32 reference:
    the median no worse than the two-pass body's (the worst row is the
    bfloat16 carry's, a chunk at a time, in both)."""
    row_ptr, col, n = _graph(seed=9)
    sect = _tables(layout, row_ptr, col, n)
    rng = np.random.RandomState(3)
    x = jnp.asarray(np.r_[rng.standard_normal((n, 64)),
                          np.zeros((1, 64))], jnp.bfloat16)
    d = rng.uniform(0.05, 1.0, n)
    w = [np.asarray(a, np.float32) for a in sect.weight_tables(d, d)]
    dst = np.repeat(np.arange(n), np.diff(row_ptr))
    want = np.zeros((n, 64))
    np.add.at(want, dst, np.asarray(x, np.float64)[col]
              * np.asarray(jnp.asarray(d[dst] * d[col], jnp.bfloat16)
                           .astype(np.float32)).astype(np.float64)[:, None])
    fused = _row_err(_run(layout, sect, x, n, w), want)
    monkeypatch.setattr(A, "GATHER_SUM_VMEM_BYTES", 0)
    two = _row_err(_run(layout, sect, x, n, w), want)
    assert np.median(fused) <= np.median(two) * 1.01
    assert max(fused.max(), two.max()) < 0.03


# ---- the kernel alone, interpreted ----

@pytest.mark.parametrize("F, dtype, weights, seg", [
    (256, "bfloat16", "table", 1024),   # two bf16 columns a word
    (300, "bfloat16", None, 40),        # two groups, zero-padded; a
                                        # chunk the tile does not divide
    (41, "bfloat16", "fp32", 520),      # float32 words under a vreg
    (128, "float32", "table", 8),
    (256, "float32", None, 512),
])
def test_kernel_interpreted_matches_the_float32_sum(F, dtype, weights, seg):
    rng = np.random.RandomState(seg)
    R = 300
    table = jnp.asarray(rng.standard_normal((R, F)), dtype).at[-1].set(0)
    idx = jnp.asarray(rng.randint(0, R, (seg, 8)), jnp.int32)
    w = None
    if weights:
        w = jnp.asarray(rng.uniform(0, 1, (seg, 8)),
                        dtype if weights == "table" else jnp.float32)
    words = A._vmem_words(table)
    packed = jnp.dtype(dtype) == jnp.bfloat16 and F > 128
    assert words.dtype == (jnp.uint32 if packed else jnp.float32)
    # rows to a multiple of 8, the padding zero
    assert words.shape == (304, A._vmem_lanes(F, dtype) if packed else F)
    assert not np.asarray(words[R:]).any()
    got = jax.jit(lambda t, i, v: A._gather_sum(
        t, A._vmem_words(t), i.T, None if v is None else v.T,
        table.dtype))(table, idx, w)
    g = np.asarray(table, np.float32)[np.asarray(idx)]
    if w is not None:
        g = g * np.asarray(w, np.float32)[:, :, None]
    want = g.sum(axis=1, dtype=np.float32)
    assert got.shape == (seg, F) and got.dtype == table.dtype
    # rounded once from the float32 sum: to the float32 sum's own
    # rounding, then within one ulp of the output's dtype
    ulp = 2.0 ** -(8 if table.dtype == jnp.bfloat16 else 22)
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               rtol=ulp, atol=1e-5)


# ---- the rule ----

@pytest.mark.parametrize("rows, F, dtype, want", [
    (65_537, 256, "bfloat16", "fused"),    # a Reddit section
    (65_537, 41, "bfloat16", "fused"),
    (65_537, 256, "float32", "fused"),     # 64.03 MiB: measured held
    (65_537, 512, "bfloat16", "two_pass"),  # words as the float32's,
                                            # wider blocks
    (65_537, 512, "float32", "two_pass"),
    (16_000, 1_024, "float32", "two_pass"),   # words fit, blocks do not
    (15_000, 1_024, "float32", "fused"),
    (56_449, 256, "bfloat16", "fused"),    # an arxiv section
    (2_449_030, 256, "bfloat16", "two_pass"),   # products' flat table
    (2_449_030, 128, "bfloat16", "two_pass"),
    (1_939_744, 128, "bfloat16", "two_pass"),   # the typed passes
])
def test_the_rule_reads_the_tables_words(rows, F, dtype, want):
    assert gather_sum_form(rows, F, dtype) == want
    assert gather_sum_slots(3, 1024, rows, F, dtype) == [want, 3 * 1024 * 8]


def test_vmem_of_the_largest_table_held_fits_the_bound():
    lanes = A._vmem_lanes(256, jnp.float32)
    words = -(-65_537 // 8) * 8 * lanes * 4
    # the words, the float32 accumulator and two output blocks
    held = A._vmem_bytes(65_537, 256, jnp.float32)
    assert held == words + 512 * 256 * 4 + 2 * 512 * 256 * 4
    assert 64 * MiB < held <= A.GATHER_SUM_VMEM_BYTES < 128 * MiB - 16 * MiB
    # packed bfloat16: the accumulator holds both halves of a word
    assert A._vmem_bytes(8, 256, jnp.bfloat16, 128) == (
        8 * 128 * 4 + 128 * 256 * 4 + 2 * 128 * 256 * 2)
    # bf16 wider than a vreg: half the words
    assert A._vmem_lanes(256, jnp.bfloat16) == 128
    assert A._vmem_lanes(300, jnp.bfloat16) == 256
    assert A._vmem_lanes(41, jnp.bfloat16) == 128


def test_fused_lowering_gathers_no_slot_rows(monkeypatch):
    """The kernel's form holds no ``[8, seg, F]`` gather; the two-pass
    form does."""
    row_ptr, col, n = _graph()
    sect = _tables("flat_sum", row_ptr, col, n)
    seg = sect.sub_dst[0].shape[-1]
    x = jnp.zeros((n + 1, 128), jnp.bfloat16)
    slot_rows = f"tensor<8x{seg}x128xbf16>"

    def text():
        return jax.jit(lambda v: _run("flat_sum", sect, v, n)
                       ).lower(x).as_text()

    assert slot_rows not in text()
    monkeypatch.setattr(A, "GATHER_SUM_VMEM_BYTES", 0)
    assert slot_rows in text()
