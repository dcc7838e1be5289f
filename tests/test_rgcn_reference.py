"""The system's R-GCN over a typed graph (``models/rgcn.py build_rgcn``
through ``Model.apply`` and the relation aggregation) against the
benchmark's plain reference (``bench/references/rgcn.py``) on seeded
random weights, on the CPU, float32, on a small typed graph — 4 kinds,
7 relations, a hub field, a paper nobody cites, an author with one
paper, an institution no author names (its kind's only in-relation is
empty there): logits over all kinds, loss and the gradient of every
parameter including each embedding table, under every layout ``auto``
can reach for it ('flat_sum'; 'segment' is the in-program reference)
and both sides of the mean; the hand-written backward against autodiff
of the 'segment' path; ``--dtype mixed`` inside the tolerances of the
cell ``rgcn-mag.fullgraph-typed``; the scopes of a compiled train step;
the ``plan`` line; the derivation of relations from kind counts; the
loss program's cut last layer (``Model.loss_cut``: the relations into
kind 0 alone) against the uncut op list, its tables, and the programs
it must leave alone; and the typed refusals."""

import hashlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from roc_tpu.core.graph import Dataset, Graph, save_dataset
from roc_tpu.core.memory import param_elems
from roc_tpu.core.relations import (GATHER_FIRST, REL_ORDERS,
                                    TRANSFORM_FIRST, derive_typed,
                                    resolve_rel_order)
from roc_tpu.models.rgcn import build_rgcn
from roc_tpu.obs.scopes import (EMBED_SCOPE, OPT_EMBED_SCOPE,
                                parse_op_name)
from roc_tpu.ops import dense
from roc_tpu.ops.aggregate import aggregate_flat_sum
from roc_tpu.train.trainer import (TrainConfig, Trainer, cast_floats,
                                   make_graph_context, model_features)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")

# paper, author, institution, field_of_study
KINDS = (40, 55, 6, 9)
OFF = np.concatenate([[0], np.cumsum(KINDS)])
V = int(OFF[-1])
F, H, CLASSES = 12, 8, 5
LAYERS = [F, H, CLASSES]
EMBED = (1, 2, 3)
RELATIONS = ((0, 0), (0, 1), (0, 3), (1, 0), (1, 2), (2, 1), (3, 0))
LONELY_PAPER, ONE_PAPER_AUTHOR = 7, int(OFF[1]) + 3
EMPTY_INSTITUTION, HUB_FIELD = int(OFF[2]) + 5, int(OFF[3])
IMPLS = ("flat_sum", "segment")
CASES = [(impl, order) for impl in IMPLS for order in REL_ORDERS]


def _typed_dataset():
    """The union CSR: symmetric, every self edge, kinds as id ranges."""
    rng = np.random.default_rng(3)

    def pairs(a, b, n):
        return (rng.integers(OFF[a], OFF[a + 1], n),
                rng.integers(OFF[b], OFF[b + 1], n))

    us, vs = [], []
    for a, b, n in [(1, 2, 40), (1, 0, 150), (0, 0, 120), (0, 3, 90)]:
        u, v = pairs(a, b, n)
        keep = ((u != LONELY_PAPER) | (a != 0) | (b != 0)) \
            & (v != LONELY_PAPER if (a, b) == (0, 0) else True) \
            & (u != ONE_PAPER_AUTHOR) & (v != EMPTY_INSTITUTION)
        us.append(u[keep])
        vs.append(v[keep])
    # the author with exactly one paper, the hub field every fourth
    # paper belongs to
    us.append(np.array([ONE_PAPER_AUTHOR]))
    vs.append(np.array([11]))
    hub = np.arange(0, KINDS[0], 4)
    us.append(hub)
    vs.append(np.full(hub.shape, HUB_FIELD))
    u, v = np.concatenate(us), np.concatenate(vs)
    diag = np.arange(V)
    key = np.unique(np.concatenate([v * V + u, u * V + v,
                                    diag * V + diag]))
    dst, src = key // V, key % V
    row_ptr = np.zeros(V + 1, np.int64)
    np.cumsum(np.bincount(dst, minlength=V), out=row_ptr[1:])
    graph = Graph(row_ptr, src.astype(np.int32))
    feats = rng.standard_normal((V, F)).astype(np.float32)
    feats[KINDS[0]:] = 0.0
    labels = np.zeros(V, np.int32)
    labels[:KINDS[0]] = rng.integers(0, CLASSES, KINDS[0])
    mask = np.zeros(V, np.int32)
    mask[:KINDS[0]] = rng.integers(1, 4, KINDS[0])
    ds = Dataset(graph, feats, labels, mask, CLASSES, name="typed")
    ds.typed = derive_typed(graph, KINDS)
    return ds


@pytest.fixture(scope="module")
def ds():
    return _typed_dataset()


@pytest.fixture(scope="module")
def ref():
    sys.path.insert(0, BENCH)
    try:
        import reference
        from references import rgcn
    finally:
        sys.path.remove(BENCH)
    return reference, rgcn


SPEC = {"family": "rgcn", "layers": LAYERS, "node_types": list(KINDS),
        "embed_types": list(EMBED)}


def _model(order=None):
    model = build_rgcn(LAYERS, 0.5, node_types=KINDS, embed_types=EMBED,
                       relations=RELATIONS)
    if order is not None:
        model = model.with_rel_orders(lambda i, o: order)
        assert set(model.rel_orders()) == {order}
    return model


def _params():
    return _model().init_params(jax.random.PRNGKey(5))


PARAM_NAMES = ([f"embed_{k}" for k in EMBED]
               + [f"rel{l}_{s}_{d}" for l in (0, 1) for s, d in RELATIONS]
               + [f"root{l}_{k}{b}" for l in (0, 1) for k in range(4)
                  for b in ("", "_b")])
# with the loss on kind 0 alone, two layers deep: the last layer's
# weights into any other kind, and the first layer's into institutions
# (whose rows only authors read, one layer from the end), get no
# gradient — the rows of the last layer the loss never reads
DEAD = ({f"rel1_{s}_{d}" for s, d in RELATIONS if d != 0}
        | {f"root1_{k}{b}" for k in (1, 2, 3) for b in ("", "_b")}
        | {"rel0_1_2", "root0_2", "root0_2_b"})
_cache = {}


def _system(ds, impl, order, dtype=jnp.float32):
    key = (impl, order, jnp.dtype(dtype).name)
    if key in _cache:
        return _cache[key]
    model, params = _model(order), _params()
    gctx = make_graph_context(ds, impl, rel_orders=model.rel_orders())
    feats = jnp.asarray(model_features(model, ds), dtype)
    labels, mask = jnp.asarray(ds.labels), jnp.asarray(ds.mask)

    def objective(p):
        return model.loss_fn(cast_floats(p, dtype), feats, labels, mask,
                             gctx, key=None, train=False)

    (loss, logits), grads = jax.value_and_grad(objective,
                                               has_aux=True)(params)
    _cache[key] = (np.asarray(logits, np.float32), float(loss),
                   {k: np.asarray(v) for k, v in grads.items()})
    return _cache[key]


def _ref_graph(reference, ds, chunk=100):
    row_ptr, col = ds.graph.row_ptr, ds.graph.col_idx
    src = np.asarray(col, np.int32)
    dst = np.repeat(np.arange(V, dtype=np.int32), np.diff(row_ptr))
    whole = (src.shape[0] // chunk) * chunk
    assert 0 < whole < src.shape[0]
    return reference.Graph(
        *(jnp.asarray(a) for a in (
            src[:whole].reshape(-1, chunk), dst[:whole].reshape(-1, chunk),
            src[whole:], dst[whole:],
            np.diff(row_ptr).astype(np.float32))), V)


@pytest.fixture(scope="module")
def plain(ref, ds):
    """Reference logits, loss and gradients, float32; the edge list in
    100-edge chunks and a tail."""
    reference, rgcn = ref
    g, params = _ref_graph(reference, ds), _params()
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(ds.features)
        logits = rgcn.forward(params, x, g, SPEC)
        loss, grads = rgcn.loss_and_grads(
            params, x, jnp.asarray(ds.labels), jnp.asarray(ds.mask), g,
            SPEC)
    return (np.asarray(logits), float(loss),
            {k: np.asarray(v) for k, v in grads.items()})


# ---------------------------------------------- relations from kinds

def test_relations_are_derived_from_kind_counts(ds):
    ty = ds.typed
    assert ty.relations == RELATIONS          # (1, 3), (2, 0), ... unseen
    assert (1, 3) not in ty.relations and (2, 2) not in ty.relations
    # self edges are not relation edges
    assert ty.num_edges == ds.graph.num_edges - V
    assert (ty.e_src != ty.e_dst).all()
    assert ty.src_rows == sum(KINDS[s] for s, _ in RELATIONS)
    assert ty.dst_rows == sum(KINDS[d] for _, d in RELATIONS)
    # per-relation in-degree, by brute force over the stored edges
    dst = np.repeat(np.arange(V), np.diff(ds.graph.row_ptr))
    src = ds.graph.col_idx
    kind = np.searchsorted(OFF, np.arange(V), side="right") - 1
    for r, (s, d) in enumerate(RELATIONS):
        sel = (kind[src] == s) & (kind[dst] == d) & (src != dst)
        deg = np.bincount(dst[sel] - OFF[d], minlength=KINDS[d])
        inv = ty.inv_deg[ty.dst_off[r]:ty.dst_off[r + 1]]
        np.testing.assert_allclose(
            inv, np.where(deg > 0, 1.0 / np.maximum(deg, 1), 0.0),
            rtol=1e-6)
        assert ty.describe()[r]["edges"] == int(sel.sum())
        assert ty.describe()[r]["deg_max"] == int(deg.max())


def test_the_fixture_holds_the_awkward_vertices(ds):
    ty = ds.typed
    rel = {p: r for r, p in enumerate(RELATIONS)}

    def deg(pair, v):
        r = rel[pair]
        inv = ty.inv_deg[ty.dst_off[r] + v - OFF[pair[1]]]
        return 0 if inv == 0 else int(round(1.0 / inv))

    assert deg((0, 0), LONELY_PAPER) == 0          # nobody cites it
    assert deg((0, 1), ONE_PAPER_AUTHOR) == 1
    # an institution's only in-relation is author -> institution
    assert deg((1, 2), EMPTY_INSTITUTION) == 0
    assert deg((0, 3), HUB_FIELD) >= KINDS[0] // 4


@pytest.mark.parametrize("bad", [(40, 55, 6), (40, 55, 6, 10),
                                 (110, 0, 0, 0)])
def test_kind_counts_must_cover_the_graph(ds, bad):
    with pytest.raises(ValueError):
        derive_typed(ds.graph, bad)


@pytest.mark.parametrize("name", ["tf_fwd", "tf_bwd", "gf_fwd", "gf_bwd"])
def test_pass_tables_hold_every_relation_edge_once(ds, name):
    """A pass's CSR is a permutation of the relation edges, and the
    weights read off its width-8 table are each edge's 1 / deg_r(v)."""
    from roc_tpu.core.ell import flat_sum_from_graph
    ty = ds.typed
    row_ptr, col, n_into, n_out = ty.pass_csr(name)
    into, out_of = ty.pass_edges(name)
    assert row_ptr[-1] == ty.num_edges
    got = np.repeat(np.arange(n_into), np.diff(row_ptr)) * n_out + col
    np.testing.assert_array_equal(np.sort(got),
                                  np.sort(into * n_out + out_of))
    sect = flat_sum_from_graph(row_ptr, col, n_into, src_rows=n_out)
    w = ty.slot_weights(name, sect.idx[0], sect.sub_dst[0])
    real = sect.idx[0] != n_out
    assert int(real.sum()) == ty.num_edges
    assert (w[~real] == 0).all() and (w[real] > 0).all()
    # summed per dst-stack row, every relation's weights add up to 1
    np.testing.assert_allclose(w.sum(), (ty.inv_deg > 0).sum(),
                               rtol=1e-5)
    assert ty.pass_sub_rows(name) * 8 <= sect.idx[0].size


INTO_PAPERS = tuple(r for r, (_, d) in enumerate(RELATIONS) if d == 0)


@pytest.mark.parametrize("name", ["tf_fwd", "tf_bwd", "gf_fwd", "gf_bwd"])
def test_cut_tables_hold_every_edge_into_kind_0_once(ds, name):
    """The restricted graph's pass is the relation edges into kind 0,
    each once, each at the weight the whole table gives it; its sorted
    order is the whole pass's, masked — what its own sort would give."""
    from roc_tpu.core.ell import flat_sum_from_graph
    ty = ds.typed
    assert INTO_PAPERS == (0, 3, 6)
    cut = ty.restrict(INTO_PAPERS)
    assert cut is ty.restrict(INTO_PAPERS)
    assert cut.relations == tuple(RELATIONS[r] for r in INTO_PAPERS)
    into_papers = ty.e_dst < KINDS[0]
    assert cut.num_edges == int(into_papers.sum()) < ty.num_edges
    assert cut.dst_nodes == KINDS[0] and cut.num_nodes == V
    assert cut.pass_rows(name) == {
        "tf_fwd": (KINDS[0], cut.src_rows),
        "tf_bwd": (cut.src_rows, KINDS[0]),
        "gf_fwd": (3 * KINDS[0], V), "gf_bwd": (V, 3 * KINDS[0])}[name]

    def weighted_edges(graph):
        """{(relation, u, v): weight} read off the pass's own table."""
        row_ptr, col, n_into, n_out = graph.pass_csr(name)
        sect = flat_sum_from_graph(row_ptr, col, n_into, src_rows=n_out)
        idx, sub = sect.idx[0], sect.sub_dst[0]
        w = graph.slot_weights(name, idx, sub)
        row = np.broadcast_to(sub[..., None], idx.shape)
        real = idx != n_out
        into, out_of, w = row[real], idx[real], w[real]
        stacked, vertex = ((out_of, into) if name in ("tf_fwd", "gf_bwd")
                           else (into, out_of))
        off = graph.src_off if name[:2] == "tf" else graph.dst_off
        r = np.searchsorted(off, stacked, side="right") - 1
        end = 0 if name[:2] == "tf" else 1
        lo = graph.offsets[[p[end] for p in graph.relations]]
        other = stacked - off[r] + lo[r]
        u, v = (other, vertex) if name[:2] == "tf" else (vertex, other)
        keys = list(zip((graph.relations[i] for i in r), u.tolist(),
                        v.tolist()))
        assert len(set(keys)) == len(keys) == graph.num_edges
        return dict(zip(keys, w.tolist()))

    whole, part = weighted_edges(ty), weighted_edges(cut)
    assert part == {k: w for k, w in whole.items() if k[0][1] == 0}
    for graph in (ty, cut):
        into, _ = graph.pass_edges(name)
        # gf_fwd's counts are the degrees derive_typed made, not recounted
        np.testing.assert_array_equal(
            graph._pass_counts(name),
            np.bincount(into, minlength=graph.pass_rows(name)[0]))
        if name == "tf_fwd":        # stored order is the pass's
            assert graph._pass_order(name) is None
            assert (np.diff(into) >= 0).all()
        else:
            np.testing.assert_array_equal(
                graph._pass_order(name), np.argsort(into, kind="stable"))


@pytest.mark.parametrize("bad", [(), (3, 0), (0, 0), (0, 7), (-1, 2)])
def test_restrict_takes_increasing_relation_indices(ds, bad):
    with pytest.raises(ValueError, match="increasing"):
        ds.typed.restrict(bad)


# ------------------------------------------------ model, parameters

def test_parameter_names_and_op_list():
    model = _model()
    assert sorted(_params()) == sorted(PARAM_NAMES)
    kinds = [op.kind for op in model._ops[1:]]
    layer = ["rel_linear", "rel_aggregate", "root_linear", "add"]
    assert kinds == (["typed_input"] + layer + ["activation", "dropout"]
                     + layer)
    swapped = _model(GATHER_FIRST)
    assert [op.kind for op in swapped._ops[1:]][1:3] == [
        "rel_aggregate", "rel_linear"]
    # the rewrite is idempotent and touches no parameter
    assert swapped.with_rel_orders(lambda i, o: GATHER_FIRST) is swapped
    assert sorted(swapped.init_params(jax.random.PRNGKey(5))) == \
        sorted(PARAM_NAMES)


def test_published_parameter_count():
    """ogbn-mag at the OGB script's widths: the leaderboard row's
    154,366,772, to the unit."""
    mag = (736389, 1134649, 8740, 59965)
    model = build_rgcn([128, 64, 349], 0.5, node_types=mag,
                       embed_types=(1, 2, 3), relations=RELATIONS)
    assert param_elems(model._ops) == 154_366_772
    emb = [op for op in model._ops if op.kind == "typed_input"][0]
    assert emb.attrs["embed_rows"] == 1_203_354


@pytest.mark.parametrize("in_dim,out_dim,impl,want", [
    (128, 64, "flat_sum", GATHER_FIRST),      # 128 lanes either way
    (64, 349, "flat_sum", GATHER_FIRST),      # 128 against 384 lanes
    (349, 64, "flat_sum", TRANSFORM_FIRST),
    (128, 64, "segment", TRANSFORM_FIRST),    # no lane pad: 64 < 128
    (64, 349, "segment", GATHER_FIRST),
    (64, 64, "segment", GATHER_FIRST),        # a tie takes the mean first
])
def test_rel_order_follows_the_gathered_width(in_dim, out_dim, impl, want):
    from roc_tpu.core.ell import agg_lane_width
    assert resolve_rel_order(
        in_dim, out_dim,
        lambda f: agg_lane_width(f, impl, "gather")) == want


# ------------------------------------------ system against reference

@pytest.mark.parametrize("impl,order", CASES)
def test_logits_match_the_reference(ds, plain, impl, order):
    logits, _, _ = _system(ds, impl, order)
    assert logits.shape == (V, CLASSES)         # every kind's rows
    np.testing.assert_allclose(logits, plain[0], rtol=2e-4, atol=2e-5)
    assert np.abs(logits[KINDS[0]:]).max() > 1e-2


@pytest.mark.parametrize("impl,order", CASES)
def test_loss_matches_the_reference(ds, plain, impl, order):
    assert _system(ds, impl, order)[1] == pytest.approx(plain[1],
                                                        rel=1e-5)


@pytest.mark.parametrize("impl,order,name", [
    (i, o, n) for i, o in CASES for n in PARAM_NAMES])
def test_gradient_matches_the_reference(ds, plain, impl, order, name):
    grads = _system(ds, impl, order)[2]
    want = plain[2][name]
    if name in DEAD:
        assert not want.any() and not grads[name].any()
        return
    assert np.abs(want).max() > 1e-7, "a dead parameter tests nothing"
    np.testing.assert_allclose(grads[name], want, rtol=2e-3,
                               atol=2e-4 * np.abs(want).max())


@pytest.mark.parametrize("order", REL_ORDERS)
def test_hand_written_backward_is_autodiff_of_segment(ds, order):
    """The pass over the transposed table against autodiff through the
    edge-list forward: the same cotangent pulled back, to round-off."""
    rows = {TRANSFORM_FIRST: ds.typed.src_rows,
            GATHER_FIRST: V}[order]
    x = jax.random.normal(jax.random.PRNGKey(1), (rows, 6))
    pulled = {}
    for impl in IMPLS:
        gctx = make_graph_context(ds, impl, rel_orders=(order,))
        y, pull = jax.vjp(lambda a: gctx.rel_aggregate(a, order), x)
        g = jax.random.normal(jax.random.PRNGKey(2), y.shape)
        pulled[impl] = (np.asarray(y), np.asarray(pull(g)[0]))
    np.testing.assert_allclose(pulled["flat_sum"][0],
                               pulled["segment"][0], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(pulled["flat_sum"][1],
                               pulled["segment"][1], rtol=1e-5, atol=1e-6)
    assert np.abs(pulled["segment"][1]).max() > 1e-3


def test_slot_major_tables_scan_to_the_same_bits(ds):
    """``[n, 8 * seg]`` tables reshaped back inside the step against
    the ``[n, seg, 8]`` form: the same values in the same order."""
    from roc_tpu.core.ell import flat_sum_from_graph
    row_ptr, col, n_into, n_out = ds.typed.pass_csr("gf_fwd")
    sect = flat_sum_from_graph(row_ptr, col, n_into, src_rows=n_out)
    idx, dst = sect.idx[0], sect.sub_dst[0]
    w = ds.typed.slot_weights("gf_fwd", idx, dst)
    x = jnp.concatenate([jax.random.normal(jax.random.PRNGKey(0),
                                           (n_out, 16)),
                         jnp.zeros((1, 16))]).astype(jnp.bfloat16)
    n = idx.shape[0]
    a = aggregate_flat_sum(x, jnp.asarray(idx), jnp.asarray(dst), n_into,
                           flat_w=jnp.asarray(w), weights_fp32=True)
    b = aggregate_flat_sum(
        x, jnp.asarray(idx.transpose(0, 2, 1).reshape(n, -1)),
        jnp.asarray(dst), n_into,
        flat_w=jnp.asarray(w.transpose(0, 2, 1).reshape(n, -1)),
        weights_fp32=True, slot_major=True)
    np.testing.assert_array_equal(np.asarray(a, np.float32),
                                  np.asarray(b, np.float32))


def test_segment_linear_gradient_is_autodiff_of_the_slices():
    """The hand-written gradient of the block-row products against
    autodiff of the same products written with slices."""
    key = jax.random.split(jax.random.PRNGKey(0), 6)
    x = jax.random.normal(key[0], (9, 4))
    bounds, out_rows = [(0, 5), (5, 9), (0, 5)], (5, 4, 5)
    terms = [(0, 0), (1, 1), (2, 2)]
    ws = [jax.random.normal(k, (4, 3)) for k in key[1:4]]

    def by_slices(x, ws):
        return jnp.concatenate([x[lo:hi] @ w
                                for (lo, hi), w in zip(bounds, ws)])

    g = jax.random.normal(key[4], (14, 3))
    want = jax.vjp(by_slices, x, ws)[1](g)
    got = jax.vjp(lambda x, ws: dense.segment_linear(
        x, bounds, out_rows, terms, ws), x, ws)[1](g)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               rtol=1e-5, atol=1e-6)
    for a, b in zip(got[1], want[1]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_reference_refuses_a_relation_without_a_weight(ref, ds):
    """The reference derives relations itself: an edge whose pair of
    kinds holds no weight turns its logits into NaN."""
    reference, rgcn = ref
    params = {k: v for k, v in _params().items()
              if not k.endswith("_3_0")}
    with jax.default_matmul_precision("highest"):
        got = rgcn.forward(params, jnp.asarray(ds.features),
                           _ref_graph(reference, ds), SPEC)
    assert np.isnan(np.asarray(got)).all()


@pytest.mark.parametrize("order", REL_ORDERS)
def test_mixed_precision_is_inside_the_cells_tolerances(ref, ds, plain,
                                                        order):
    reference, _ = ref
    with open(os.path.join(BENCH, "workloads",
                           "rgcn-mag.fullgraph-typed.json")) as f:
        tol = json.load(f)["correct"]
    logits, _, _ = _system(ds, "flat_sum", order, dtype=jnp.bfloat16)
    got = reference.compare(logits, plain[0])
    assert got["finite"]
    assert got["row_rel_l2_max"] <= tol["row_rel_l2_max"], got
    assert got["row_rel_l2_median"] <= tol["row_rel_l2_median"], got


# ------------------------------------------- the loss program's cut

def test_loss_cut_is_read_off_the_relations_and_the_labelled_kind():
    for order in REL_ORDERS:
        model = _model(order)
        cut = model.loss_cut()
        assert cut is not model and cut.loss_cut() is cut
        assert [op.kind for op in cut._ops] == [op.kind
                                                for op in model._ops]
        assert [op.inputs for op in cut._ops] == [op.inputs
                                                  for op in model._ops]
        assert cut.rel_cuts() == ((order, INTO_PAPERS),)
        assert model.rel_cuts() == ()
        changed = [i for i, (a, b) in enumerate(zip(model._ops, cut._ops))
                   if a.attrs != b.attrs]
        last = len(model._ops) - 1
        # the input (label_scale goes) and the last layer's four ops
        assert changed == [0, last - 3, last - 2, last - 1, last]
        rows = KINDS[0] / V
        stack = sum(KINDS[RELATIONS[r][order == GATHER_FIRST]]
                    for r in INTO_PAPERS) / V
        assert [cut._ops[i].attrs["row_scale"] for i in changed[1:]] == \
            pytest.approx([stack, rows, rows, rows])
        assert "label_scale" in model._ops[0].attrs
        assert "label_scale" not in cut._ops[0].attrs
        assert sorted(cut.init_params(jax.random.PRNGKey(5))) == \
            sorted(PARAM_NAMES)


def test_a_model_whose_every_kind_is_labelled_resolves_to_no_cut():
    one = build_rgcn(LAYERS, 0.5, node_types=(V,), embed_types=(),
                     relations=((0, 0),))
    assert one.loss_cut() is one and one.rel_cuts() == ()
    # nor one no relation of which ends in the labelled kind
    away = build_rgcn(LAYERS, 0.5, node_types=KINDS, embed_types=EMBED,
                      relations=((0, 1), (1, 2)))
    assert away.loss_cut() is away
    from roc_tpu.models.gcn import build_gcn
    gcn = build_gcn(LAYERS, 0.5)
    assert gcn.loss_cut() is gcn


def _cut_pair(ds, impl, order):
    """(loss, gradients) of the train-mode objective — dropout on, one
    key — through the uncut op list and through ``loss_fn``'s cut
    one, float32, on one context that holds both table sets."""
    key = ("cut", impl, order)
    if key in _cache:
        return _cache[key]
    from roc_tpu.ops.loss import masked_softmax_cross_entropy
    model, params = _model(order), _params()
    gctx = make_graph_context(ds, impl, rel_orders=model.rel_orders(),
                              rel_cuts=model.loss_cut().rel_cuts())
    feats = jnp.asarray(model_features(model, ds))
    labels, mask = jnp.asarray(ds.labels), jnp.asarray(ds.mask)
    drop = jax.random.PRNGKey(11)

    def uncut(p):
        logits = model.apply(p, feats, gctx, key=drop, train=True)
        assert logits.shape == (V, CLASSES)
        return masked_softmax_cross_entropy(
            *model.labelled(logits, labels, mask))

    def cut(p):
        loss, logits = model.loss_fn(p, feats, labels, mask, gctx,
                                     key=drop, train=True)
        assert logits.shape == (KINDS[0], CLASSES)
        return loss

    with jax.default_matmul_precision("highest"):
        out = [jax.value_and_grad(f)(params) for f in (uncut, cut)]
    _cache[key] = [(float(l), {k: np.asarray(v) for k, v in g.items()})
                   for l, g in out]
    return _cache[key]


@pytest.mark.parametrize("impl,order", CASES)
def test_cut_loss_is_the_uncut_op_lists(ds, impl, order):
    (want, _), (got, _) = _cut_pair(ds, impl, order)
    assert got == pytest.approx(want, rel=1e-6)


@pytest.mark.parametrize("impl,order,name", [
    (i, o, n) for i, o in CASES for n in PARAM_NAMES])
def test_cut_gradient_is_the_uncut_op_lists(ds, impl, order, name):
    (_, want), (_, got) = _cut_pair(ds, impl, order)
    if name in DEAD:
        assert not want[name].any() and not got[name].any()
        return
    assert np.abs(want[name]).max() > 1e-7
    np.testing.assert_allclose(got[name], want[name], rtol=1e-6,
                               atol=1e-6 * np.abs(want[name]).max())


def _lowered_sha(tr, step):
    """SHA-256 of a trainer's lowered train or eval program."""
    if step == "train":
        args = (tr.params, tr.opt_state, jax.random.PRNGKey(0),
                jnp.float32(0.01), tr.feats, tr.labels, tr.mask, tr.gctx)
        fn = jax.jit(tr._train_step_impl)
    else:
        args = (tr.params, tr.feats, tr.labels, tr.mask, tr.gctx)
        fn = jax.jit(tr._eval_step_impl)
    return hashlib.sha256(fn.lower(*args).as_text().encode()).hexdigest()


def _untyped(family):
    from roc_tpu.core.graph import synthetic_dataset
    from roc_tpu.models.gat import build_gat
    from roc_tpu.models.gcn import build_gcn
    from roc_tpu.models.gcn2 import build_gcn2
    data = synthetic_dataset(num_nodes=96, avg_degree=4, in_dim=F,
                             num_classes=CLASSES, seed=1)
    model = {"gcn": lambda: build_gcn(LAYERS, 0.5),
             "gat": lambda: build_gat(LAYERS, 0.5),
             "gcn2": lambda: build_gcn2([F, H, H, CLASSES],
                                        dropout_rate=0.5)}[family]()
    return model, data


@pytest.mark.parametrize("family,step", [
    ("rgcn", "eval"), ("gcn", "train"), ("gat", "train"),
    ("gcn2", "train"), ("gcn", "eval")])
def test_programs_the_cut_must_leave_alone(ds, monkeypatch, family, step):
    """Lowered with ``loss_cut`` on the tree and with it answering
    ``self``: the eval program of the typed model and both programs of
    the families that label every row hash equal; the typed train
    program does not (the cut reaches it)."""
    from roc_tpu.models.builder import Model
    cfg = TrainConfig(verbose=False, aggr_impl="auto", weight_decay=0.0)

    def sha(which):
        model, data = (_model(), ds) if family == "rgcn" \
            else _untyped(family)
        return _lowered_sha(Trainer(model, data, cfg), which)

    with_cut = sha(step)
    typed_train = sha("train") if family == "rgcn" else None
    monkeypatch.setattr(Model, "loss_cut", lambda self: self)
    assert sha(step) == with_cut
    if family == "rgcn":
        assert sha("train") != typed_train


# ----------------------------------------------------- the normal path

@pytest.fixture(scope="module")
def trainer(ds):
    tr = Trainer(_model(), ds, TrainConfig(
        verbose=False, aggr_impl="auto", memory="auto", weight_decay=0.0,
        dtype=jnp.float32, compute_dtype=jnp.bfloat16))
    tr.train(epochs=1)
    return tr


def test_auto_resolves_to_the_flat_scan_and_trains(ds, trainer):
    assert trainer.config.aggr_impl == "flat_sum"
    assert trainer.model.rel_orders() == (GATHER_FIRST, GATHER_FIRST)
    assert trainer.feats.shape == (KINDS[0], F)     # kind 0's rows only
    assert sorted(trainer.params) == sorted(PARAM_NAMES)
    assert all(v.dtype == jnp.float32 for v in trainer.params.values())
    before = trainer.evaluate()["train_loss"]
    trainer.train(epochs=30)
    assert trainer.evaluate()["train_loss"] < 0.5 * before
    assert trainer.predict().shape == (V, CLASSES)


def test_plan_line_carries_relations_orders_and_embedding_rows(ds, trainer):
    plan = trainer.gctx.relation_plan(trainer.model._ops, ds.typed,
                                      trainer.model.loss_cut()._ops)
    assert plan["node_types"] == list(KINDS)
    assert [(r["src"], r["dst"]) for r in plan["relations"]] == \
        list(RELATIONS)
    assert {"edges", "src_rows", "dst_rows", "deg_mean", "deg_max"} <= \
        set(plan["relations"][0])
    assert [l["rel_order"] for l in plan["rel_layers"]] == [GATHER_FIRST] * 2
    for l in plan["rel_layers"]:
        assert l["stacked_rows"] == ds.typed.dst_rows
        assert l["scan_width"] == 128
        assert 0 < l["agg_slot_fill"] <= 1
        assert l["slots_fwd"] * l["agg_slot_fill"] == pytest.approx(
            ds.typed.num_edges, rel=1e-3)
    assert plan["embedding_rows"] == sum(KINDS[k] for k in EMBED)
    assert plan["embedding_bytes"] == plan["embedding_rows"] * F * 4
    assert plan["relation_edges"] == ds.typed.num_edges
    # what the loss program runs of each layer: all of layer 1, of
    # layer 2 the three relations into kind 0 and kind 0's rows
    first, last = plan["rel_layers"]
    assert (first["train_relations"], first["train_edges"],
            first["train_slots_fwd"], first["train_slots_bwd"],
            first["train_out_rows"]) == (
        len(RELATIONS), ds.typed.num_edges, first["slots_fwd"],
        first["slots_bwd"], V)
    cut = ds.typed.restrict(INTO_PAPERS)
    assert (last["train_relations"], last["train_edges"],
            last["train_out_rows"]) == (3, cut.num_edges, KINDS[0])
    assert cut.num_edges <= last["train_slots_fwd"] < last["slots_fwd"]
    assert cut.num_edges <= last["train_slots_bwd"] < last["slots_bwd"]
    assert last["train_slots_fwd"] >= 8 * cut.pass_sub_rows("gf_fwd")
    # and the plan without a loss program's op list: nothing cut
    same = trainer.gctx.relation_plan(trainer.model._ops, ds.typed)
    assert all(l["train_slots_fwd"] == l["slots_fwd"]
               and l["train_relations"] == len(RELATIONS)
               for l in same["rel_layers"])


def test_scopes_of_the_compiled_train_step(trainer):
    ops = trainer.model._ops
    names = list(trainer._train_step.instruction_scopes()
                 ["scopes"].values())
    rows = {}
    for name in names:
        key = parse_op_name(name)
        if key and key[1] is not None:
            rows.setdefault(key[1], set()).add((key[0], key[2]))
    for i, op in enumerate(ops):
        if op.kind == "rel_aggregate":
            # forward, and the hand-written pass over the transposed
            # table under the same scope
            assert rows[i] == {("agg", "fwd"), ("agg", "bwd")}
        elif op.kind in ("rel_linear", "root_linear"):
            assert ("dense", "fwd") in rows[i] and ("dense", "bwd") in rows[i]
            assert any(f"op{i:02d}.{op.kind}" in n for n in names)
    # the two nested names, forward and backward
    embed = [n for n in names if EMBED_SCOPE in n]
    assert embed and all("typed_input" in n for n in embed)
    assert any("transpose(" in n for n in embed)
    opt = [n for n in names if OPT_EMBED_SCOPE in n]
    assert opt and all(parse_op_name(n)[0] == "opt" for n in opt)
    # the weights' optimizer work stays outside it
    assert any("roc.opt" in n and OPT_EMBED_SCOPE not in n for n in names)


# ---------------------------------------------------------- refusals

def test_typed_graph_refuses_partitions(ds):
    from roc_tpu.parallel.distributed import DistributedTrainer
    with pytest.raises(NotImplementedError, match="one chip"):
        DistributedTrainer(_model(), ds, 2, TrainConfig(verbose=False))


def test_typed_graph_refuses_a_layout_it_lacks(ds):
    with pytest.raises(NotImplementedError, match="no 'sectioned' layout"):
        Trainer(_model(), ds, TrainConfig(verbose=False,
                                          aggr_impl="sectioned"))


def test_export_refuses_a_typed_model(ds, tmp_path):
    from roc_tpu.serve import export
    with pytest.raises(NotImplementedError, match="no serving export"):
        export.build_predictor(_model(), ds, TrainConfig(verbose=False))
    # and the CLI a typed checkpoint, by name, exit 2
    from roc_tpu.utils.checkpoint import checkpoint_trainer
    tr = Trainer(_model(), ds, TrainConfig(verbose=False,
                                           aggr_impl="segment"))
    ck = str(tmp_path / "typed.npz")
    checkpoint_trainer(tr, ck)
    assert export.main(["--out", str(tmp_path / "art"), "--checkpoint",
                        ck, "--cpu", "-layers", f"{F}-{H}-{CLASSES}"]) == 2


def test_model_must_match_the_graphs_relations(ds):
    model = build_rgcn(LAYERS, 0.5, node_types=KINDS, embed_types=EMBED,
                       relations=RELATIONS[:-1])
    with pytest.raises(ValueError, match="relations"):
        Trainer(model, ds, TrainConfig(verbose=False))


def test_cli_runs_the_typed_dataset_from_disk(ds, tmp_path, capsys):
    """The existing on-disk format carries the whole typed dataset once
    the kind counts are on the command line."""
    from roc_tpu.train import cli
    prefix = str(tmp_path / "typed")
    save_dataset(ds, prefix, csv=False)
    seen = {}
    rc = cli.main(["--cpu", "--no-compile-cache", "-file", prefix,
                   "--model", "rgcn", "-layers", f"{F}-{H}-{CLASSES}",
                   "--node-types", ",".join(map(str, KINDS)),
                   "--embed-types", "1,2,3", "-decay", "0", "-e", "5",
                   "--eval-every", "5"],
                  inspect=lambda tr: seen.update(tr=tr))
    assert rc == 0
    tr = seen["tr"]
    assert tr.config.aggr_impl == "flat_sum"
    assert tr.model.typed["relations"] == RELATIONS
    out = capsys.readouterr()
    assert "[INFER]" in out.out
