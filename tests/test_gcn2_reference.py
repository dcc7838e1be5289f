"""The system's GCNII* (``models/gcn2.py build_gcn2(star=True)``
through ``Model.apply`` and the sum layouts) against the benchmark's
plain reference (``bench/references/gcn2.py``) on seeded random
weights, on the CPU, float32: logits, loss and every parameter's
gradient at 4 and at 16 layers; ``--fuse`` on and off identical; 2 and
4 partitions exact against one; the shared-weight GCNII unchanged; the
CLI's ``--star``; ``--dtype mixed`` inside the tolerances of the cell
``gcn2-arxiv.fullgraph``; and ``step_scopes``' attribution under remat
(every op has a row, ``recompute`` rows appear, none is booked twice).
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from roc_tpu.core.graph import synthetic_dataset
from roc_tpu.models.gcn2 import build_gcn2
from roc_tpu.obs.scopes import (AGG, DENSE, RECOMPUTE_SCOPE,
                                parse_op_name)
from roc_tpu.train.trainer import (TrainConfig, Trainer, cast_floats,
                                   make_graph_context)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")

V, F, H, CLASSES = 240, 12, 16, 5
ALPHA, LAM = 0.5, 1.0
DEPTHS = (4, 16)


def _layers(depth):
    return [F] + [H] * depth + [CLASSES]


def _spec(depth, variant="gcn2star"):
    return {"family": "gcn2", "layers": _layers(depth),
            "variant": variant, "alpha": ALPHA, "lam": LAM}


def _param_names(depth, star=True):
    return [f"linear_{k}" for k in range((2 if star else 1) * depth + 2)]


@pytest.fixture(scope="module")
def ref():
    """The benchmark's reference modules, imported as the benchmark
    imports them (``bench/`` on the path)."""
    sys.path.insert(0, BENCH)
    try:
        import reference
        from references import gcn2
    finally:
        sys.path.remove(BENCH)
    return reference, gcn2


@pytest.fixture(scope="module")
def ds():
    """Symmetric, every self edge, skewed degrees (a hub of 48)."""
    d = synthetic_dataset(V, 7, in_dim=F, num_classes=CLASSES, seed=11)
    assert d.graph.is_symmetric()
    return d


def _params(depth, star=True):
    model = build_gcn2(_layers(depth), alpha=ALPHA, lam=LAM,
                       dropout_rate=0.1, star=star)
    return model, model.init_params(jax.random.PRNGKey(depth))


_cache = {}


def _system(ds, depth, impl="sectioned", fuse=True, dtype=jnp.float32):
    key = (depth, impl, fuse, jnp.dtype(dtype).name)
    if key in _cache:
        return _cache[key]
    model, params = _params(depth)
    if fuse:
        model = model.fuse_norm_aggregate()
        assert model.num_fused_aggregates() == depth
    gctx = make_graph_context(ds, impl, fuse=fuse)
    feats = jnp.asarray(ds.features, dtype)
    labels, mask = jnp.asarray(ds.labels), jnp.asarray(ds.mask)

    def objective(p):
        return model.loss_fn(cast_floats(p, dtype), feats, labels, mask,
                             gctx, key=None, train=False)

    (loss, logits), grads = jax.value_and_grad(objective,
                                               has_aux=True)(params)
    _cache[key] = (np.asarray(logits, np.float32), float(loss),
                   {k: np.asarray(v) for k, v in grads.items()})
    return _cache[key]


@pytest.fixture(scope="module")
def plain(ref, ds):
    """Reference logits, loss and gradients, float32, per depth; the
    edge list in 100-edge chunks and a tail."""
    reference, gcn2 = ref
    row_ptr, col = ds.graph.row_ptr, ds.graph.col_idx
    src = np.asarray(col, np.int32)
    dst = np.repeat(np.arange(V, dtype=np.int32), np.diff(row_ptr))
    whole = (src.shape[0] // 100) * 100
    assert 0 < whole < src.shape[0]
    g = reference.Graph(
        *(jnp.asarray(a) for a in (
            src[:whole].reshape(-1, 100), dst[:whole].reshape(-1, 100),
            src[whole:], dst[whole:],
            np.diff(row_ptr).astype(np.float32))), V)
    out = {}
    for depth in DEPTHS:
        _, params = _params(depth)
        with jax.default_matmul_precision("highest"):
            x = jnp.asarray(ds.features)
            logits = gcn2.forward(params, x, g, _spec(depth))
            loss, grads = gcn2.loss_and_grads(
                params, x, jnp.asarray(ds.labels), jnp.asarray(ds.mask),
                g, _spec(depth))
        out[depth] = (np.asarray(logits), float(loss),
                      {k: np.asarray(v) for k, v in grads.items()})
    return out


@pytest.mark.parametrize("depth", DEPTHS)
def test_parameter_names_and_op_list(depth):
    model, params = _params(depth)
    assert sorted(params) == sorted(_param_names(depth))
    kinds = [op.kind for op in model._ops[1:]]
    layer = ["dropout", "indegree_norm", "scatter_gather",
             "indegree_norm", "lerp", "linear", "linear", "add", "lerp",
             "activation"]
    assert kinds == (["dropout", "linear", "activation"] + layer * depth
                     + ["dropout", "linear"])
    # two 256 x 256 matrices a layer at the published widths: the
    # leaderboard row's count less its BatchNorms and two biases
    big = build_gcn2([128] + [256] * 16 + [40], star=True)
    n = sum(op.attrs["in_dim"] * op.dim for op in big._ops
            if op.kind == "linear")
    assert n == 2_148_648 - 16 * 512 - 256 - 40


@pytest.mark.parametrize("depth", DEPTHS)
def test_logits_match_the_reference(ds, plain, depth):
    logits, _, _ = _system(ds, depth)
    np.testing.assert_allclose(logits, plain[depth][0], rtol=2e-4,
                               atol=2e-5)
    assert np.abs(logits).max() > 1e-2


@pytest.mark.parametrize("depth", DEPTHS)
def test_loss_matches_the_reference(ds, plain, depth):
    _, loss, _ = _system(ds, depth)
    assert loss == pytest.approx(plain[depth][1], rel=1e-5)


@pytest.mark.parametrize("depth,name", [
    (d, n) for d in DEPTHS for n in _param_names(d)])
def test_gradient_matches_the_reference(ds, plain, depth, name):
    _, _, grads = _system(ds, depth)
    want = plain[depth][2][name]
    assert np.abs(want).max() > 1e-6, "a dead parameter tests nothing"
    np.testing.assert_allclose(grads[name], want, rtol=2e-3,
                               atol=2e-4 * np.abs(want).max())


@pytest.mark.parametrize("impl", ["segment", "sectioned", "ell"])
def test_fuse_on_and_off_are_identical(ds, impl):
    """The fused table-baked norms are the same linear algebra."""
    on = _system(ds, 4, impl=impl, fuse=True)
    off = _system(ds, 4, impl=impl, fuse=False)
    np.testing.assert_allclose(on[0], off[0], rtol=1e-5, atol=1e-6)
    assert on[1] == pytest.approx(off[1], rel=1e-6)
    for k in on[2]:
        np.testing.assert_allclose(on[2][k], off[2][k], rtol=1e-4,
                                   atol=1e-6 * np.abs(off[2][k]).max()
                                   + 1e-9)


@pytest.mark.parametrize("parts", [2, 4])
def test_partitions_are_exact_against_one(ds, parts):
    """``--parts`` 2 and 4: one step from the same parameters gives
    the same loss and the same updated parameters as one partition."""
    from roc_tpu.parallel.distributed import DistributedTrainer
    cfg = TrainConfig(learning_rate=0.01, epochs=1, eval_every=1 << 30,
                      verbose=False, dropout_rate=0.0)

    def build():
        return build_gcn2(_layers(4), alpha=ALPHA, lam=LAM,
                          dropout_rate=0.0, star=True)

    one = Trainer(build(), ds, cfg)
    many = DistributedTrainer(build(), ds, parts, cfg)
    np.testing.assert_allclose(
        np.asarray(one.predict()), np.asarray(many.predict()),
        rtol=1e-4, atol=1e-5)
    one.train(epochs=2)
    many.train(epochs=2)
    for k, v in one.params.items():
        np.testing.assert_allclose(np.asarray(v),
                                   np.asarray(many.params[k]),
                                   rtol=1e-4, atol=1e-6)


def test_shared_weight_gcn2_is_unchanged(ref, ds):
    """Without ``star`` the op list is the one it was — one ``linear``
    a layer, reading the initial-residual mix — and it matches the
    reference's GCNII."""
    reference, gcn2 = ref
    model, params = _params(4, star=False)
    kinds = [op.kind for op in model._ops[1:]]
    layer = ["dropout", "indegree_norm", "scatter_gather",
             "indegree_norm", "lerp", "linear", "lerp", "activation"]
    assert kinds == (["dropout", "linear", "activation"] + layer * 4
                     + ["dropout", "linear"])
    assert sorted(params) == sorted(_param_names(4, star=False))
    lerp = [i for i, op in enumerate(model._ops) if op.kind == "lerp"][0]
    assert model._ops[lerp + 1].inputs == (lerp,)
    gctx = make_graph_context(ds, "segment")
    got = model.apply(params, jnp.asarray(ds.features), gctx, train=False)
    g = reference.Graph.from_csr(ds.graph.row_ptr, ds.graph.col_idx,
                                 widest=H)
    g = reference.Graph(*(jnp.asarray(a) for a in g.arrays()), V)
    with jax.default_matmul_precision("highest"):
        want = gcn2.forward(params, jnp.asarray(ds.features), g,
                            _spec(4, variant="gcn2"))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("argv,rc", [
    (["--model", "gcn2", "--star", "-layers", "12-8-8-4"], 0),
    (["--model", "gcn2", "-layers", "12-8-8-4"], 0),
    (["--model", "gcn", "--star", "-layers", "12-8-4"], 2),
    (["--model", "appnp", "--star", "-layers", "12-8-4"], 2),
])
def test_cli_star_flag(argv, rc, capsys):
    """Accepted with ``gcn2`` (and builds the two-weight layer), exit
    code 2 with any other family."""
    from roc_tpu.train import cli
    seen = {}
    got = cli.main(argv + ["--cpu", "-e", "1", "--alpha", "0.5"]
                   if rc == 0 else argv + ["--cpu", "-e", "1"],
                   inspect=lambda tr: seen.update(tr=tr))
    assert got == rc
    if rc == 0:
        linears = sum(op.kind == "linear" for op in seen["tr"].model._ops)
        assert linears == (2 * 2 + 2 if "--star" in argv else 2 + 2)
    else:
        assert "--star applies to --model gcn2 only" in \
            capsys.readouterr().err


@pytest.mark.parametrize("depth", DEPTHS)
def test_mixed_precision_is_inside_the_cells_tolerances(ref, ds, plain,
                                                        depth):
    reference, _ = ref
    with open(os.path.join(BENCH, "workloads",
                           "gcn2-arxiv.fullgraph.json")) as f:
        tol = json.load(f)["correct"]
    logits, _, _ = _system(ds, depth, dtype=jnp.bfloat16)
    got = reference.compare(logits, plain[depth][0])
    assert got["finite"]
    assert got["row_rel_l2_max"] <= tol["row_rel_l2_max"], got
    assert got["row_rel_l2_median"] <= tol["row_rel_l2_median"], got


@pytest.mark.parametrize("remat", [False, True])
def test_step_scopes_under_remat(ds, remat):
    """Every model op of the compiled train step has a forward row and
    every matrix product and aggregation a backward row; with remat on, the ops
    inside a run computed again have ``recompute`` rows, the
    aggregations none (they are never computed again), and no
    instruction is booked to two directions; with remat off the rows
    are ``fwd`` and ``bwd`` alone, as they were."""
    model, _ = _params(4)
    tr = Trainer(model, ds, TrainConfig(verbose=False, remat=remat,
                                        aggr_impl="sectioned"))
    tr.train(epochs=1)
    ops = tr.model._ops
    scopes = tr._train_step.instruction_scopes()["scopes"]
    rows = {}
    for name in scopes.values():
        key = parse_op_name(name)
        if key and key[1] is not None:
            rows.setdefault(key[1], set()).add((key[0], key[2]))
    assert set(rows) == set(range(1, len(ops)))
    for i, got in rows.items():
        cls = AGG if ops[i].kind == "fused_aggregate" else DENSE
        assert {c for c, _ in got} == {cls}
        ways = {w for _, w in got}
        # (XLA folds an op's two identical evaluations into one
        # instruction where it can, and keeps either's name)
        assert ways & ({"fwd", "recompute"} if remat else {"fwd"})
        # (an add's transpose is the identity: no instruction)
        if ops[i].kind in ("linear", "fused_aggregate") and i > 3:
            assert "bwd" in ways, (i, ops[i].kind, ways)
        if not remat or cls == AGG:
            assert "recompute" not in ways
    recomputed = {i for i, got in rows.items()
                  if "recompute" in {w for _, w in got}}
    if remat:
        # every run's ReLU is computed again for its sign (what else
        # is, XLA decides: the CPU compiler folds the second matrix
        # product into the first, the TPU compiler keeps both)
        assert {i for i, op in enumerate(ops)
                if op.kind == "activation"} <= recomputed
        assert all(RECOMPUTE_SCOPE in n for n in scopes.values()
                   if parse_op_name(n)
                   and parse_op_name(n)[2] == "recompute")
    else:
        assert not recomputed
