"""The system's DeeperGCN (``models/deepergcn.py`` through
``Model.apply_stateful``, ``GraphContext.batch_norm`` /
``soft_aggregate`` and the sum layouts) against the benchmark's plain
reference (``bench/references/deepergcn.py``) on seeded random weights,
on the CPU, float32: eval logits at 4 and at 28 layers over four
layouts; loss, every parameter's gradient and the statistics a train
step returns, under the same dropout masks; the detached softmax rule
(equal to the reference's ``stop_gradient``, different from autodiff of
the same forward); shift invariance; padding rows and 2 / 4 partitions
exact against one; the running statistics' recurrence, eval reading
them, save -> restore; the optimizer leaving the statistics alone; the
directed-graph refusal; the serving export's refusal; the plan line.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from roc_tpu.core.graph import synthetic_dataset
from roc_tpu.models.builder import SOFT_DIRECTED_REFUSAL
from roc_tpu.models.deepergcn import build_deepergcn
from roc_tpu.train.trainer import (TrainConfig, Trainer, cast_params,
                                   make_graph_context, split_state)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")

V, F, H, CLASSES = 240, 12, 16, 5
T, RATE = 0.1, 0.5
DEPTHS = (4, 28)
IMPLS = ("sectioned", "flat_sum", "ell", "segment")


def _layers(depth):
    return [F] + [H] * depth + [CLASSES]


def _spec(depth):
    return {"family": "deepergcn", "layers": _layers(depth), "t": T}


@pytest.fixture(scope="module")
def ref():
    """The benchmark's reference modules, imported as the benchmark
    imports them (``bench/`` on the path)."""
    sys.path.insert(0, BENCH)
    try:
        import reference
        from references import deepergcn
    finally:
        sys.path.remove(BENCH)
    return reference, deepergcn


@pytest.fixture(scope="module")
def ds():
    """Symmetric, every self edge, skewed degrees (a hub of 48)."""
    d = synthetic_dataset(V, 7, in_dim=F, num_classes=CLASSES, seed=11)
    assert d.graph.is_symmetric()
    return d


_param_cache = {}


def _params(depth, seed=0):
    """Seeded random everything: weights, biases, BatchNorm scale and
    shift — and running statistics near the moments the weights give
    (each batch moment off by a seeded few percent): statistics far
    from them let a 28-layer residual stream grow by orders of
    magnitude, where ``t m`` turns the softmax into a hard maximum
    and a rounding decides which neighbour wins."""
    if (depth, seed) in _param_cache:
        model, params = _param_cache[depth, seed]
        return model, dict(params)
    model = build_deepergcn(_layers(depth), t=T, dropout_rate=RATE)
    params = model.init_params(jax.random.PRNGKey(depth + seed))
    rng = np.random.default_rng(depth + seed)
    for k, v in params.items():
        if k.endswith(("_scale", "_shift", "_b")):
            params[k] = jnp.asarray(
                (1.0 if k.endswith("_scale") else 0.0)
                + 0.3 * rng.standard_normal(v.shape), jnp.float32)
    d = synthetic_dataset(V, 7, in_dim=F, num_classes=CLASSES, seed=11)
    quiet = build_deepergcn(_layers(depth), t=T, dropout_rate=0.0)
    _, moved = quiet.apply_stateful(
        params, jnp.asarray(d.features), make_graph_context(d, "segment"),
        train=True)
    for k, new in moved.items():
        # new = 0.9 * old + 0.1 * batch  ->  the batch moment
        batch = (np.asarray(new) - 0.9 * np.asarray(params[k])) / 0.1
        params[k] = jnp.asarray(
            batch * rng.uniform(0.9, 1.1, batch.shape)
            + (0.05 * rng.standard_normal(batch.shape)
               if k.endswith("_mean") else 0.0), jnp.float32)
    _param_cache[depth, seed] = (model, params)
    return model, dict(params)


def _graph(reference, ds):
    """The reference's edge list in 100-edge chunks and a tail."""
    row_ptr, col = ds.graph.row_ptr, ds.graph.col_idx
    src = np.asarray(col, np.int32)
    dst = np.repeat(np.arange(V, dtype=np.int32), np.diff(row_ptr))
    whole = (src.shape[0] // 100) * 100
    assert 0 < whole < src.shape[0]
    return reference.Graph(
        *(jnp.asarray(a) for a in (
            src[:whole].reshape(-1, 100), dst[:whole].reshape(-1, 100),
            src[whole:], dst[whole:],
            np.diff(row_ptr).astype(np.float32))), V)


def _masks(model, key):
    """The multipliers the program's dropouts apply under ``key``: the
    op's own draw (``_eval_op``: the stream of its ordinal), keep mask
    over keep probability."""
    out = []
    for op in model._ops:
        if op.kind == "dropout":
            keep = 1.0 - op.attrs["rate"]
            sub = jax.random.fold_in(key, len(out))
            out.append(jax.random.bernoulli(sub, p=keep, shape=(V, op.dim))
                       .astype(jnp.float32) / keep)
    return out


_eval_cache = {}


def _eval_logits(ds, depth, impl):
    if (depth, impl) not in _eval_cache:
        model, params = _params(depth)
        gctx = make_graph_context(ds, impl)
        _eval_cache[depth, impl] = np.asarray(jax.jit(
            lambda p, x, g: model.apply(p, x, g, train=False))(
                params, jnp.asarray(ds.features), gctx))
    return _eval_cache[depth, impl]


@pytest.fixture(scope="module")
def plain(ref, ds):
    reference, dg = ref
    g = _graph(reference, ds)
    out = {}
    for depth in DEPTHS:
        _, params = _params(depth)
        with jax.default_matmul_precision("highest"):
            out[depth] = np.asarray(dg.forward(
                params, jnp.asarray(ds.features), g, _spec(depth)))
    return out


def test_parameter_names_count_and_op_list():
    model, params = _params(4)
    names = ([f"linear_{k}{s}" for k in range(6) for s in ("", "_b")]
             + [f"bn_{l}_{s}" for l in range(4)
                for s in ("scale", "shift", "mean", "var")])
    assert sorted(params) == sorted(names)
    assert sorted(model.state_names()) == sorted(
        n for n in names if n.endswith(("_mean", "_var")))
    kinds = [op.kind for op in model._ops[1:]]
    block = ["batch_norm", "activation", "dropout", "soft_aggregate",
             "linear", "add"]
    assert kinds == (["linear", "soft_aggregate", "linear"] + block * 3
                     + ["batch_norm", "activation", "dropout", "linear"])
    # the leaderboard row's count, to the unit
    big = build_deepergcn([128] + [128] * 28 + [40])
    p = big.init_params(jax.random.PRNGKey(0))
    count = sum(int(np.prod(v.shape)) for k, v in p.items()
                if k not in big.state_names())
    assert count == 491_176 == (16_512 + 28 * 16_512 + 28 * 256 + 5_160)
    from roc_tpu.core.memory import param_elems
    assert param_elems(big._ops) == 491_176
    assert all(p[k].dtype == jnp.float32 for k in big.state_names())
    spec = json.loads(json.dumps(big.to_spec()))
    again = type(big).from_spec(spec)
    assert [o.kind for o in again._ops] == [o.kind for o in big._ops]
    assert again.state_names() == big.state_names()
    assert again.batch_norm(again.input()).idx and \
        again._ops[-1].param == "bn_28"


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("depth", DEPTHS)
def test_eval_logits_match_the_reference(ds, plain, depth, impl):
    got = _eval_logits(ds, depth, impl)
    np.testing.assert_allclose(got, plain[depth], rtol=2e-4,
                               atol=2e-4 * np.abs(plain[depth]).max())
    assert np.abs(got).max() > 1e-2


def _train_side(ds, depth, impl="sectioned", dtype=jnp.float32):
    model, params = _params(depth)
    key = jax.random.PRNGKey(7)
    gctx = make_graph_context(ds, impl)
    feats = jnp.asarray(ds.features, dtype)
    labels, mask = jnp.asarray(ds.labels), jnp.asarray(ds.mask)
    trainable, state = split_state(params, model.state_names())

    def objective(p):
        return model.loss_and_state(
            {**cast_params(p, dtype), **state}, feats, labels, mask,
            gctx, key=key)

    (loss, moved), grads = jax.jit(jax.value_and_grad(
        objective, has_aux=True))(trainable)
    return model, params, key, float(loss), grads, moved


@pytest.fixture(scope="module")
def step(ref, ds):
    """One training step at 4 layers: the program's and the
    reference's (detached, and autodiff through the weights)."""
    reference, dg = ref
    model, params, key, loss, grads, moved = _train_side(ds, 4)
    g = _graph(reference, ds)
    args = (params, jnp.asarray(ds.features), jnp.asarray(ds.labels),
            jnp.asarray(ds.mask), g, _spec(4), _masks(model, key))
    with jax.default_matmul_precision("highest"):
        want = dg.loss_and_grads(*args)
        through = dg.loss_and_grads(*args, detach=False)
    return {"model": model, "loss": loss, "grads": grads, "moved": moved,
            "want": want, "through": through}


def test_loss_matches_the_reference(step):
    assert step["loss"] == pytest.approx(float(step["want"][0]), rel=1e-5)
    assert step["loss"] == pytest.approx(float(step["through"][0]),
                                         rel=1e-5)   # same forward


@pytest.mark.parametrize("name", [
    f"linear_{k}{s}" for k in range(6) for s in ("", "_b")] + [
    f"bn_{l}_{s}" for l in range(4) for s in ("scale", "shift")])
def test_gradient_matches_the_reference(step, name):
    want = np.asarray(step["want"][1][name])
    got = np.asarray(step["grads"][name])
    if name in {f"linear_{k}_b" for k in range(1, 5)}:
        # a GENConv's bias shifts every row of a residual stream that
        # only BatchNorms read: the batch mean takes it out again, so
        # in train mode its gradient is zero by construction — in the
        # reference and in the program (to rounding, against the
        # matrix beside it)
        scale = np.abs(np.asarray(step["want"][1][name[:-2]])).max()
        assert np.abs(want).max() < 1e-5 * scale
        assert np.abs(got).max() < 1e-5 * scale
        return
    assert np.abs(want).max() > 1e-6, "a dead parameter tests nothing"
    np.testing.assert_allclose(got, want, rtol=2e-3,
                               atol=2e-4 * np.abs(want).max())


def test_statistics_carry_no_gradient_and_match_the_reference(step):
    assert sorted(step["grads"]) == sorted(
        k for k in step["want"][1]
        if k not in step["model"].state_names())
    assert sorted(step["moved"]) == sorted(step["model"].state_names())
    for k, v in step["moved"].items():
        np.testing.assert_allclose(np.asarray(v),
                                   np.asarray(step["want"][2][k]),
                                   rtol=1e-4, atol=1e-6)
        assert not np.any(np.asarray(step["want"][1][k]))


def test_the_detached_rule_is_the_published_one(step):
    """The program's gradient is the reference's with ``stop_gradient``
    on the softmax weights — and is NOT autodiff of the same forward,
    which differentiates the weights too."""
    name = "linear_0"
    got = np.asarray(step["grads"][name])
    detached = np.asarray(step["want"][1][name])
    through = np.asarray(step["through"][1][name])
    scale = np.abs(detached).max()
    assert np.abs(got - detached).max() < 2e-4 * scale
    assert np.abs(detached - through).max() > 1e-2 * scale
    assert np.abs(got - through).max() > 1e-2 * scale


def test_soft_aggregate_differs_from_autodiff_of_its_own_forward(ds):
    """On the op itself: ``jax.vjp`` of ``GraphContext.soft_aggregate``
    against autodiff of an edge-list softmax aggregation of the same
    values."""
    gctx = make_graph_context(ds, "sectioned")
    z = jax.random.normal(jax.random.PRNGKey(3), (V, H)) * 3.0
    ct = jax.random.normal(jax.random.PRNGKey(4), (V, H))
    s = jnp.asarray(ds.graph.col_idx, jnp.int32)
    d = jnp.asarray(np.repeat(np.arange(V, dtype=np.int32),
                              np.diff(ds.graph.row_ptr)))

    def plain_softmax(z, detach):
        m = jax.nn.relu(z) + 1e-7
        logit = T * m
        if detach:
            logit = jax.lax.stop_gradient(logit)
        e = jnp.exp(logit[s] - jax.ops.segment_max(logit[s], d, V)[d])
        w = e / jax.ops.segment_sum(e, d, V)[d]
        return z + jax.ops.segment_sum(w * m[s], d, V)

    y, pull = jax.vjp(lambda a: gctx.soft_aggregate(a, T, 1e-7), z)
    for detach, same in ((True, True), (False, False)):
        y2, pull2 = jax.vjp(lambda a: plain_softmax(a, detach), z)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y2),
                                   rtol=1e-5, atol=1e-5)
        diff = np.abs(np.asarray(pull(ct)[0]) - np.asarray(pull2(ct)[0]))
        assert (diff.max() < 1e-4) == same, (detach, diff.max())


@pytest.mark.parametrize("scale,offset", [
    (1.0, 0.0), (30.0, 0.0), (100.0, 0.0), (1.0, 5000.0), (50.0, 9000.0)])
def test_shift_invariance(ref, ds, scale, offset):
    """The program shifts by a per-channel maximum, the reference by a
    per-destination one: the same aggregate to float32 rounding — also
    where ``exp(t m)`` itself overflows (``t m`` of 500 to 900), and
    over spreads inside a channel of up to ~600 (``ops/softagg.py``:
    the one-table form is exact while ``t (max - m) < 87``)."""
    reference, dg = ref
    g = _graph(reference, ds)
    z = jax.random.normal(jax.random.PRNGKey(5), (V, H)) * scale + offset
    gctx = make_graph_context(ds, "sectioned")
    got = np.asarray(gctx.soft_aggregate(z, T, 1e-7))
    want = np.asarray(dg.soft_aggregate(z, g, T))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=2e-5,
                               atol=2e-5 * np.abs(want).max())


def test_the_shift_and_the_table_read_one_input(ds):
    """The maximum and the table must see the same ``z`` (on the TPU
    XLA's excess precision showed one pass a bfloat16 activation
    rounded and the other not: ``e`` overflowed in the untrained
    model's unnormalized stream).  The input is pinned by a
    ``reduce_precision`` XLA may not elide, and the exponent is capped
    at 0: a ``z`` whose producer is float32 arithmetic, with entries
    half a bfloat16 ulp above the rounded maximum, stays finite and
    keeps ``e <= 1`` at a stream of 5e5."""
    from roc_tpu.ops import softagg
    gctx = make_graph_context(ds, "sectioned")
    base = jax.random.normal(jax.random.PRNGKey(8), (V, H)) * 2e5

    def prog(b):
        z = (b * 1.00390625 + 0.123).astype(jnp.bfloat16)   # a producer
        return gctx.soft_aggregate(z, T, 1e-7)

    text = jax.jit(prog).lower(base).as_text()
    assert "reduce_precision" in text
    y = np.asarray(jax.jit(prog)(base), np.float32)
    assert np.isfinite(y).all()
    c = jnp.full((H,), 10.0)
    over = jnp.full((V, H), 500.0)         # above the "maximum" by 490
    tab = np.asarray(softagg.table(over, c, T, 1e-7))
    assert float(tab[:, H:].max()) == 1.0 and np.isfinite(tab).all()
    z32 = jax.random.normal(jax.random.PRNGKey(9), (V, H))
    np.testing.assert_array_equal(np.asarray(softagg.as_stored(z32)),
                                  np.asarray(z32))


def test_a_subnormal_denominator_counts_as_none():
    """The TPU flushes a subnormal operand to zero in arithmetic; a
    denominator in that range must take the guarded branch."""
    from roc_tpu.ops import softagg
    z = jnp.ones((4, 3))
    den = jnp.asarray([[0.0] * 3, [1e-40] * 3, [2e-38] * 3, [0.5] * 3],
                      jnp.float32)
    num = den * 2.0
    y, kept = softagg.combine(z, num, den)
    np.testing.assert_allclose(np.asarray(y),
                               [[1.0] * 3, [1.0] * 3, [3.0] * 3, [3.0] * 3])
    q = softagg.cotangent_over_den(jnp.ones((4, 3)), kept)
    assert np.isfinite(np.asarray(q)).all()
    assert not np.any(np.asarray(q)[:2]) and np.all(np.asarray(q)[2:] > 0)


def test_a_directed_graph_is_refused_by_name(ds):
    import dataclasses
    gctx = dataclasses.replace(make_graph_context(ds, "segment"),
                               symmetric=False)
    with pytest.raises(NotImplementedError) as e:
        gctx.soft_aggregate(jnp.zeros((V, H)), T, 1e-7)
    assert str(e.value) == SOFT_DIRECTED_REFUSAL
    assert "symmetric" in SOFT_DIRECTED_REFUSAL


def _cfg(**kw):
    base = dict(learning_rate=0.01, epochs=1, eval_every=1 << 30,
                verbose=False, dropout_rate=0.0, weight_decay=0.0)
    base.update(kw)
    return TrainConfig(**base)


@pytest.mark.parametrize("parts", [2, 4])
def test_partitions_are_exact_against_one(ds, parts):
    """``--parts`` 2 and 4 (their blocks padded: 240 rows do not split
    evenly by edges): the same logits, and after two steps the same
    parameters AND statistics as one partition — the moments count the
    real rows of every partition and no padding."""
    from roc_tpu.parallel.distributed import DistributedTrainer

    def build():
        return build_deepergcn(_layers(4), t=T, dropout_rate=0.0)

    one = Trainer(build(), ds, _cfg())
    many = DistributedTrainer(build(), ds, parts, _cfg())
    # a GENConv's bias has a zero gradient by construction (every
    # reader of its stream is a BatchNorm), so what Adam sees of it is
    # rounding noise, and at epsilon 1e-8 the first step moves it by
    # lr * sign(noise): an epsilon far above the noise keeps such
    # parameters still on both sides (read when the step is traced)
    from roc_tpu.train.optimizer import AdamConfig
    one.adam_cfg = many.adam_cfg = AdamConfig(epsilon=1e-3)
    assert many.pg.part_nodes * parts > V           # padding exists
    assert [int(n) for n in np.asarray(many.data.real_rows)] == [
        r - l + 1 for l, r in many.pg.bounds]
    np.testing.assert_allclose(
        np.asarray(one.predict()), np.asarray(many.predict()),
        rtol=1e-4, atol=1e-5)
    one.train(epochs=2)
    many.train(epochs=2)
    assert sorted(one.params) == sorted(many.params)
    for k, v in one.params.items():
        np.testing.assert_allclose(np.asarray(v),
                                   np.asarray(many.params[k]),
                                   rtol=2e-4, atol=2e-6, err_msg=k)
    np.testing.assert_allclose(
        np.asarray(one.predict()), np.asarray(many.predict()),
        rtol=2e-4, atol=2e-5)
    assert sorted(many.opt_state.m) == sorted(
        k for k in many.params if k not in many.model.state_names())


def test_partition_moments_leave_padding_out(ds):
    """One ``batch_norm`` over a padded two-partition layout equals the
    moments of the real rows alone, whatever the padding rows hold."""
    from roc_tpu.ops.norm import batch_norm_train
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((V, H)) * 2 + 1, jnp.float32)
    scale = jnp.asarray(rng.uniform(0.5, 1.5, H), jnp.float32)
    shift = jnp.asarray(rng.standard_normal(H), jnp.float32)
    y, mean, var = batch_norm_train(x, scale, shift, V)
    np.testing.assert_allclose(np.asarray(mean), np.asarray(x).mean(0),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(var), np.asarray(x).var(0),
                               rtol=1e-4)
    padded = jnp.concatenate([x, jnp.full((16, H), 1e3)])
    valid = jnp.arange(V + 16) < V
    y2, mean2, var2 = batch_norm_train(padded, scale, shift, V, valid)
    np.testing.assert_allclose(np.asarray(y2)[:V], np.asarray(y),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(mean2), np.asarray(mean),
                               rtol=1e-6)
    ct = jnp.asarray(rng.standard_normal((V + 16, H)), jnp.float32)
    g1 = jax.vjp(lambda a: batch_norm_train(a, scale, shift, V)[0],
                 x)[1](ct[:V])[0]
    g2 = jax.vjp(lambda a: batch_norm_train(a, scale, shift, V,
                                            valid)[0], padded)[1](ct)[0]
    np.testing.assert_allclose(np.asarray(g2)[:V], np.asarray(g1),
                               rtol=1e-5, atol=1e-6)
    assert not np.any(np.asarray(g2)[V:])
    # and the hand-written backward is the derivative of the two-pass
    # form
    def two_pass(a):
        mu = a.mean(0)
        return scale * (a - mu) / jnp.sqrt(((a - mu) ** 2).mean(0)
                                           + 1e-5) + shift
    g3 = jax.vjp(two_pass, x)[1](ct[:V])[0]
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g3),
                               rtol=1e-3, atol=1e-5)


def test_moments_are_float32_whatever_the_compute_dtype():
    """What the benchmark's eval-only comparison cannot see (both
    sides read the statistics the program made) is held here: from a
    bfloat16 input whose mean is six sigma off zero, over 16,384 rows,
    the moments come out float32 and to float32's accuracy — a
    bfloat16 accumulator stalls at 256 times an addend, three rows in
    four lost — and the one-pass variance keeps its digits."""
    from roc_tpu.ops.norm import batch_norm_train
    rng = np.random.default_rng(3)
    n = 16_384
    x = jnp.asarray(rng.standard_normal((n, H)) * 0.5 + 3.0,
                    jnp.bfloat16)
    y, mean, var = jax.jit(lambda a: batch_norm_train(
        a, jnp.ones(H), jnp.zeros(H), n))(x)
    assert (y.dtype, mean.dtype, var.dtype) == (
        jnp.bfloat16, jnp.float32, jnp.float32)
    exact = np.asarray(x, np.float64)
    np.testing.assert_allclose(np.asarray(mean), exact.mean(0), rtol=2e-6)
    np.testing.assert_allclose(np.asarray(var), exact.var(0), rtol=2e-4)
    stalled = np.float32(256 * 3.0)       # where a bf16 running sum sticks
    assert stalled / n < 0.1 * float(np.asarray(mean).min())


def test_running_statistics_follow_the_recurrence_and_eval_reads_them(
        ref, ds):
    """Three steps at lr 0 (the parameters stand still, so every step
    sees the same moments): the running statistics are the reference's
    recurrence applied three times; the eval program reads them (its
    logits move while the parameters do not) and equals the reference's
    forward on the dict the trainer holds."""
    reference, dg = ref
    model = build_deepergcn(_layers(4), t=T, dropout_rate=0.0)
    tr = Trainer(model, ds, _cfg(learning_rate=0.0,
                                 aggr_impl="sectioned"))
    before = {k: np.asarray(v) for k, v in tr.params.items()}
    logits0 = np.asarray(tr.predict())
    g = _graph(reference, ds)
    ones = [jnp.ones((V, H))] * 4
    want = {k: jnp.asarray(v) for k, v in before.items()}
    for _ in range(3):
        with jax.default_matmul_precision("highest"):
            _, _, moved = dg.loss_and_grads(
                want, jnp.asarray(ds.features), jnp.asarray(ds.labels),
                jnp.asarray(ds.mask), g, _spec(4), ones)
        want.update(moved)
    tr.train(epochs=3)
    for k in model.state_names():
        np.testing.assert_allclose(np.asarray(tr.params[k]),
                                   np.asarray(want[k]), rtol=2e-4,
                                   atol=1e-6, err_msg=k)
        assert np.abs(np.asarray(tr.params[k]) - before[k]).max() > 1e-3
    for k in before:
        if k not in model.state_names():
            np.testing.assert_array_equal(np.asarray(tr.params[k]),
                                          before[k])
    logits3 = np.asarray(tr.predict())
    assert np.abs(logits3 - logits0).max() > 1e-2
    with jax.default_matmul_precision("highest"):
        ref_logits = np.asarray(dg.forward(
            tr.params, jnp.asarray(ds.features), g, _spec(4)))
    np.testing.assert_allclose(logits3, ref_logits, rtol=2e-4,
                               atol=2e-4 * np.abs(ref_logits).max())


def test_save_and_restore_keep_the_statistics(ds, tmp_path):
    from roc_tpu.utils.checkpoint import (checkpoint_trainer,
                                          restore_trainer)

    def build():
        return build_deepergcn(_layers(4), t=T, dropout_rate=RATE)

    tr = Trainer(build(), ds, _cfg(dropout_rate=RATE))
    tr.train(epochs=3)
    path = str(tmp_path / "ck.npz")
    checkpoint_trainer(tr, path)
    fresh = Trainer(build(), ds, _cfg(dropout_rate=RATE, seed=5))
    assert np.abs(np.asarray(fresh.params["bn_0_mean"])).max() == 0
    restore_trainer(fresh, path)
    for k, v in tr.params.items():
        np.testing.assert_array_equal(np.asarray(v),
                                      np.asarray(fresh.params[k]))
    np.testing.assert_array_equal(np.asarray(tr.predict()),
                                  np.asarray(fresh.predict()))


def test_the_optimizer_never_sees_the_statistics(ds):
    """No Adam moments for them, no weight decay (a decay of 0.5 would
    halve them in a few steps), no compute-dtype copy; ``params_opt``
    of the plan charges the trainable scalars alone."""
    model = build_deepergcn(_layers(4), t=T, dropout_rate=0.0)
    tr = Trainer(model, ds, _cfg(weight_decay=0.5, dtype=jnp.float32,
                                 compute_dtype=jnp.bfloat16))
    state = set(model.state_names())
    assert set(tr.opt_state.m) == set(tr.opt_state.v) \
        == set(tr.params) - state
    tr.train(epochs=2)
    assert set(tr.params) == set(tr.opt_state.m) | state
    cast = cast_params(tr.params, jnp.bfloat16)
    assert all(cast[k].dtype == jnp.float32 for k in tr.params
               if k.startswith("bn_"))
    assert cast["linear_1"].dtype == jnp.bfloat16
    # the running variance still tracks the batch's, undecayed
    var = np.asarray(tr.params["bn_0_var"])
    assert var.min() > 0.5
    from roc_tpu.core.memory import param_elems
    n = param_elems(tr.model._ops)
    assert n == sum(int(np.prod(v.shape)) for k, v in tr.params.items()
                    if k not in state)
    assert tr._plan["components"]["params_opt"] == n * (4 * 4 + 2)


def test_the_serving_export_refuses_the_family_by_name(ds):
    from roc_tpu.serve.export import STATE_REFUSAL, build_predictor
    model = build_deepergcn(_layers(4), t=T, dropout_rate=0.0)
    with pytest.raises(NotImplementedError) as e:
        build_predictor(model, ds, _cfg())
    assert str(e.value) == STATE_REFUSAL
    assert "deepergcn" in STATE_REFUSAL and "batch" in STATE_REFUSAL


def test_plan_line_and_scopes(ds, tmp_path):
    """The manifest's ``resolved`` carries ``batch_norm`` and
    ``soft_aggregate``; every op of the compiled train step has its
    scope, the statistics' reductions sit under ``roc.bn.stats`` inside
    the op's dense scope and the softmax arithmetic under
    ``roc.sagg.weights`` inside the op's agg scope, forward and
    backward."""
    from roc_tpu.obs.events import configure
    from roc_tpu.obs.scopes import (AGG, BN_STATS_SCOPE, DENSE,
                                    SAGG_WEIGHTS_SCOPE, parse_op_name)
    path = str(tmp_path / "events.jsonl")
    configure(jsonl_path=path)
    try:
        tr = Trainer(build_deepergcn(_layers(4), t=T, dropout_rate=RATE),
                     ds, _cfg(dropout_rate=RATE, aggr_impl="sectioned",
                              dtype=jnp.float32,
                              compute_dtype=jnp.bfloat16))
    finally:
        configure(jsonl_path=None)
    with open(path) as f:
        res = [json.loads(ln) for ln in f
               if '"manifest"' in ln][-1]["resolved"]
    bn, soft = res["batch_norm"], res["soft_aggregate"]
    assert (bn["count"], bn["width"], bn["rows_counted"]) == (4, H, V)
    assert bn["stats_bytes"] == 4 * 2 * H * 4 and bn["momentum"] == 0.1
    assert soft["count"] == 4 and soft["t"] == T
    assert soft["gather_lanes_fwd"] == 2 * H
    assert soft["gather_lanes_bwd"] == H
    assert soft["e_dtype"] == "bfloat16"
    assert "den float32" in soft["keeps"]
    mem = res["memory_plan"]
    assert mem["aggregating_ops"] == 4 and mem["remat"] is False
    kinds = {k for _, k, _, _ in mem["saved"]}
    assert {"batch_norm", "soft_aggregate", "linear", "dropout"} <= kinds
    tr.train(epochs=1)
    ops = tr.model._ops
    names = list(tr._train_step.instruction_scopes()["scopes"].values())
    rows = {}
    for name in names:
        key = parse_op_name(name)
        if key and key[1] is not None:
            rows.setdefault(key[1], set()).add((key[0], key[2]))
    assert set(rows) == set(range(1, len(ops)))
    for i, got in rows.items():
        cls = AGG if ops[i].kind == "soft_aggregate" else DENSE
        assert {c for c, _ in got} == {cls}, (i, ops[i].kind, got)
    for scope, kind in ((BN_STATS_SCOPE, "batch_norm"),
                        (SAGG_WEIGHTS_SCOPE, "soft_aggregate")):
        under = [n for n in names if scope in n]
        assert under and all(
            ops[parse_op_name(n)[1]].kind == kind for n in under)
        assert {parse_op_name(n)[2] for n in under} == {"fwd", "bwd"}


def test_mixed_precision_stays_close_at_28_layers(ref, ds, plain):
    """``--dtype mixed`` through 28 residual layers, bfloat16
    activations against the float32 reference.  At this toy width (16
    hidden channels, 5 logits a row) a row's relative error has little
    to average over, and XLA:CPU rounds a bfloat16 carry after every
    addition, so the CPU reads 0.013 median / 0.052 at worst where the
    chip at the published widths reads 0.0034 / 0.0093 (PERF.md section
    6, PR 40): the cell's own tolerances are held on the chip; here the
    bound is what keeps a wrong dtype path (statistics or biases
    rounded, a float32 table gone missing) from passing."""
    reference, _ = ref
    model, params = _params(28)
    gctx = make_graph_context(ds, "sectioned")
    cast = cast_params(params, jnp.bfloat16)
    assert cast["bn_3_mean"].dtype == jnp.float32
    logits = np.asarray(jax.jit(lambda p, x, g: model.apply(
        p, x, g, train=False))(
            cast, jnp.asarray(ds.features, jnp.bfloat16), gctx),
        np.float32)
    got = reference.compare(logits, plain[28])
    assert got["finite"] and got["argmax_agree"] > 0.9, got
    assert got["row_rel_l2_median"] < 0.03, got
    assert got["row_rel_l2_max"] < 0.15, got
