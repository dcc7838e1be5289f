"""The system's Graph Transformer (``models/gtrans.py`` through
``Model.apply``, ``GraphContext.transformer_attention`` and the
dot-product tiles of ``ops/attention.py``) against the benchmark's plain
reference (``bench/references/gtrans.py``) on seeded random weights, on
the CPU: eval logits in float32 and in mixed precision; the hand-written
two-pass backward against ``jax.grad`` of the plain forward under the
same hashed dropout mask; the mask pass B draws against the forward's
on every stored edge; ``layer_norm`` against its formula; the lowered
train step's shape; the refusals (a directed graph, the flat layout,
two partitions, the serving export); the plan line and the scopes; the
memory plan's rules; and the GAT programs, which this family must leave
as they were.
"""

import hashlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from roc_tpu.core.graph import synthetic_dataset
from roc_tpu.models.builder import (EDGE_DROPOUT_STREAM,
                                    TFATTN_DIRECTED_REFUSAL,
                                    TFATTN_FLAT8_REFUSAL,
                                    TFATTN_PARTITION_REFUSAL)
from roc_tpu.models.gtrans import build_gtrans
from roc_tpu.ops.attention import edge_keep_scale
from roc_tpu.train.trainer import (TrainConfig, Trainer, cast_params,
                                   make_graph_context)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")

V, F, HEADS, CLASSES = 160, 12, 2, 5
LAYERS = [F, 16, 16, CLASSES]
RATE = 0.3
MODEL = {"family": "gtrans", "layers": LAYERS, "heads": HEADS}


@pytest.fixture(scope="module")
def ref():
    """The benchmark's reference modules, imported as the benchmark
    imports them (``bench/`` on the path)."""
    sys.path.insert(0, BENCH)
    try:
        import reference
        from references import gtrans
    finally:
        sys.path.remove(BENCH)
    return reference, gtrans


@pytest.fixture(scope="module")
def ds():
    """Symmetric, every self edge, skewed degrees."""
    d = synthetic_dataset(V, 7, in_dim=F, num_classes=CLASSES, seed=5)
    assert d.graph.is_symmetric()
    return d


def _params(seed=0, rate=RATE):
    """Seeded random everything: weights, biases, the gates and
    LayerNorm's scale and shift (its defaults would hide a swapped
    pair); the query weights scaled up so that the softmax is far from
    uniform."""
    model = build_gtrans(LAYERS, dropout_rate=rate, heads=HEADS)
    params = model.init_params(jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    for k, v in params.items():
        if k.endswith(("_b", "_beta", "_shift")):
            params[k] = jnp.asarray(0.3 * rng.standard_normal(v.shape),
                                    jnp.float32)
        elif k.endswith("_scale"):
            params[k] = jnp.asarray(1.0 + 0.3 * rng.standard_normal(
                v.shape), jnp.float32)
    for k in ("linear_0", "linear_3", "linear_6"):
        params[k] = params[k] * 3.0
    return model, params


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


def _seeds(key, layers=len(LAYERS) - 1):
    """The per-layer seeds the program's ops draw their masks from
    (``Model._eval_op``: the stream of the op's ordinal)."""
    base = jax.random.fold_in(key, EDGE_DROPOUT_STREAM)
    return [jax.random.bits(jax.random.fold_in(base, l), (2,), jnp.uint32)
            for l in range(layers)]


@pytest.fixture(scope="module")
def plain(ref, ds):
    reference, gt = ref
    _, params = _params()
    with jax.default_matmul_precision("highest"):
        return np.asarray(gt.forward(params, jnp.asarray(ds.features),
                                     _graph(reference, ds), MODEL))


def test_parameter_names_count_and_op_list():
    model, params = _params()
    names = ([f"linear_{k}{s}" for k in range(9) for s in ("", "_b")]
             + [f"tfattn_{l}_beta" for l in range(3)]
             + [f"ln_{l}_{s}" for l in range(2) for s in ("scale", "shift")])
    assert sorted(params) == sorted(names)
    kinds = [op.kind for op in model._ops[1:]]
    layer = ["linear", "linear", "linear", "transformer_attention"]
    assert kinds == (layer + ["layer_norm", "activation"]) * 2 + layer
    att = [op for op in model._ops if op.kind == "transformer_attention"]
    assert [(op.attrs["heads"], op.attrs["head_width"], op.attrs["concat"],
             op.dim) for op in att] == [
        (2, 8, True, 16), (2, 8, True, 16), (2, CLASSES, False, CLASSES)]
    assert params["linear_4"].shape == (16, 32)       # [W_k | W_v]
    # the published widths' count, to the unit
    big = build_gtrans([128, 256, 256, 40], heads=2)
    p = jax.eval_shape(big.init_params, jax.random.PRNGKey(0))
    count = sum(int(np.prod(v.shape)) for v in p.values())
    assert count == 469_904 == 133_376 + 264_448 + 72_080
    from roc_tpu.core.memory import param_elems
    assert param_elems(big._ops) == 469_904
    spec = json.loads(json.dumps(big.to_spec()))
    again = type(big).from_spec(spec)
    assert [(o.kind, o.param, o.attrs) for o in again._ops] == [
        (o.kind, o.param, o.attrs) for o in big._ops]


def test_eval_logits_match_the_reference_in_float32(ds, plain):
    model, params = _params()
    gctx = make_graph_context(ds, "ell")
    got = np.asarray(jax.jit(lambda p, x, g: model.apply(
        p, x, g, train=False))(params, jnp.asarray(ds.features), gctx))
    norm = np.linalg.norm(plain, axis=1)
    rel = np.linalg.norm(got - plain, axis=1) / norm
    assert rel.max() <= 1e-5, rel.max()
    assert norm.min() > 1e-2


def test_mixed_precision_within_the_cells_limits(ref, ds, plain):
    """``--dtype mixed``: bfloat16 weights, features and activations,
    float32 scores, softmax, sums and LayerNorm, against the float32
    reference.  The median is held to the cell's own ``correct`` limit;
    the worst row to two and a half times its limit: at this toy width
    (8 channels a head, 5 logits a row) a row's relative error has
    little to average over, and XLA:CPU reads twice the chip's median
    (0.0066 here against 0.0031-0.0032 at the published widths, PERF.md
    section 6), so the worst of 160 rows reads 0.030 where the
    chip's worst of 169,343 reads 0.0068 under the limit 0.02.  What
    the bound keeps out is a wrong dtype path: a softmax or weighted
    sum in bfloat16 reads 0.056-0.074 on the chip."""
    reference, _ = ref
    with open(os.path.join(BENCH, "workloads",
                           "gtrans-arxiv.fullgraph.json")) as f:
        tol = json.load(f)["correct"]
    model, params = _params()
    cast = cast_params(params, jnp.bfloat16)
    assert cast["ln_0_scale"].dtype == jnp.float32
    assert cast["linear_1"].dtype == jnp.bfloat16
    logits = np.asarray(jax.jit(lambda p, x, g: model.apply(
        p, x, g, train=False))(
            cast, jnp.asarray(ds.features, jnp.bfloat16),
            make_graph_context(ds, "ell")), np.float32)
    got = reference.compare(logits, plain)
    assert got["finite"] and got["argmax_agree"] > 0.95, got
    assert got["row_rel_l2_median"] <= tol["row_rel_l2_median"], got
    assert got["row_rel_l2_max"] <= 2.5 * tol["row_rel_l2_max"], got


@pytest.fixture(scope="module")
def step(ref, ds):
    """One training step's loss and gradient, with the attention
    dropout on: the program's (two-pass rule) and ``jax.grad`` of the
    plain forward under the program's own hashed mask."""
    reference, gt = ref
    model, params = _params()
    key = jax.random.PRNGKey(7)
    gctx = make_graph_context(ds, "ell")
    feats = jnp.asarray(ds.features)
    labels, mask = jnp.asarray(ds.labels), jnp.asarray(ds.mask)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: model.loss_fn(p, feats, labels, mask, gctx, key=key)[0]
    ))(params)
    seeds = _seeds(key)

    def keep(dst, src, l):
        return edge_keep_scale(dst, src, HEADS, seeds[l], RATE)

    g = _graph(reference, ds)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p: gt.loss_and_grads(
            p, feats, labels, mask, g, MODEL, keep=keep))(params)
        bare = jax.jit(lambda p: gt.loss_and_grads(
            p, feats, labels, mask, g, MODEL)[0])(params)
    return {"loss": float(loss), "grads": grads, "want": want,
            "bare": bare}


def test_loss_with_dropout_matches_the_reference(step):
    assert step["loss"] == pytest.approx(float(step["want"][0]), rel=1e-5)
    # and the mask is on: without it the loss is another
    assert abs(float(step["bare"]) - step["loss"]) > 1e-3 * step["loss"]


@pytest.mark.parametrize("name", (
    [f"linear_{k}{s}" for k in range(9) for s in ("", "_b")]
    + [f"tfattn_{l}_beta" for l in range(3)]
    + [f"ln_{l}_{s}" for l in range(2) for s in ("scale", "shift")]))
def test_two_pass_gradient_matches_autodiff(step, name):
    """Every parameter's gradient through the hand-written rule (pass A
    for the queries, pass B for the keys and values) against
    ``jax.grad`` of the plain forward, to float32 rounding."""
    want = np.asarray(step["want"][1][name])
    got = np.asarray(step["grads"][name])
    assert np.abs(want).max() > 1e-6, "a dead parameter tests nothing"
    np.testing.assert_allclose(got, want, rtol=1e-4,
                               atol=2e-5 * np.abs(want).max())


def test_pass_b_draws_the_forward_mask_on_every_stored_edge(ds):
    """The forward draws edge ``i <- j``'s mask at bucket row ``i``,
    slot ``j``; pass B at bucket row ``j``, slot ``i``.  Read both off
    the ELL tables, per head: the same ``D`` for every stored edge, a
    keep rate near ``1 - p`` and masks that differ by head and by key."""
    gctx = make_graph_context(ds, "ell")
    seed = _seeds(jax.random.PRNGKey(3))[0]
    fwd, bwd = {}, {}
    for idx, rid in zip(gctx.ell_idx, gctx.ell_row_id):
        idx, rid = np.asarray(idx), np.asarray(rid)
        real = (idx != V) & (rid[:, None] < V)
        rows = np.broadcast_to(rid[:, None], idx.shape)
        a = np.asarray(edge_keep_scale(jnp.asarray(rows), jnp.asarray(idx),
                                       HEADS, seed, RATE))
        b = np.asarray(edge_keep_scale(jnp.asarray(idx), jnp.asarray(rows),
                                       HEADS, seed, RATE))
        for i, j, da, db in zip(rows[real], idx[real], a[real], b[real]):
            fwd.setdefault((int(i), int(j)), []).append(tuple(da))
            bwd.setdefault((int(j), int(i)), []).append(tuple(db))
    stored = set(zip(np.repeat(np.arange(V), np.diff(ds.graph.row_ptr)),
                     np.asarray(ds.graph.col_idx)))
    assert set(fwd) == set(bwd) == {(int(i), int(j)) for i, j in stored}
    assert fwd == bwd
    d = np.array([x for v in fwd.values() for x in v])
    assert set(np.unique(d)) == {0.0, np.float32(1 / (1 - RATE))}
    assert abs((d > 0).mean() - (1 - RATE)) < 0.05
    assert (d[:, 0] != d[:, 1]).mean() > 0.2
    other = _seeds(jax.random.PRNGKey(4))[0]
    e = np.asarray(edge_keep_scale(jnp.arange(V), jnp.arange(V)[::-1],
                                   HEADS, other, RATE))
    f = np.asarray(edge_keep_scale(jnp.arange(V), jnp.arange(V)[::-1],
                                   HEADS, seed, RATE))
    assert (e != f).mean() > 0.2


def test_layer_norm_matches_its_formula():
    from roc_tpu.ops.norm import layer_norm
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((64, 24)) * 3 + 5, jnp.float32)
    scale = jnp.asarray(rng.uniform(0.5, 1.5, 24), jnp.float32)
    shift = jnp.asarray(rng.standard_normal(24), jnp.float32)

    def formula(a, s, b):
        mu = a.mean(1, keepdims=True)
        return s * (a - mu) / jnp.sqrt(((a - mu) ** 2).mean(
            1, keepdims=True) + 1e-5) + b

    y, pull = jax.vjp(layer_norm, x, scale, shift)
    y2, pull2 = jax.vjp(formula, x, scale, shift)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y2), rtol=1e-5,
                               atol=1e-5)
    ct = jnp.asarray(rng.standard_normal((64, 24)), jnp.float32)
    for got, want in zip(pull(ct), pull2(ct)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-5)
    # bfloat16 in, bfloat16 out, the moments in float32 (a row whose
    # mean is far from zero keeps its spread)
    xb = (x + 300.0).astype(jnp.bfloat16)
    yb = layer_norm(xb, scale, shift)
    assert yb.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(yb, np.float32),
        np.asarray(formula(xb.astype(jnp.float32), scale, shift)),
        rtol=0.02, atol=0.05)


def _cfg(**kw):
    base = dict(learning_rate=0.01, epochs=1, eval_every=1 << 30,
                verbose=False, dropout_rate=RATE, weight_decay=0.0,
                aggr_impl="ell")
    base.update(kw)
    return TrainConfig(**base)


def test_train_step_scatters_into_no_whole_array(ds):
    """The lowered train step: no scatter under the attention ops'
    scopes, and no scatter whose result is a whole ``[V+1, .]`` array
    anywhere (autodiff through the bucket loop would scatter-add every
    segment into one); the attention's passes are gathers."""
    import re
    tr = Trainer(build_gtrans(LAYERS, dropout_rate=RATE, heads=HEADS), ds,
                 _cfg())
    tr.train(epochs=1)
    scopes = tr._train_step.instruction_scopes()["scopes"]
    ops = {i for i, op in enumerate(tr.model._ops)
           if op.kind == "transformer_attention"}
    from roc_tpu.obs.scopes import parse_op_name
    under = [n for n, s in scopes.items() if n.startswith("scatter")
             and (parse_op_name(s) or (0, None))[1] in ops]
    assert not under, under
    text = tr._train_step.lower(
        tr.params, tr.opt_state, jax.random.PRNGKey(0), jnp.float32(0.01),
        tr.feats, tr.labels, tr.mask, tr.gctx).as_text()
    whole = re.findall(rf"\}}\) : \([^)]*\) -> tensor<{V + 1}x", text)
    assert "stablehlo.gather" in text and not whole, whole[:3]


def test_a_directed_graph_is_refused_by_name(ds):
    import dataclasses
    gctx = dataclasses.replace(make_graph_context(ds, "ell"),
                               symmetric=False)
    model, params = _params()
    with pytest.raises(NotImplementedError) as e:
        model.apply(params, jnp.asarray(ds.features), gctx, train=False)
    assert str(e.value) == TFATTN_DIRECTED_REFUSAL
    assert "symmetric" in TFATTN_DIRECTED_REFUSAL


def test_the_flat_layout_is_refused_by_name(ds):
    from roc_tpu.train.trainer import resolve_attention_impl
    model, params = _params()
    with pytest.raises(NotImplementedError) as e:
        resolve_attention_impl(model, _cfg(aggr_impl="attn_flat8"), ds)
    assert str(e.value) == TFATTN_FLAT8_REFUSAL
    gctx = make_graph_context(ds, "attn_flat8")
    with pytest.raises(NotImplementedError) as e:
        model.apply(params, jnp.asarray(ds.features), gctx, train=False)
    assert str(e.value) == TFATTN_FLAT8_REFUSAL
    # the bucketed layout is where 'auto' resolves on this graph
    assert resolve_attention_impl(model, _cfg(aggr_impl="auto"),
                                  ds).aggr_impl == "ell"


def test_two_partitions_are_refused_by_name(ds, capsys):
    from roc_tpu.parallel.distributed import DistributedTrainer
    from roc_tpu.train.cli import main
    with pytest.raises(NotImplementedError) as e:
        DistributedTrainer(build_gtrans(LAYERS, heads=HEADS), ds, 2, _cfg())
    assert str(e.value) == TFATTN_PARTITION_REFUSAL
    rc = main(["--cpu", "--model", "gtrans", "--heads", "2", "-layers",
               "8-8-3", "--parts", "2", "-e", "1"])
    assert rc == 2
    assert TFATTN_PARTITION_REFUSAL in capsys.readouterr().err


def test_the_serving_export_refuses_the_family_by_name(ds):
    from roc_tpu.serve.export import DOT_ATTENTION_REFUSAL, build_predictor
    with pytest.raises(NotImplementedError) as e:
        build_predictor(build_gtrans(LAYERS, heads=HEADS), ds, _cfg())
    assert str(e.value) == DOT_ATTENTION_REFUSAL
    assert "gtrans" in DOT_ATTENTION_REFUSAL
    assert "LayerNorm" in DOT_ATTENTION_REFUSAL


def test_the_cli_trains_the_family(capsys):
    from roc_tpu.train.cli import main
    rc = main(["--cpu", "--model", "gtrans", "--heads", "2", "-layers",
               "8-8-8-3", "-dropout", "0.3", "-e", "1"])
    assert rc == 0
    rc = main(["--cpu", "--model", "gtrans", "--heads", "3", "-layers",
               "8-8-3", "-e", "1"])
    assert rc == 2 and "not divisible" in capsys.readouterr().err


def test_plan_line_memory_plan_and_scopes(ds, tmp_path):
    """The manifest's ``resolved``: ``score: "dot"``, the lanes a pass
    gathers, two backward passes and their rule, the dropout; the
    memory plan charges both new kinds; every op of the compiled train
    step has its scope, the gate under ``roc.attn.gate`` inside the
    attention op's agg scope and the row moments under ``roc.ln.stats``
    inside the layer_norm op's dense scope, forward and backward."""
    from roc_tpu.obs.events import configure
    from roc_tpu.obs.scopes import (AGG, ATTN_GATE_SCOPE, DENSE,
                                    LN_STATS_SCOPE, parse_op_name)
    path = str(tmp_path / "events.jsonl")
    configure(jsonl_path=path)
    try:
        tr = Trainer(build_gtrans(LAYERS, dropout_rate=RATE, heads=HEADS),
                     ds, _cfg(aggr_impl="auto", dtype=jnp.float32,
                              compute_dtype=jnp.bfloat16))
    finally:
        configure(jsonl_path=None)
    with open(path) as f:
        res = [json.loads(ln) for ln in f
               if '"manifest"' in ln][-1]["resolved"]
    att, back = res["attention"], res["attention_backward"]
    assert [e["op"] for e in att] == [4, 10, 16]
    assert {e["score"] for e in att} == {"dot"}
    assert [e["gather_lanes_fwd"] for e in att] == [32, 32, 20]
    assert [e["out_width"] for e in att] == [16, 16, CLASSES]
    assert att[0]["bwd_passes"] == [["dq", 32], ["dk_dv", 32]]
    assert att[0]["edge_dropout"]["p"] == RATE
    assert back == [{"op": i, "rule": "transposed_two_pass",
                     "edge_passes": 2, "scatters": 0} for i in (4, 10, 16)]
    mem = res["memory_plan"]
    kinds = {k for _, k, _, _ in mem["saved"]}
    # the projections' input is the features or a ReLU's output, which
    # the activation charges first
    assert {"transformer_attention", "layer_norm", "activation"} == kinds
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
        cls = AGG if ops[i].kind == "transformer_attention" else DENSE
        assert {c for c, _ in got} == {cls}, (i, ops[i].kind, got)
    for scope, kind in ((ATTN_GATE_SCOPE, "transformer_attention"),
                        (LN_STATS_SCOPE, "layer_norm")):
        under = [n for n in names if scope in n]
        assert under and all(
            ops[parse_op_name(n)[1]].kind == kind for n in under)
        assert {parse_op_name(n)[2] for n in under} == {"fwd", "bwd"}


def test_memory_rules_of_the_new_kinds():
    from roc_tpu.core import memory as M
    model = build_gtrans([128, 256, 256, 40], heads=2)
    for i, op in enumerate(model._ops):
        got = M.op_residuals(i, op, 2)
        if op.kind == "transformer_attention":
            q, kv, r = op.inputs
            w = op.attrs["heads"] * op.attrs["head_width"]
            assert got == [(("t", q), w, 2), (("t", kv), 2 * w, 2),
                           (("t", r), op.dim, 2),
                           (("m", i), w + 2 * op.attrs["heads"] + 1, 4)]
        if op.kind == "layer_norm":
            assert got == [(("t", op.inputs[0]), op.dim, 2),
                           (("m", i), 2, 4)]
    assert "transformer_attention" in M.AGG_KINDS
    # a hidden layer a row, bfloat16: its input 512 B (kept by the
    # projections), q 512, [k | v] 1,024, r 512, m 1,024 + 16 + 4, the
    # LayerNorm's input 512 + 8, the ReLU's output 512
    kept, _ = M.saved_for_backward(model._ops, 2)
    by_op = {i: row for i, _, row in kept}
    assert by_op[4] == 512 + 1024 + 512 + 4 * (256 + 4 + 1)
    assert by_op[5] == 512 + 8


# the SHA-256 of the small GAT model's lowered train and eval programs
# below, as the commit before the dot-product attention made them: the
# additive attention's programs are left token for token
GAT_PROGRAMS = (
    "16bea08e993049d4f170430527548184b03e07cd02a7492e1783eed392d480f6",
    "afe13aa0c90fa15d6b492fb2b4fb1ebfbba1a4cb10e2ecf6be7b9b69f029dc3e")


def test_the_gat_programs_are_unchanged():
    from roc_tpu.models.gat import build_gat
    d = synthetic_dataset(96, 6, in_dim=8, num_classes=4, seed=3)
    model = build_gat([8, 12, 4], dropout_rate=0.5, heads=3, skip=True,
                      activation="relu")
    tr = Trainer(model, d, TrainConfig(
        verbose=False, aggr_impl="ell", epochs=1, eval_every=1 << 30,
        dtype=jnp.float32, compute_dtype=jnp.bfloat16))
    train = tr._train_step.lower(
        tr.params, tr.opt_state, jax.random.PRNGKey(0), jnp.float32(0.01),
        tr.feats, tr.labels, tr.mask, tr.gctx).as_text()
    evl = tr._eval_step.lower(tr.params, tr.feats, tr.labels, tr.mask,
                              tr.gctx).as_text()
    assert tuple(hashlib.sha256(t.encode()).hexdigest()
                 for t in (train, evl)) == GAT_PROGRAMS
