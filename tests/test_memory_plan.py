"""The memory plan read off the model's op list (``core/memory.py``):
the one rule of what an op keeps for its backward, the three accepted
configurations' shapes resolving to the plans they resolved to before
the rule, a second ``linear`` on a kept input costing nothing, the depth
at which ``remat`` turns on being exactly where the estimate crosses the
budget, the runs remat computes again, and the ``memory_plan`` a trainer
writes into its manifest."""

import jax.numpy as jnp
import pytest

from roc_tpu.core import memory as M
from roc_tpu.core.ell import scan_chunk_rows
from roc_tpu.core.graph import synthetic_dataset
from roc_tpu.models.gat import build_gat
from roc_tpu.models.gcn import build_gcn
from roc_tpu.models.gcn2 import build_gcn2
from roc_tpu.models.sage import build_sage
from roc_tpu.train.trainer import TrainConfig, Trainer, modeled_plan

GIB = 2**30
BUDGET = int(15.75 * GIB * 0.85)         # what a v5e chip reports, usable
ARXIV = (169_343, 2_501_829)


def gcn2_star(depth, width=256):
    return build_gcn2([128] + [width] * depth + [40], alpha=0.5, lam=1.0,
                      dropout_rate=0.1, star=True).fuse_norm_aggregate()


def plan(model, V, E, parts=1, impl="sectioned", **kw):
    return M.choose_memory_plan(
        V, E, model._ops, num_parts=parts, dtype_bytes=2, param_bytes=4,
        hbm_bytes=BUDGET,
        scan_rows=scan_chunk_rows(impl, -(-E // parts)), **kw)


@pytest.mark.parametrize("name,build,V,E,parts,impl", [
    ("gcn-reddit", lambda: build_gcn([602, 256, 41]).fuse_norm_aggregate(),
     232_965, 114_848_857, 1, "sectioned"),
    ("gcn-products",
     lambda: build_gcn([100, 256, 256, 47]).fuse_norm_aggregate(),
     2_449_029, 126_167_309, 4, "flat_sum"),
    ("gat-arxiv", lambda: build_gat(
        [128, 750, 750, 40], dropout_rate=0.75, heads=3, skip=True,
        activation="relu", input_dropout=0.1), *ARXIV, 1, "ell"),
    ("gcn2-arxiv", lambda: gcn2_star(16), *ARXIV, 1, "sectioned"),
])
def test_accepted_shapes_resolve_to_the_plain_plan(name, build, V, E,
                                                   parts, impl):
    """Gathered halo, features on the device, no remat: what each
    accepted cell's ``plan`` line has always said."""
    p = plan(build(), V, E, parts, impl)
    assert (p.halo, p.features, p.remat, p.fits) == (
        "gather", "hbm", False, True), (name, p.echo())
    assert p.est_bytes < 0.5 * BUDGET


def test_a_second_linear_on_a_kept_input_adds_no_activation():
    """GCNII*'s two products a layer: ``W1`` reads ``P H`` (kept),
    ``W2`` reads ``H_0`` (kept once, by the first layer): the starred
    form keeps exactly what the shared-weight form keeps, less nothing,
    plus nothing, whatever the depth; and a ``lerp`` or an ``add`` keeps
    nothing at all."""
    for depth in (2, 8):
        star = gcn2_star(depth)
        kept, _ = M.saved_for_backward(star._ops, 2)
        by_kind = {}
        for i, n, row in kept:
            k = star._ops[i].kind
            by_kind[k] = by_kind.get(k, 0) + n
        assert set(by_kind) == {"dropout", "linear", "activation"}
        # per layer: P H, the first product's charge; the second
        # product adds none
        per_linear = {i: n for i, n, _ in kept
                      if star._ops[i].kind == "linear"}
        second = [i for i, op in enumerate(star._ops)
                  if op.kind == "linear"
                  and star._ops[op.inputs[0]].kind == "activation"
                  and i < len(star._ops) - 1 and op.inputs[0] == 3]
        assert len(second) == depth
        # H_0 is charged once, by the ReLU that makes it
        assert all(i not in per_linear for i in second)
    # the same bytes a vertex row as two separately-built charges
    ops = gcn2_star(1)._ops
    assert [o.kind for o in ops[4:12]] == [
        "dropout", "fused_aggregate", "lerp", "linear", "linear", "add",
        "lerp", "activation"]
    one = dict((i, row) for i, _, row in M.saved_for_backward(ops, 2)[0])
    assert one[7] == 256 * 2               # P H, the first product's input
    assert 8 not in one and 6 not in one and 9 not in one and 10 not in one


def test_residual_rule_by_kind():
    ops = build_sage([12, 16, 5])._ops
    kinds = {op.kind for op in ops}
    assert "scatter_gather" in kinds
    for i, op in enumerate(ops):
        got = M.op_residuals(i, op, 2)
        if op.kind in ("add", "lerp", "indegree_norm", "input"):
            assert got == []
        if op.kind == "dropout":
            assert got == [(("m", i), op.dim, 1)]
        if op.kind == "linear":
            assert got[0] == (("t", op.inputs[0]), op.attrs["in_dim"], 2)
    gat = build_gat([12, 16, 5], heads=2)._ops
    (g,) = [i for i, op in enumerate(gat) if op.kind == "gat"][:1]
    assert [k for k, _, _ in M.op_residuals(g, gat[g], 2)] == [
        ("t", gat[g].inputs[0]), ("t", g), ("m", g)]


def test_depth_sweep_flips_remat_where_the_estimate_crosses_the_budget():
    V, E = ARXIV
    flipped = None
    for depth in range(8, 97, 4):     # past ~100 remat does not fit either
        model = gcn2_star(depth)
        p = plan(model, V, E)
        plain = p.candidates["gather/hbm"]
        assert p.remat == (plain > BUDGET), (depth, p.echo())
        assert p.fits and p.features == "hbm"
        if p.remat:
            assert p.est_bytes == p.candidates["gather/hbm/remat"]
            assert p.est_bytes < 0.7 * plain
            flipped = flipped or depth
    assert flipped == 64          # the estimate crosses at 61 layers
    # the estimate grows by the same bytes for every layer added
    a, b, c = (plan(gcn2_star(d), V, E).candidates["gather/hbm"]
               for d in (16, 17, 18))
    assert b - a == c - b > 0


def test_remat_runs_are_the_stretches_between_aggregations():
    ops = gcn2_star(3)._ops
    runs = M.remat_segments(ops)
    assert len(runs) == 4 and runs[0][0] == 1 and runs[-1][1] == len(ops)
    agg = {i for i, op in enumerate(ops) if op.kind in M.AGG_KINDS}
    covered = {k for lo, hi in runs for k in range(lo, hi)}
    assert covered | agg == set(range(1, len(ops))) and not covered & agg
    kept, again = M.saved_for_backward(ops, 2, remat=True)
    # a run keeps what it reads from outside itself: the aggregation's
    # output; H_0 once; its dropout mask; nothing else of its insides,
    # which are charged once, for the largest run, as what is computed
    # again
    assert sum(n for _, n, _ in kept) < sum(
        n for _, n, _ in M.saved_for_backward(ops, 2)[0])
    assert again > 0
    assert M.saved_for_backward(ops, 2)[1] == 0


def test_components_sum_and_scale():
    ops = gcn2_star(4)._ops
    kw = dict(dtype_bytes=2, param_bytes=4, scan_rows=131_072)
    c = M.plan_components(*ARXIV, ops, **kw)
    assert set(c) == {"params_opt", "features", "tables", "activations",
                      "transient"}
    assert M.estimate_plan_bytes(*ARXIV, ops, **kw) == sum(c.values())
    w = M.param_elems(ops)
    assert w == 128 * 256 + 4 * 2 * 256 * 256 + 256 * 40
    assert c["params_opt"] == w * (4 * 4 + 2)
    assert c["features"] == ARXIV[0] * 128 * 2
    # one chunk of the scan: 131,072 sub-rows of 8 gathered rows + sum
    bare = M.plan_components(*ARXIV, ops, dtype_bytes=2, param_bytes=4)
    assert c["transient"] - bare["transient"] == 131_072 * 9 * 256 * 2
    assert M.model_depth(ops) == {"aggregating_ops": 4, "linear_ops": 10}
    assert scan_chunk_rows("sectioned", 2_501_829) == 131_072
    assert scan_chunk_rows("sectioned", 1_000) == 128
    assert scan_chunk_rows("sectioned", 114_848_857) == 131_072
    assert scan_chunk_rows("flat_sum", 31_541_828) == 8_192
    assert scan_chunk_rows("ell", 10**9) == 0


@pytest.mark.parametrize("remat", [False, True])
def test_trainer_writes_the_plan_into_its_manifest(remat, tmp_path):
    import json
    from roc_tpu.obs.events import configure
    ds = synthetic_dataset(300, 6, in_dim=12, num_classes=4, seed=2)
    model = build_gcn2([12, 16, 16, 16, 4], dropout_rate=0.1, star=True)
    path = str(tmp_path / "events.jsonl")
    configure(jsonl_path=path)
    try:
        tr = Trainer(model, ds, TrainConfig(
            verbose=False, remat=remat, dtype=jnp.float32,
            compute_dtype=jnp.bfloat16))
    finally:
        configure(jsonl_path=None)
    with open(path) as f:
        man = [json.loads(ln) for ln in f if '"manifest"' in ln][-1]
    mem = man["resolved"]["memory_plan"]
    assert mem == json.loads(json.dumps(modeled_plan(
        tr.model, ds, tr.config)))
    assert mem["remat"] is remat and man["resolved"]["remat"] is remat
    assert mem["remat_runs"] == (4 if remat else 0)
    assert (mem["aggregating_ops"], mem["linear_ops"]) == (3, 8)
    assert mem["est_bytes"] == sum(mem["components"].values()) \
        == man["modeled_step_bytes"]
    assert all(tr.model._ops[i].kind == kind
               for i, kind, _, _ in mem["saved"])


# ------------------------------------------------------ a typed model

MAG = (736_389, 1_134_649, 8_740, 59_965)
MAG_RELATIONS = ((0, 0), (0, 1), (0, 3), (1, 0), (1, 2), (2, 1), (3, 0))
MAG_V, MAG_E = sum(MAG), 44_161_757
# the relation tables of both gather_first passes at ogbn-mag's size:
# 7,243,006 + 6,180,819 width-8 sub-rows (my host build, PR 35), an
# index and a weight a slot, an output row a sub-row
MAG_TABLE_BYTES = 68 * (7_243_006 + 6_180_819)


def rgcn_mag(order):
    from roc_tpu.models.rgcn import build_rgcn
    return build_rgcn([128, 64, 349], 0.5, node_types=MAG,
                      embed_types=(1, 2, 3), relations=MAG_RELATIONS
                      ).with_rel_orders(lambda i, o: order)


@pytest.mark.parametrize("order", ["gather_first", "transform_first"])
def test_typed_residual_rules_and_heights(order):
    """``rel_linear`` and ``root_linear`` keep their input, the
    relation sum and the input assembly nothing; a stacked tensor is
    charged at its own height, the input at the feature rows it
    holds."""
    ops = rgcn_mag(order)._ops
    stacked = 4_547_170 / MAG_V
    for i, op in enumerate(ops):
        got = M.op_residuals(i, op, 2)
        if op.kind in ("rel_aggregate", "typed_input"):
            assert got == []
        if op.kind in ("rel_linear", "root_linear"):
            assert got == [(("t", op.inputs[0]), op.attrs["in_dim"], 2)]
    tall = [op for op in ops if op.attrs.get("row_scale", 1) > 1]
    assert [op.kind for op in tall] == [
        "rel_aggregate" if order == "gather_first" else "rel_linear"] * 2
    assert all(op.attrs["row_scale"] == pytest.approx(stacked)
               for op in tall)
    assert ops[0].attrs["row_scale"] == pytest.approx(MAG[0] / MAG_V)
    kept, _ = M.saved_for_backward(ops, 2)
    by_op = {i: row for i, _, row in kept}
    if order == "gather_first":
        # the stacked means a relation's dW needs: 2.34 rows a vertex
        lin = [i for i, op in enumerate(ops) if op.kind == "rel_linear"]
        assert by_op[lin[0]] == pytest.approx(128 * 2 * stacked, abs=0.1)
        assert by_op[lin[1]] == pytest.approx(64 * 2 * stacked, abs=0.1)


def test_typed_plan_charges_the_loss_programs_cut_heights():
    """The train step is the peak and runs ``Model.loss_cut``'s op
    list: layer 2's stack holds the three relations into papers, its
    products, root term, sum, logits and softmax are papers tall."""
    model = rgcn_mag("gather_first")
    cut = model.loss_cut()._ops
    whole, _ = M.saved_for_backward(model._ops, 2)
    kept, _ = M.saved_for_backward(cut, 2)
    assert [(i, n) for i, n, _ in kept] == [(i, n) for i, n, _ in whole]
    by_op, was = ({i: row for i, _, row in k} for k in (kept, whole))
    papers, last = MAG[0] / MAG_V, len(cut) - 1
    lin = last - 2
    assert cut[lin].kind == "rel_linear" and cut[last].kind == "add"
    # the stacked means of three relations into papers, 64 wide
    assert by_op[lin] == pytest.approx(64 * 2 * 3 * papers, abs=0.1)
    assert was[lin] == pytest.approx(64 * 2 * 4_547_170 / MAG_V, abs=0.1)
    # logits in bfloat16 and their fp32 softmax, papers tall both
    assert by_op[last] == pytest.approx(349 * 6 * papers, abs=0.1)
    assert was[last] == pytest.approx(349 * (2 + 4 * papers), abs=0.1)
    assert all(by_op[i] == was[i] for i in by_op if i < lin)
    gib = [sum(M.plan_components(
        MAG_V, MAG_E, ops, dtype_bytes=2, param_bytes=4, scan_rows=8192,
        extra_table_bytes=MAG_TABLE_BYTES + extra).values()) / 2**30
        for ops, extra in ((model._ops, 0),
                           (cut, 68 * (4_229_910 + 4_095_324)))]
    # the two cut tables cost 0.53 GiB, the heights give back 1.24
    assert gib[0] - gib[1] == pytest.approx(0.71, abs=0.02)


def test_typed_plan_charges_the_embedding_tables_and_fits():
    """154,366,772 parameters at 18 bytes are most of the plan; with
    the relation tables beside them ``auto`` resolves the plain plan,
    inside the chip."""
    model = rgcn_mag("gather_first")
    assert M.param_elems(model._ops) == 154_366_772
    c = M.plan_components(MAG_V, MAG_E, model._ops, dtype_bytes=2,
                          param_bytes=4, scan_rows=8192,
                          extra_table_bytes=MAG_TABLE_BYTES)
    assert c["params_opt"] == 154_366_772 * 18
    assert c["features"] == MAG[0] * 128 * 2          # kind 0's rows
    assert c["tables"] > MAG_TABLE_BYTES
    # second only to the activations, 73x the deepest accepted cell's
    assert sorted(c, key=c.get)[-2] == "params_opt"
    p = plan(model, MAG_V, MAG_E, impl="flat_sum",
             extra_table_bytes=MAG_TABLE_BYTES)
    assert (p.halo, p.features, p.remat, p.fits) == (
        "gather", "hbm", False, True), p.echo()
    assert 0.55 * BUDGET < p.est_bytes < BUDGET


# --------------------------------------- batch norm, softmax aggregation

def deepergcn(depth=28, width=128):
    from roc_tpu.models.deepergcn import build_deepergcn
    return build_deepergcn([128] + [width] * depth + [40])


def test_residual_rules_of_batch_norm_and_soft_aggregate():
    ops = deepergcn(4, 16)._ops
    for i, op in enumerate(ops):
        got = M.op_residuals(i, op, 2)
        if op.kind == "batch_norm":
            # its input; the two [F] vectors weigh nothing a row
            assert got == [(("t", op.inputs[0]), 16, 2)]
        if op.kind == "soft_aggregate":
            # its input and the float32 denominator
            assert got == [(("t", op.inputs[0]), 16, 2),
                           (("m", i), 16, 4)]
    assert M.AGG_KINDS[-1] == "soft_aggregate"
    # a res+ block a row, bfloat16: BN's input 2F, ReLU's output 2F,
    # the dropout mask F, the aggregation's input 2F and denominator
    # 4F, the linear's input 2F = 13 F bytes
    kept, _ = M.saved_for_backward(deepergcn(6, 16)._ops, 2)
    by_op = {i: row for i, _, row in kept}
    ops = deepergcn(6, 16)._ops
    block = [i for i, op in enumerate(ops) if op.kind == "batch_norm"][2]
    assert sum(by_op.get(block + k, 0) for k in range(6)) == 13 * 16


def test_deepergcn_at_its_published_shape_resolves_to_the_plain_plan():
    """The deepest plan the chip has checked: 28 aggregating layers at
    ogbn-arxiv's size fit one chip with remat off; the statistics are
    not charged to ``params_opt``; remat would save little here (the
    aggregations stay outside the runs and keep their input and
    denominator, 6 of a layer's 13 F bytes)."""
    model = deepergcn()
    p = plan(model, *ARXIV, 1, "sectioned")
    assert (p.halo, p.features, p.remat, p.fits) == (
        "gather", "hbm", False, True), p.echo()
    assert 0.25 * 15.75 * GIB < p.est_bytes < 0.75 * BUDGET
    comps = M.plan_components(*ARXIV, model._ops, dtype_bytes=2,
                              param_bytes=4)
    assert comps["params_opt"] == 491_176 * (4 * 4 + 2)
    # 1,664 bytes a vertex row a res+ layer
    per_layer = comps["activations"] / ARXIV[0] / 28
    assert 1_600 < per_layer < 1_760
    # the softmax aggregation gathers a table twice its width
    assert comps["transient"] >= ARXIV[0] * 256 * 2
    remat = M.plan_components(*ARXIV, model._ops, dtype_bytes=2,
                              param_bytes=4, remat=True)
    assert 0.7 * comps["activations"] < remat["activations"] \
        < 0.9 * comps["activations"]
    desc = M.describe_plan(*ARXIV, model._ops, dtype_bytes=2)
    assert desc["aggregating_ops"] == 28 and desc["linear_ops"] == 30
    kinds = [k for _, k, _, _ in desc["saved"]]
    assert kinds.count("batch_norm") == 28
    assert kinds.count("soft_aggregate") == 28


def test_deepergcn_plan_is_within_its_stated_miss_on_a_cpu_build():
    """A CPU build at small V: XLA's own peak for the compiled train
    step (arguments + outputs + temporaries) against the plan, read as
    a slope (the plan knows nothing of a build's fixed scratch): from
    V to 2V the float32 CPU build grows by 1.9x what the plan charges
    — XLA:CPU keeps a float32 copy where the TPU build keeps a
    predicate or nothing (at the published shape the TPU compiler's
    6.62 GiB reads UNDER the plan's 8.13, PERF.md section 6, PR 40) —
    so the stated miss here is a factor between 1 and 2.5."""
    from roc_tpu.models.deepergcn import build_deepergcn
    peaks, ests = [], []
    for n in (600, 1200):
        ds = synthetic_dataset(n, 6, in_dim=12, num_classes=4, seed=2)
        tr = Trainer(build_deepergcn([12] + [32] * 8 + [4]), ds,
                     TrainConfig(verbose=False, aggr_impl="sectioned"))
        tr.train(epochs=1)
        peaks.append(tr._train_step.cost["peak_bytes"])
        ests.append(tr._plan["components"]["activations"])
    grown, planned = peaks[1] - peaks[0], ests[1] - ests[0]
    assert 0 < planned <= grown <= 2.5 * planned, (peaks, ests)
