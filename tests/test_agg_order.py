"""The side of a GCN-form layer's linear its normalized sum runs on
(``core/relations.py resolve_agg_order``, ``Model.with_agg_order``,
``train/trainer.py resolve_agg_orders``).  ``Â (X W) = (Â X) W``
exactly for a bias-free linear between diagonal norms, so the resolve
pass moves the sum onto the linear's input wherever that gathers
fewer lanes a train step: an input no parameter precedes is summed
once, with no transposed pass.  Held here: the rule at the cells'
widths and on every shape it must leave alone, idempotence, the
values and gradients against the reference's order on one, two and
four partitions, the train step's passes, the ``plan`` line's
``agg_order`` entries, and the programs of the families the rule must
not touch, lowered unchanged.
"""

import collections
import hashlib
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from roc_tpu.core.ell import agg_lane_width
from roc_tpu.core.graph import synthetic_dataset
from roc_tpu.core.relations import (AGGREGATE_FIRST, LINEAR_FIRST,
                                    agg_passes, resolve_agg_order)
from roc_tpu.models.builder import Model
from roc_tpu.models.gcn import build_gcn
from roc_tpu.ops.dense import AC_MODE_RELU
from roc_tpu.parallel import distributed
from roc_tpu.parallel.distributed import DistributedTrainer
from roc_tpu.train import cli, trainer
from roc_tpu.train.trainer import TrainConfig, Trainer, resolve_config

L1, L2 = AGGREGATE_FIRST, LINEAR_FIRST


@pytest.fixture(scope="module")
def ds():
    return synthetic_dataset(num_nodes=120, avg_degree=5, in_dim=12,
                             num_classes=5, seed=3)


def _rule_off(monkeypatch):
    """The parent's resolve pass: every sum stays after its linear."""
    monkeypatch.setattr(trainer, "resolve_agg_orders",
                        lambda model, *a, **k: model)


# ------------------------------------------------------------ the rule

def _tail(m, t, out):
    """``t`` through the GCN normalized sum, relu, then a fused-sum
    output layer of ``out`` wide."""
    t = m.relu(m.indegree_norm(m.scatter_gather(m.indegree_norm(t))))
    t = m.linear(t, out)
    t = m.indegree_norm(m.scatter_gather(m.indegree_norm(t)))
    m.softmax_cross_entropy(t)
    return m


def _biased():
    m = Model(in_dim=12)
    return _tail(m, m.linear(m.input(), 256, bias=True), 5)


def _activated():
    m = Model(in_dim=12)
    return _tail(m, m.linear(m.input(), 256, AC_MODE_RELU), 5)


def _read_twice():
    m = Model(in_dim=12)
    t = m.linear(m.input(), 256)
    s = m.indegree_norm(m.scatter_gather(m.indegree_norm(t)))
    m.softmax_cross_entropy(m.linear(m.add(s, t), 5))
    return m


def _hidden(width, out):
    """A trained input ``width`` wide (a linear precedes it) into a
    GCN layer ``out`` wide."""
    m = Model(in_dim=12)
    t = m.linear(m.input(), width, AC_MODE_RELU)
    return _tail(m, m.linear(t, out), 5)


# (builder, impl, the orders resolve_config leaves, in layer order)
RULE_CASES = {
    # products: 128 lanes once against 2 x 256; 2 x 256 a tie; 2 x 256
    # against 2 x 128
    "products-flat_sum": (lambda: build_gcn([100, 256, 256, 47]),
                          "flat_sum", [L1, L2, L2]),
    # Reddit: 602 once against 2 x 256; 2 x 256 against 2 x 41
    "reddit-sectioned": (lambda: build_gcn([602, 256, 41]),
                         "sectioned", [L2, L2]),
    "biased-linear": (_biased, "segment", [L2]),
    "activated-linear": (_activated, "segment", [L2]),
    "second-reader": (_read_twice, "segment", []),
    # a trained input: 2 x 100 against 2 x 120 moves; at 128 lanes
    # 2 x 128 against 2 x 128 is a tie and stays, 2 x 128 against
    # 2 x 200 moves
    "hidden-segment": (lambda: _hidden(100, 120), "segment", [L1, L2]),
    "hidden-flat_sum-tie": (lambda: _hidden(100, 120), "flat_sum",
                            [L2, L2]),
    "hidden-flat_sum": (lambda: _hidden(100, 200), "flat_sum",
                        [L1, L2]),
}


@pytest.mark.parametrize("fuse", ["on", "off"])
@pytest.mark.parametrize("case", list(RULE_CASES))
def test_resolve_pass_moves_only_the_cheaper_sums(ds, case, fuse):
    """The orders ``resolve_config`` leaves at each case, fused or
    not; and a resolved model re-entering the pass comes back as the
    SAME object."""
    build, impl, want = RULE_CASES[case]
    cfg = TrainConfig(aggr_impl=impl, aggr_fuse=fuse, verbose=False,
                      symmetric=True)
    model, cfg2, _ = resolve_config(build(), ds, cfg)
    layers = model.gcn_layers()
    assert [l.order for l in layers] == want
    again, cfg3, _ = resolve_config(model, ds, cfg2)
    assert again is model and cfg3 == cfg2
    names = {op.param for op in build()._ops if op.param}
    assert {op.param for op in model._ops if op.param} == names


@pytest.mark.parametrize("in_dim,out_dim,trained,impl,want,passes", [
    (100, 256, False, "flat_sum", L1, 1),
    (256, 256, True, "flat_sum", L2, 2),
    (256, 47, True, "flat_sum", L2, 2),
    (602, 256, False, "sectioned", L2, 2),
    (100, 120, True, "flat_sum", L2, 2),
    (100, 120, True, "segment", L1, 2),
    (300, 200, False, "segment", L1, 1),
    (400, 200, False, "segment", L2, 2),
])
def test_rule_counts_lanes_and_passes(in_dim, out_dim, trained, impl,
                                      want, passes):
    """One pass at ``L(in)`` for an input no parameter precedes, two
    for any other, against two at ``L(out)``; a tie keeps the
    reference's order."""
    got = resolve_agg_order(in_dim, out_dim, trained,
                            lambda f: agg_lane_width(f, impl))
    assert got == want
    assert agg_passes(got, trained) == passes


@pytest.mark.parametrize("memory,hbm_bytes,asked,features,moves", [
    ("manual", None, "host", "host", False),
    ("manual", None, "hbm", "hbm", True),
    ("auto", 1 << 40, "hbm", "hbm", True),
    ("auto", 1, "hbm", "host", False)])
def test_host_tier_keeps_the_streamable_head(ds, memory, hbm_bytes,
                                             asked, features, moves):
    """``features='host'`` streams ``dropout -> linear``: asked for,
    or chosen by the autopilot where the rewritten model fits no
    budget, the pass leaves the model alone and its head streams."""
    cfg = TrainConfig(aggr_impl="segment", memory=memory,
                      hbm_bytes=hbm_bytes, features=asked,
                      verbose=False, symmetric=True)
    model = build_gcn([12, 64, 5]).fuse_norm_aggregate()
    got, cfg, _ = resolve_config(model, ds, cfg)
    assert (got is not model) == moves
    assert cfg.features == features
    assert (got.streamable_head() is None) == moves


def test_ring_halo_reads_its_own_lanes():
    """Where the autopilot trades the all-gather for the ring, the rule
    reads the ring's lanes (every width its own, no 128-lane pad):
    100 -> 120 behind a linear is a tie at 128 lanes and a move at
    its widths; the resolved model re-enters as itself."""
    ds = synthetic_dataset(num_nodes=2000, avg_degree=8, in_dim=12,
                           num_classes=5, seed=3)
    # gather plans ~3.9 MB, ring plans ~1.3-1.5 MB at this graph
    cfg = TrainConfig(aggr_impl="flat_sum", memory="auto",
                      hbm_bytes=2_000_000, verbose=False, symmetric=True)
    model, cfg2, _ = resolve_config(_hidden(100, 120), ds, cfg,
                                    num_parts=4)
    assert cfg2.halo == "ring"
    assert [l.order for l in model.gcn_layers()] == [L1, L2]
    again, cfg3, _ = resolve_config(model, ds, cfg2, num_parts=4)
    assert again is model and cfg3 == cfg2


# --------------------------------------------- values and gradients

def _grads_instead(monkeypatch):
    """The step hands back its (all-reduced) gradient in place of the
    Adam update."""
    def grads(params, g, opt_state, lr, cfg):
        return g, opt_state
    monkeypatch.setattr(trainer, "adam_update", grads)
    monkeypatch.setattr(distributed, "adam_update", grads)


def _run(ds, parts, impl, fuse):
    cfg = TrainConfig(aggr_impl=impl, aggr_fuse=fuse, verbose=False,
                      symmetric=True, eval_every=100)
    # 12 -> 16: summed first at either layout; 16 -> 48 (trained):
    # summed first where the lanes are the widths ('segment')
    model = build_gcn([12, 16, 48, 5])
    tr = (Trainer(model, ds, cfg) if parts == 1
          else DistributedTrainer(model, ds, parts, cfg))
    logits = np.asarray(tr.predict(), np.float32)
    tr.train(1)
    grads = {k: np.asarray(v, np.float32) for k, v in tr.params.items()}
    return [l.order for l in tr.model.gcn_layers()], logits, grads


@pytest.mark.parametrize("parts,fuse,impl", [
    (1, "on", "flat_sum"), (1, "off", "segment"),
    (2, "on", "segment"), (2, "off", "flat_sum"),
    (4, "on", "flat_sum"), (4, "off", "segment")])
def test_moved_sums_match_the_reference_order(ds, parts, fuse, impl,
                                              monkeypatch):
    """Eval logits and the train step's gradient of the rewritten
    model equal the reference order's to float32 rounding, on one,
    two and four partitions, fused and unfused, each layout on each
    partition count."""
    _grads_instead(monkeypatch)
    orders, logits, grads = _run(ds, parts, impl, fuse)
    want_orders = [L1, L1 if impl == "segment" else L2, L2]
    assert orders == want_orders
    _rule_off(monkeypatch)
    jax.clear_caches()
    ref_orders, ref_logits, ref_grads = _run(ds, parts, impl, fuse)
    assert ref_orders == [L2, L2, L2]
    np.testing.assert_allclose(logits, ref_logits, rtol=1e-4,
                               atol=1e-5 * np.abs(ref_logits).max())
    assert grads.keys() == ref_grads.keys()
    for k, g in ref_grads.items():
        np.testing.assert_allclose(grads[k], g, rtol=1e-4,
                                   atol=1e-5 * np.abs(g).max(),
                                   err_msg=k)
        assert np.abs(g).max() > 0, k


# ----------------------------------------------- the train step's passes

def _subjaxprs(eqn):
    for v in eqn.params.values():
        for sub in v if isinstance(v, (list, tuple)) else [v]:
            if hasattr(sub, "eqns"):
                yield sub
            elif hasattr(getattr(sub, "jaxpr", None), "eqns"):
                yield sub.jaxpr


def _kernel_calls(jaxpr, stack=""):
    """op -> the gather-sum kernel's calls under ``roc.agg.op<op>``
    (one a pass over the ``flat_sum`` table at this size)."""
    out = collections.Counter()
    for e in jaxpr.eqns:
        here = stack + "/" + str(e.source_info.name_stack)
        if e.primitive.name == "pallas_call":
            out[int(re.findall(r"roc\.agg\.op(\d+)", here)[-1])] += 1
        for sub in _subjaxprs(e):
            out.update(_kernel_calls(sub, here))
    return out


def _step_passes(ds):
    cfg = TrainConfig(aggr_impl="flat_sum", verbose=False,
                      symmetric=True)
    tr = Trainer(build_gcn([12, 16, 48, 5]), ds, cfg)
    jx = jax.make_jaxpr(tr._train_step_impl)(
        tr.params, tr.opt_state, tr.key, 0.01, tr.feats, tr.labels,
        tr.mask, tr.gctx)
    return dict(_kernel_calls(jx.jaxpr))


def test_train_step_sums_the_first_layer_once(ds, monkeypatch):
    """The train step's traced program: layer 1's sum, moved onto the
    dropped-out features, is one forward pass and no transpose; every
    other layer keeps its two.  The reference order's step makes
    two passes for every layer."""
    assert _step_passes(ds) == {2: 1, 8: 2, 13: 2}
    _rule_off(monkeypatch)
    assert _step_passes(ds) == {3: 2, 8: 2, 13: 2}


# ----------------------------------------------------- the plan line

@pytest.mark.parametrize("parts", [1, 4])
def test_plan_line_carries_agg_order(tmp_path, ds, parts):
    """``resolved`` (the benchmark's ``plan`` line) holds one ``[op,
    order, in_lanes, out_lanes, passes]`` a GCN-form layer, from both
    trainers, and the resolve event names the moved sums."""
    from roc_tpu.obs.events import configure
    p = str(tmp_path / "ev.jsonl")
    cfg = TrainConfig(aggr_impl="flat_sum", verbose=False,
                      symmetric=True)
    try:
        configure(jsonl_path=p, console=False)
        if parts > 1:
            DistributedTrainer(build_gcn([12, 160, 160, 5]), ds, parts,
                               cfg)
        else:
            Trainer(build_gcn([12, 160, 160, 5]), ds, cfg)
    finally:
        configure(jsonl_path=None)
    events = [json.loads(line) for line in open(p)]
    res = [e for e in events if e["cat"] == "manifest"][-1]["resolved"]
    assert res["agg_order"] == [[2, L1, 128, 160, 1],
                                [8, L2, 160, 160, 2],
                                [13, L2, 160, 128, 2]]
    moved = [e for e in events if "agg_order" in e and e["cat"] ==
             "resolve"]
    assert [e["agg_order"] for e in moved] == [[2]]


# ---------------------------------- programs the rule must not touch

FAMILIES = {
    "gcn-reddit": ["-layers", "602-256-41", "--impl", "sectioned",
                   "--dtype", "mixed"],
    "gcn2": ["--model", "gcn2", "-layers", "12-16-16-4"],
    "deepergcn": ["--model", "deepergcn", "-layers", "12-16-16-4"],
    "gat": ["--model", "gat", "-layers", "8-12-12-3", "--heads", "3",
            "--skip"],
    "gtrans": ["--model", "gtrans", "-layers", "8-16-16-3", "--heads",
               "2"],
    "rgcn": ["--model", "rgcn", "--node-types", "300,212",
             "-layers", "8-16-3"],
}


# the SHA-256 of each family's train and eval programs as ``_lowered``
# lowers them, taken at the commit before a layout's tables became one
# value (models/tables.py): the seam between the table build and the
# kernels moved, and the programs are left token for token
FAMILY_PROGRAMS = {
    "gcn-reddit": (
        "f9942e6f8d47b62b22b738371415ab88ead2decd2146927d06b707c58a12a9d6",
        "7ae275f9152993ce01842c37b47f602840bf875fbb28931a62c69fe532c5d285"),
    "gcn2": (
        "c3d09e249addd13711ce862a89def67f58b6abe66adf580c4d87eb53589a5a03",
        "46a1e9c4cea2191cdaee89a103a7255c6e60dfe6043bb4bde59d5109a6d417ae"),
    "deepergcn": (
        "6630b312786e8e499239e7f16761582d03be06fa279a1722cef09829d3f76c64",
        "84b684c74ce902aac3f8364c6aeff36304c9b8a33ba28802843dc1e5bae6d9c5"),
    "gat": (
        "8925c6fad72071742a20352c9edeb458dfa733d56c1a8396113de8c6c38c3590",
        "044a4420606f605778e2e9d3c9e5d46869d4d9aff75680812f70025b7992bd88"),
    "gtrans": (
        "29ca44f94bceea7658677d799cf43fdcf84edba2195d20c3f8c3c0e868e725ee",
        "f4e820ba8e5805b00b226c79379a508b156664281b8540d1825974aa80d15170"),
    "rgcn": (
        "88f06c299240ce6d833e446cb1b65a9fb7b7c624ab0fcbcf1eaad283ab856d58",
        "72165dc1905a829f9564815feb4309c18e52bf8c3cc242bd1dea78130a588ce1"),
}
_LOWERED = {}


def _family_programs(family):
    """The family's programs under the resolve pass as it stands,
    lowered once a module."""
    if family not in _LOWERED:
        _LOWERED[family] = _lowered(FAMILIES[family])
    return _LOWERED[family]


def _lowered(argv):
    seen = []
    assert cli.main(["--cpu", "--no-compile-cache", "--eval-only"]
                    + argv, inspect=seen.append) == 0
    tr = seen[0]
    train = jax.jit(tr._train_step_impl).lower(
        tr.params, tr.opt_state, tr.key, jnp.float32(0.01), tr.feats,
        tr.labels, tr.mask, tr.gctx).as_text()
    ev = jax.jit(tr._eval_step_impl).lower(
        tr.params, tr.feats, tr.labels, tr.mask, tr.gctx).as_text()
    return [hashlib.sha256(t.encode()).hexdigest() for t in (train, ev)]


@pytest.mark.parametrize("family", list(FAMILIES))
def test_untouched_families_lower_to_the_parents_text(family,
                                                      monkeypatch):
    """Reddit's widths keep both layers after their linears, and no
    other family holds a bias-free linear read only by a normalized
    sum: train and eval programs lower to the text they have with the
    rule off."""
    got = _family_programs(family)
    _rule_off(monkeypatch)
    jax.clear_caches()
    assert _lowered(FAMILIES[family]) == got


@pytest.mark.parametrize("family", list(FAMILIES))
def test_family_programs_are_pinned(family):
    """The families' programs against the text pinned above."""
    assert _family_programs(family) == list(FAMILY_PROGRAMS[family])
