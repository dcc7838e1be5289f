"""Program-space auditor + SPMD collective verifier (ISSUE 6): every
new rule fires on a synthetic violation, the statically enumerated
program-key set matches what ObservedJit actually records compiling in
a live rig run (the acceptance criterion — no under- or
over-enumeration), the program budget ratchets shrink-only, and the
CLI's --json output is machine-readable."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from roc_tpu.analysis.collective_lint import (CollectiveUnit,
                                              check_axis_names,
                                              check_conditional_collective,
                                              check_ppermute_cycle,
                                              check_ring_halo,
                                              ring_table_halo_counts)
from roc_tpu.analysis.programspace import (ProgramEntry, ProgramSpace,
                                           _check_distinct,
                                           build_rig_dataset,
                                           build_rig_trainer,
                                           check_cache_key_drift,
                                           check_compile_explosion,
                                           enumerate_programs,
                                           rig_configs)
from roc_tpu.obs.events import get_bus

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_AX = {"parts": 4}


def _cunit(fn, *args, axis_env=(("parts", 4),), axes=_AX):
    return CollectiveUnit(
        "fix", jax.make_jaxpr(fn, axis_env=list(axis_env))(*args), axes)


# -------------------------------------- collective verifier fixtures

def test_ppermute_two_cycle_fires():
    """A permutation made of two disjoint sub-rings rotates each half
    of the mesh among itself — every shard silently sees only half the
    graph.  The cycle rule must name the defect."""
    u = _cunit(lambda x: lax.ppermute(
        x, "parts", [(0, 1), (1, 0), (2, 3), (3, 2)]), jnp.ones(3))
    got = check_ppermute_cycle(u)
    assert [f.rule for f in got] == ["collective-ppermute-cycle"]
    assert "2 disjoint cycles" in got[0].msg


def test_ppermute_partial_cover_fires():
    """A permutation covering a strict subset of the axis leaves the
    uncovered shards waiting on sends that never come — a hang, not an
    error, at P>=2."""
    u = _cunit(lambda x: lax.ppermute(
        x, "parts", [(0, 1), (1, 0)]), jnp.ones(3))
    got = check_ppermute_cycle(u)
    assert len(got) == 1
    assert "covers 2/4" in got[0].msg and "missing [2, 3]" in got[0].msg


def test_ppermute_named_schedule_clean():
    """ring_hop_perm — THE schedule ring_aggregate issues — is a
    single full cycle at every width, and so is its reversal (any
    single cycle is deadlock-free; the canonical one is the ring's)."""
    from roc_tpu.parallel.ring import ring_hop_perm
    for s in (2, 3, 4, 8):
        perm = ring_hop_perm(s)
        u = _cunit(lambda x: lax.ppermute(x, "parts", perm),
                   jnp.ones(3), axis_env=(("parts", s),),
                   axes={"parts": s})
        assert not check_ppermute_cycle(u), f"S={s}"
    rev = [(d, s) for s, d in ring_hop_perm(4)]
    u = _cunit(lambda x: lax.ppermute(x, "parts", rev), jnp.ones(3))
    assert not check_ppermute_cycle(u)


def test_axis_name_fires_on_unknown_axis():
    """A collective over an axis the rig mesh does not define binds
    only on a larger mesh, or never."""
    u = _cunit(lambda x: lax.psum(x, "model"), jnp.ones(3),
               axis_env=(("model", 2),))
    got = check_axis_names(u)
    assert [f.key for f in got] == ["axis|psum|model"]
    # the mesh's own axis is of course clean
    assert not check_axis_names(
        _cunit(lambda x: lax.psum(x, "parts"), jnp.ones(3)))


def test_conditional_collective_fires():
    """A psum issued in one cond branch but not the other is an
    instant P>=2 hang when shards disagree on the predicate."""
    u = _cunit(lambda p, x: lax.cond(
        p, lambda v: lax.psum(v, "parts"), lambda v: v * 2.0, x),
        True, jnp.ones(3))
    got = check_conditional_collective(u)
    assert [f.rule for f in got] == ["collective-conditional"]
    assert "deadlock" in got[0].msg
    # branches issuing the SAME collective sequence are lockstep-safe
    u2 = _cunit(lambda p, x: lax.cond(
        p, lambda v: lax.psum(v, "parts") + 1.0,
        lambda v: lax.psum(v, "parts") * 2.0, x), True, jnp.ones(3))
    assert not check_conditional_collective(u2)


def test_conditional_ppermute_perm_mismatch_fires():
    """Same primitive/axis/shape in both branches but DIFFERENT
    permutations is just as deadlock-prone — device A sends along one
    schedule while B waits on the other — so the perm is part of the
    sequence identity."""
    from roc_tpu.parallel.ring import ring_hop_perm
    fwd = ring_hop_perm(4)
    rev = [(d, s) for s, d in fwd]
    u = _cunit(lambda p, x: lax.cond(
        p, lambda v: lax.ppermute(v, "parts", fwd),
        lambda v: lax.ppermute(v, "parts", rev), x),
        True, jnp.ones(3))
    got = check_conditional_collective(u)
    assert [f.rule for f in got] == ["collective-conditional"]
    # identical perms in both branches stay clean
    u2 = _cunit(lambda p, x: lax.cond(
        p, lambda v: lax.ppermute(v, "parts", fwd) + 1.0,
        lambda v: lax.ppermute(v, "parts", fwd) * 2.0, x),
        True, jnp.ones(3))
    assert not check_conditional_collective(u2)


def test_ring_halo_parity_and_violation():
    """The ring tables and the partition plan are two independent
    derivations of the same halo exchange: the real build ties
    exactly, and a tampered table (rows collapsed onto one source)
    fires on both sides of the drifted pair."""
    from roc_tpu.core.costmodel import partition_halo_stats
    from roc_tpu.core.graph import synthetic_dataset
    from roc_tpu.core.partition import partition_graph
    from roc_tpu.parallel.ring import build_ring_tables
    ds = synthetic_dataset(num_nodes=96, avg_degree=5, in_dim=8,
                           num_classes=4, seed=3)
    pg = partition_graph(ds.graph, 3, node_multiple=8)
    rt = build_ring_tables(pg)
    assert not check_ring_halo("collective:fix", pg, rt)
    recv, send = ring_table_halo_counts(pg, rt)
    hi, ho = partition_halo_stats(pg)
    assert np.array_equal(recv, hi) and np.array_equal(send, ho)
    src = rt.src.copy()
    ext = np.where(src[0, 1] < pg.part_nodes)[0]
    assert len(ext) > 1, "fixture graph must have a real halo"
    src[0, 1, ext] = src[0, 1, ext[0]]
    rt2 = type(rt)(src=src, dst=rt.dst,
                   padding_ratio=rt.padding_ratio)
    keys = sorted(f.key for f in check_ring_halo("collective:fix",
                                                 pg, rt2))
    assert keys == ["halo-in|part=0", "halo-out|part=1"]


# ------------------------------------------ program-space rule fixtures

def _entry(slot, dims, dtype="float32", spec="-", eqns=10,
           observed=True):
    leaves = tuple(("{}".format(dtype), tuple(d), spec) for d in dims)
    sig = ";".join(f"{dtype}[{','.join(map(str, d))}]@{spec}"
                   for d in dims)
    return ProgramEntry(slot=slot, key=f"{slot}|{sig}|donate=",
                        leaves=leaves, observed=observed, eqns=eqns)


def test_cache_key_drift_fires_on_unquantized_pair():
    """Two program keys differing ONLY by dims that snap to the same
    node multiple are a guaranteed persistent-compile-cache miss — the
    shapes would have tied had the quantization been applied."""
    space = ProgramSpace(config="fix", entries=[
        _entry("a", [(250, 48)]), _entry("b", [(252, 48)])],
        node_multiple=8, edge_multiple=128)
    got = check_cache_key_drift(space)
    assert [f.rule for f in got] == ["cache-key-drift"]
    assert "250 vs 252" in got[0].msg


def test_cache_key_drift_quiet_on_real_differences():
    # dims that snap to DIFFERENT multiples: distinct programs for
    # real reasons
    s1 = ProgramSpace(config="fix", entries=[
        _entry("a", [(250, 48)]), _entry("b", [(260, 48)])])
    assert not check_cache_key_drift(s1)
    # dtype difference: structural, never drift
    s2 = ProgramSpace(config="fix", entries=[
        _entry("a", [(250, 48)]),
        _entry("b", [(252, 48)], dtype="bfloat16")])
    assert not check_cache_key_drift(s2)
    # sharding-spec difference likewise
    s3 = ProgramSpace(config="fix", entries=[
        _entry("a", [(250, 48)]),
        _entry("b", [(252, 48)], spec="parts")])
    assert not check_cache_key_drift(s3)


def test_cache_key_drift_quiet_on_node_quantized_pairs():
    """Dims that are ALREADY exact node multiples (quantized shapes,
    or widths that happen to sit on the 8-grid) landing in the same
    128-edge-window are not drift — nothing leaked, there is nothing
    left to quantize, and flagging the pair would be an unclearable
    finding."""
    # 8 vs 120: both on the node grid, same edge window
    s1 = ProgramSpace(config="fix", entries=[
        _entry("a", [(8, 48)]), _entry("b", [(120, 48)])])
    assert not check_cache_key_drift(s1)
    # 136 vs 240: same, in the second edge window
    s2 = ProgramSpace(config="fix", entries=[
        _entry("a", [(136, 48)]), _entry("b", [(240, 48)])])
    assert not check_cache_key_drift(s2)
    # but a pair with one dim OFF the node grid in the same edge
    # window is still a leak (244 = 4 mod 8)
    s3 = ProgramSpace(config="fix", entries=[
        _entry("a", [(256, 48)]), _entry("b", [(244, 48)])])
    assert check_cache_key_drift(s3)


def test_cache_key_drift_exempts_aux_block_programs():
    """The streamed head's per-block jit variants (observed=False)
    legitimately differ by a row count — a ragged tail block is not a
    quantization failure, and block sizes are not partition shapes, so
    the drift rule must not flag a pair the gate could never clear."""
    a = _entry("head_fwd_block:256:train", [(256, 48)], observed=False)
    b = _entry("head_fwd_block:244:train", [(244, 48)], observed=False)
    space = ProgramSpace(config="fix", entries=[a, b])
    assert not check_cache_key_drift(space)
    # the same shapes on OBSERVED slots are a real drift
    space2 = ProgramSpace(config="fix", entries=[
        _entry("a", [(256, 48)]), _entry("b", [(244, 48)])])
    assert check_cache_key_drift(space2)


def test_compile_explosion_fires_past_budget():
    space = ProgramSpace(config="fix", entries=[
        _entry("a", [(8, 8)]), _entry("b", [(16, 8)]),
        _entry("c", [(24, 8)])])
    got = check_compile_explosion(space, 2)
    assert [f.rule for f in got] == ["compile-explosion"]
    assert got[0].detail["programs"] == 3
    assert got[0].detail["budget"] == 2
    # at or under the bound, or with no bound recorded yet: quiet
    assert not check_compile_explosion(space, 3)
    assert not check_compile_explosion(space, None)


def test_enumeration_rejects_duplicate_keys():
    e = _entry("a", [(8, 8)])
    dup = ProgramEntry(slot="b", key=e.key, leaves=e.leaves,
                       observed=True, eqns=1)
    with pytest.raises(AssertionError, match="duplicate keys"):
        _check_distinct(ProgramSpace(config="fix", entries=[e, dup]))


def test_quantize_plan_shapes_is_the_shared_derivation():
    """plan_from_bounds' padded shapes must come from the SAME
    function the auditor calls — including the full-part padding-edge
    correction (a part whose real rows exactly fill part_nodes while
    carrying padding edges gets one extra row-multiple)."""
    from roc_tpu.core.partition import quantize_plan_shapes
    assert quantize_plan_shapes([5, 7], [100, 120]) == (8, 128)
    # part 0 exactly fills the 8-row multiple AND carries padding
    # edges (100 < 128): the correction adds one row-multiple
    assert quantize_plan_shapes([8, 7], [100, 120]) == (16, 128)
    # a full part with FULL edges needs no padding edges: uncorrected
    assert quantize_plan_shapes([8, 7], [128, 120]) == (8, 128)


# -------------------------------- enumeration + live parity (rig runs)

@pytest.fixture(scope="module")
def rig_dataset():
    return build_rig_dataset()


def test_enumeration_counts_and_structure(rig_dataset):
    """The enumerated spaces of both rig configs: counts match the
    committed program budget (the compile-explosion baseline), keys
    are distinct, and the streamed config's space is strictly larger
    than its ObservedJit slots (the per-block head jits)."""
    from roc_tpu.analysis.findings import load_program_budget
    budget = load_program_budget(
        os.path.join(_REPO, "scripts", "lint_baseline.json"))
    spaces = {name: enumerate_programs(spec, dataset=rig_dataset)
              for name, spec in rig_configs().items()}
    for name, space in spaces.items():
        assert space.program_count == budget[name], name
        assert len({e.key for e in space.entries}) == \
            space.program_count
        assert space.modeled_compile_ms() > 0
    # gin_flat8: every program is an ObservedJit slot, and the rig
    # runs the uniform flat-sum consolidation
    g = spaces["gin_flat8"]
    assert all(e.observed for e in g.entries)
    assert g.resolved["parts"] == 2
    assert g.resolved["aggr_impl"] == "flat_sum"
    # sgc_stream: the aux head-block programs exceed the observed set
    s = spaces["sgc_stream"]
    assert len(s.observed_keys()) < s.program_count
    assert any(e.slot.startswith("head_fwd_block") for e in s.entries)


def test_resolve_idempotency_asserted(rig_dataset, monkeypatch):
    """The auditor refuses to enumerate through a non-idempotent
    resolve pass — re-resolving a resolved config must be a fixpoint,
    or the static program space silently forks from the trainers."""
    import roc_tpu.train.trainer as T
    real = T.resolve_config
    calls = {"n": 0}

    def flappy(model, dataset, config, **kw):
        model, config, census = real(model, dataset, config, **kw)
        calls["n"] += 1
        if calls["n"] > 1:     # second resolve: mutate the config
            import dataclasses
            config = dataclasses.replace(config, chunk=config.chunk + 1)
        return model, config, census

    monkeypatch.setattr(T, "resolve_config", flappy)
    spec = rig_configs()["gin_flat8"]
    with pytest.raises(AssertionError, match="not idempotent"):
        enumerate_programs(spec, dataset=rig_dataset)


class _Recorder:
    def __init__(self):
        self.records = []

    def write(self, record):
        self.records.append(dict(record))

    def close(self):
        pass


@pytest.mark.parametrize("name", ["gin_flat8", "sgc_stream",
                                  "gin_mesh2d"])
def test_program_key_parity_static_vs_live(rig_dataset, name):
    """THE acceptance criterion: for these rig configs the auditor's
    statically enumerated program-key set exactly matches the set of
    programs ObservedJit records compiling in a live
    train+eval+predict run — no under- or over-enumeration.  The 2-D
    rig bounds the mesh PR's program growth to exactly its declared
    new step variants (sharded-in/out train + eval keys)."""
    from roc_tpu.analysis.programspace import rig_required_devices
    spec = rig_configs()[name]
    need = rig_required_devices(spec)
    if need > len(jax.devices()):
        pytest.skip(f"needs {need} devices")
    space = enumerate_programs(spec, dataset=rig_dataset)
    static = space.observed_keys()
    rec = _Recorder()
    bus = get_bus()
    bus.add_sink(rec)
    try:
        tr = build_rig_trainer(spec, dataset=rig_dataset)
        tr.train(1)
        tr.evaluate()
        tr.predict()
    finally:
        bus.sinks.remove(rec)
    live = {r["program_key"] for r in rec.records
            if r.get("cat") == "compile" and "program_key" in r}
    assert live == static, (
        f"{name}: static-only={sorted(static - live)} "
        f"live-only={sorted(live - static)}")


def test_enumeration_follows_dataset_scale():
    """The streamed branch must size the [V,H] activation and the
    head blocks from the AUDITED dataset, not the rig constant — an
    enumeration over a 320-node dataset whose keys carried 256-row
    shapes would under- and over-enumerate at once."""
    from roc_tpu.core.graph import synthetic_dataset
    ds = synthetic_dataset(num_nodes=320, avg_degree=6, in_dim=48,
                           num_classes=6, seed=1)
    spec = rig_configs()["sgc_stream"]
    space = enumerate_programs(spec, dataset=ds)
    tg = next(e for e in space.entries if e.slot == "tail_grad")
    # leaf 0+ are the param leaves; the streamed activation y is the
    # one [V, H] leaf — its row count must be the dataset's V
    assert any(dims[:1] == (320,) for _, dims, _ in tg.leaves), \
        tg.leaves
    assert not any(dims[:1] == (256,) for _, dims, _ in tg.leaves), \
        "rig-constant rows leaked into a non-rig dataset's keys"
    blocks = {int(s.rsplit(":", 2)[1]) for s in
              (e.slot for e in space.entries)
              if s.startswith("head_fwd_block")}
    tr = build_rig_trainer(spec, dataset=ds)
    assert blocks == {hi - lo for lo, hi in tr._head._blocks(320)}


def test_program_key_parity_plain_single_device(rig_dataset):
    """The single-device NON-streamed enumeration branch (plain
    train/eval/predict ObservedJit slots) is not reachable from either
    registered rig config — gin_flat8 is distributed, sgc_stream is
    streamed — so an ad-hoc rig pins its static-vs-live parity too:
    a drifted donate tuple or arg order in that branch must fail here,
    not the day a third rig config is registered."""
    from roc_tpu.analysis.programspace import _C, _F, _H, RigSpec
    from roc_tpu.models.gcn import build_gcn
    from roc_tpu.train.trainer import TrainConfig
    spec = RigSpec(
        name="gcn_plain",
        model=lambda: build_gcn([_F, _H, _C], dropout_rate=0.5),
        config=lambda: TrainConfig(verbose=False, symmetric=True,
                                   aggr_impl="segment",
                                   dtype=jnp.float32,
                                   compute_dtype=jnp.bfloat16),
        parts=1)
    space = enumerate_programs(spec, dataset=rig_dataset)
    # predict compiles NOTHING of its own — it reuses the eval
    # program's logits output (the eval/predict consolidation)
    assert {e.slot for e in space.entries} == \
        {"train_step", "eval_step"}
    assert all(e.observed for e in space.entries)
    rec = _Recorder()
    bus = get_bus()
    bus.add_sink(rec)
    try:
        tr = build_rig_trainer(spec, dataset=rig_dataset)
        tr.train(1)
        tr.evaluate()
        tr.predict()
    finally:
        bus.sinks.remove(rec)
    live = {r["program_key"] for r in rec.records
            if r.get("cat") == "compile" and "program_key" in r}
    assert live == space.observed_keys(), (
        f"static-only={sorted(space.observed_keys() - live)} "
        f"live-only={sorted(live - space.observed_keys())}")


# --------------------------------- uniform-scan consolidation (pins)

def _scan_shapes(closed_jaxpr):
    """Distinct scan-body signatures in a jaxpr (recursing through
    pjit/custom_vjp/etc. via iter_eqns) — each distinct signature is
    one scan program XLA compiles."""
    from roc_tpu.analysis.jaxpr_lint import iter_eqns
    shapes = set()
    for eqn in iter_eqns(closed_jaxpr):
        avals = tuple(str(v.aval) for v in eqn.invars)
        # a loop over refs is inside a Pallas kernel's body (the
        # gather-sum's rows), not a program of its own
        if eqn.primitive.name == "scan" and not any(
                a.startswith("Ref<") for a in avals):
            shapes.add(avals)
    return shapes


def test_flat_sum_single_scan_program(rig_dataset):
    """THE consolidation pin: a flat_sum config with ONE aggregation
    width compiles exactly ONE scan program into its train step —
    forward and symmetric-vjp backward share the shape, and the degree
    distribution moves nothing in it but the chunk's height (a skewed
    dataset enumerates one scan too; the per-bucket ELL unroll would
    have compiled one program per width bucket).  The height is the
    graph's own sub-row count, to the 8, while that is under the cap,
    and the cap's from sixteen chunks on (``core/ell.py
    fit_chunks``)."""
    import re
    from roc_tpu.analysis.programspace import _C, _F
    from roc_tpu.core.graph import synthetic_dataset
    from roc_tpu.models.sgc import build_sgc
    from roc_tpu.train.trainer import TrainConfig, Trainer

    def shapes_for(ds):
        tr = Trainer(build_sgc([_F, _C], k=2, dropout_rate=0.5), ds,
                     TrainConfig(verbose=False, symmetric=True,
                                 aggr_impl="flat_sum",
                                 dtype=jnp.float32,
                                 compute_dtype=jnp.bfloat16))
        lr = jnp.asarray(0.01, jnp.float32)
        jaxpr = jax.make_jaxpr(tr._train_step._jit)(
            tr.params, tr.opt_state, tr.key, lr, tr.feats,
            tr.labels, tr.mask, tr.gctx)
        return _scan_shapes(jaxpr)

    def one_scan(ds):
        """(the scan's operand shapes with the chunk height masked,
        the chunk height)"""
        shapes = shapes_for(ds)
        assert len(shapes) == 1, shapes
        (avals,) = shapes
        (seg,) = {int(m) for a in avals
                  for m in re.findall(r"\[1,(\d+)(?:,8)?\]", a)}
        subs = int((-(-np.diff(ds.graph.row_ptr) // 8)).sum())
        assert seg == -(-subs // 8) * 8
        return tuple(a.replace(f"[1,{seg}", "[1,seg") for a in avals), seg

    masked, seg = one_scan(rig_dataset)
    # a much more skewed graph of the same size: the same single scan
    # at its own height
    skew = synthetic_dataset(num_nodes=256, avg_degree=12, in_dim=_F,
                             num_classes=_C, seed=7)
    masked_skew, seg_skew = one_scan(skew)
    assert masked_skew == masked and seg_skew != seg


def test_flat_sum_rig_one_scan_per_width(rig_dataset):
    """The flat-sum rig (gin_flat8, two aggregation widths F and H):
    the distributed train step's distinct scan programs == one per
    (dtype, F-quantum) — the tentpole claim, pinned.  The quantum is
    the width the scan runs at (``core/ell.py agg_lane_width``): both
    of the rig's widths sit under the 128 lanes, so both layers share
    ONE scan program."""
    spec = rig_configs()["gin_flat8"]
    if spec.parts > len(jax.devices()):
        pytest.skip(f"needs {spec.parts} devices")
    tr = build_rig_trainer(spec, rig_dataset)
    assert tr.config.aggr_impl == "flat_sum"
    d = tr.data
    lr = jnp.asarray(0.01, jnp.float32)
    jaxpr = jax.make_jaxpr(tr._train_step._jit)(
        tr.params, tr.opt_state, d.feats, d.labels, d.mask,
        d.edge_src, d.edge_dst, d.in_degree, d.ell_idx,
        d.ell_row_pos, d.ell_row_id, d.ring_idx, d.sect_idx,
        d.sect_sub_dst, d.bd_tabs,
        (d.ell_w, d.sect_w, d.ring_w, d.bd_scale), tr.key, lr)
    shapes = _scan_shapes(jaxpr)
    widths = {op.dim for op in tr.model._ops
              if op.kind == "scatter_gather"}
    assert len(widths) == 2          # GIN aggregates at F and H
    from roc_tpu.core.ell import agg_lane_width
    quanta = {agg_lane_width(w, "flat_sum") for w in widths}
    assert quanta == {128}
    assert len(shapes) == len(quanta), shapes


# -------------------------------------------- program budget ratchet

def test_program_budget_shrink_only(tmp_path):
    """min(stored, measured): a bound initializes and shrinks, never
    grows; unmeasured configs keep their stored bounds; the findings
    list rides through untouched."""
    from roc_tpu.analysis.findings import (load_baseline,
                                           load_program_budget,
                                           save_baseline,
                                           shrink_program_budget)
    bp = str(tmp_path / "baseline.json")
    save_baseline(bp, ["r|u|k"], program_budget={"a": 5, "keep": 9})
    got = shrink_program_budget(bp, {"a": 7, "b": 4})
    # a: 7 > 5 stored -> stays 5; b: initialized at 4; keep: untouched
    assert got == {"a": 5, "b": 4, "keep": 9}
    assert load_program_budget(bp) == got
    assert load_baseline(bp) == {"r|u|k"}
    # shrink: measured 3 < stored 5
    assert shrink_program_budget(bp, {"a": 3})["a"] == 3
    # saving findings with program_budget=None preserves the section
    save_baseline(bp, [])
    assert load_program_budget(bp)["a"] == 3
    # known= drops bounds for configs that no longer exist (renamed
    # rigs) while keeping known-but-unmeasured ones
    got = shrink_program_budget(bp, {"a": 3}, known={"a", "keep"})
    assert got == {"a": 3, "keep": 9}


# --------------------------------------------------- CLI + registration

def test_new_rules_registered():
    from roc_tpu.analysis.driver import all_rule_names, is_trace_rule
    names = set(all_rule_names())
    for r in ("collective-ppermute-cycle", "collective-axis-name",
              "collective-conditional", "collective-ring-halo",
              "compile-explosion", "cache-key-drift"):
        assert r in names, r
        assert is_trace_rule(r), r


def test_cli_json_update_baseline_reports_post_state(tmp_path):
    """--json --update-baseline: the payload describes the state the
    run LEAVES (stale entries it just removed are gone from the
    output, and the file is rewritten) — a CI consumer must not
    re-flag a ratchet the same invocation already cleared."""
    bp = tmp_path / "scripts" / "lint_baseline.json"
    bp.parent.mkdir()
    bp.write_text(json.dumps(
        {"version": 1, "findings": ["stdout-print|gone|x"]}))
    (tmp_path / "roc_tpu").mkdir()
    (tmp_path / "roc_tpu" / "clean.py").write_text("x = 1\n")
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, "-m", "roc_tpu.analysis", "--json",
         "--update-baseline", "--root", str(tmp_path),
         "--select", "stdout-print"],
        capture_output=True, text=True, timeout=60, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    payload = json.loads(r.stdout)
    assert payload["stale"] == []
    assert payload["summary"]["stale"] == 0
    assert json.loads(bp.read_text())["findings"] == []


def test_cli_baseline_override_governs_program_budget(tmp_path):
    """--baseline points the compile-explosion bound AND the ratchet
    at the same file: an override with a tighter budget must fire the
    rule (the check and the shrink can't operate on different
    files)."""
    bp = tmp_path / "baseline.json"
    bp.write_text(json.dumps(
        {"version": 1, "findings": [],
         "program_budget": {"gin_flat8": 1, "sgc_stream": 99}}))
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, "-m", "roc_tpu.analysis",
         "--baseline", str(bp), "--select", "compile-explosion"],
        cwd=_REPO, capture_output=True, text=True, timeout=180,
        env=env)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "compile-explosion" in r.stdout
    assert "baseline 1, delta +1" in r.stdout


def test_cli_strict_fails_on_budget_slack(tmp_path):
    """Same ratchet semantics as stale findings: a measured program
    count BELOW the recorded bound must be committed via
    --update-baseline under --strict — a later program-count
    regression would otherwise hide inside the slack and the
    compile-wall tripwire would never fire."""
    bp = tmp_path / "baseline.json"
    bp.write_text(json.dumps(
        {"version": 1, "findings": [],
         "program_budget": {"gin_flat8": 9, "sgc_stream": 7}}))
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    args = [sys.executable, "-m", "roc_tpu.analysis",
            "--baseline", str(bp), "--select", "compile-explosion"]
    r = subprocess.run(args + ["--strict"], cwd=_REPO,
                       capture_output=True, text=True, timeout=180,
                       env=env)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "2 measured < 9 baselined" in r.stdout
    # non-strict: a note, not a failure
    r2 = subprocess.run(args, cwd=_REPO, capture_output=True,
                        text=True, timeout=180, env=env)
    assert r2.returncode == 0, r2.stdout + r2.stderr
    assert "note:" in r2.stdout
    # --update-baseline ratchets the bound down and clears the slack
    r3 = subprocess.run(args + ["--strict", "--update-baseline"],
                        cwd=_REPO, capture_output=True, text=True,
                        timeout=180, env=env)
    assert r3.returncode == 0, r3.stdout + r3.stderr
    assert json.loads(bp.read_text())["program_budget"] == \
        {"gin_flat8": 2, "sgc_stream": 6, "sgc_serve": 4,
         "sgc_serve_q8": 4, "gin_mesh2d": 2}


def test_cli_json_reports_program_space():
    """--json: one machine-readable object on stdout with the
    compile-budget reports and full program-key sets, so CI can diff
    program counts across commits without parsing text.  A
    programspace-only --select skips the jaxpr/HLO trace stage."""
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, "-m", "roc_tpu.analysis", "--json",
         "--select", "compile-explosion,cache-key-drift"],
        cwd=_REPO, capture_output=True, text=True, timeout=180,
        env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    payload = json.loads(r.stdout)
    assert payload["summary"]["new"] == 0
    reports = {p["config"]: p for p in payload["program_space"]}
    assert set(reports) == {"gin_flat8", "sgc_stream", "sgc_serve",
                            "sgc_serve_q8", "gin_mesh2d"}
    for rep in reports.values():
        assert rep["programs"] == len(rep["keys"])
        assert rep["budget"] is not None
        assert rep["delta"] == 0
