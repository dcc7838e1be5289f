"""Program scopes (obs/scopes.py): every operation of a compiled step
carries a ``roc.<class>`` scope in its ``op_name`` metadata — and only
there — and ``ObservedJit.instruction_scopes()`` reads the instruction
-> scope map back from the compiled program's own text.

Model families x aggregation layouts: the train and eval steps are
compiled once per case (module cache) and three properties are held on
each; then the collectives of the four-device steps, the stale-cache
recompile path, the parser, and ``--profile-dir``'s ``scopes.*.json``.
"""

import json
import os
import re

import jax
import pytest

from roc_tpu.core.graph import synthetic_dataset
from roc_tpu.models.builder import Model
from roc_tpu.models.deepergcn import build_deepergcn
from roc_tpu.models.gat import build_gat
from roc_tpu.models.gcn import build_gcn
from roc_tpu.models.gin import build_gin
from roc_tpu.models.sage import build_sage
from roc_tpu.models.sgc import build_sgc
from roc_tpu.obs import compile_watch
from roc_tpu.obs.compile_watch import ObservedJit
from roc_tpu.obs.scopes import (AGG, AGG_KINDS, ALLREDUCE, CLASSES, DENSE,
                                HALO, has_scopes, op_scope, parse_op_name,
                                parse_program_text)
from roc_tpu.parallel.distributed import DistributedTrainer
from roc_tpu.train.trainer import TrainConfig, Trainer

LAYERS = [12, 8, 5]
BUILDERS = {"gcn": build_gcn, "sage": build_sage, "gin": build_gin,
            "sgc": build_sgc, "gat": build_gat,
            "deepergcn": build_deepergcn}
# attention needs the ELL tables: the trainers force that layout
CASES = [(fam, impl) for fam in ("gcn", "sage", "gin", "sgc")
         for impl in ("sectioned", "flat_sum", "segment")] + [("gat", "ell")] \
    + [("deepergcn", impl) for impl in ("sectioned", "flat_sum")]
# instructions traced from a primitive (their op_name starts "jit(")
# that may sit outside every roc. scope: the argument plumbing of jit
# and shard_map, value_and_grad's seed and, in the distributed step, the
# ~100 scalar instructions of the per-partition dropout-key fold
UNSCOPED_MAX = {1: 24, 4: 260}
# the gather-sum Pallas call's name (ops/aggregate.py _gather_sum_call).
# On the chip it is one custom call under its op's scope; interpreted on
# the CPU its body is under that scope too, but XLA hoists the scalar
# constants of its index arithmetic to the step's top level, where they
# carry no scope: a constant that only the kernel reads is its own
KERNEL = "agg_gather_sum"

_cache = {}


@pytest.fixture(scope="module")
def ds():
    return synthetic_dataset(num_nodes=96, avg_degree=5, in_dim=LAYERS[0],
                             num_classes=LAYERS[-1], seed=5)


@pytest.fixture(scope="module", autouse=True)
def _release():
    yield
    _cache.clear()
    jax.clear_caches()


def _steps(ds, family, impl, parts=1, halo="gather"):
    """(model, train-step map, eval-step map) of one compiled trainer."""
    key = (family, impl, parts, halo)
    if key not in _cache:
        cfg = TrainConfig(verbose=False, symmetric=True, aggr_impl=impl,
                          halo=halo)
        model = BUILDERS[family](LAYERS)
        tr = (Trainer(model, ds, cfg) if parts == 1
              else DistributedTrainer(model, ds, parts, cfg))
        tr.train(epochs=1)
        tr.evaluate()
        _cache[key] = (tr.model, _scopes(tr._train_step),
                       _scopes(tr._eval_step))
    return _cache[key]


def _scopes(step) -> dict:
    """``step.instruction_scopes()`` and, under ``"kernel_consts"``,
    the constants of its program that only the kernel reads."""
    got = dict(step.instruction_scopes())
    got["kernel_consts"] = _kernel_constants(step._compiled.as_text())
    return got


def _kernel_constants(text: str) -> set:
    """Names of the ``constant`` instructions of a compiled program's
    ``text`` whose every user's ``op_name`` holds :data:`KERNEL`."""
    consts, users = set(), {}
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = (.*)$", line)
        if m is None:
            continue
        name, rest = m.groups()
        if re.search(r"\bconstant\(", rest):
            consts.add(name)
        op_name = re.search(r'op_name="([^"]*)"', rest)
        for operand in re.findall(r"%([\w.\-]+)", rest):
            users.setdefault(operand, []).append(
                op_name.group(1) if op_name else "")
    return {c for c in consts
            if users.get(c) and all(KERNEL in u for u in users[c])}


def _loose(got) -> list:
    """The op_names traced from a primitive (they start "jit(") that
    sit outside every roc. scope, bar the kernel's own constants."""
    return [n for ins, n in got["scopes"].items()
            if n.startswith("jit(") and parse_op_name(n) is None
            and ins not in got["kernel_consts"]]


def _parsed(got):
    return [p for p in map(parse_op_name, got["scopes"].values())
            if p is not None]


@pytest.mark.parametrize("family,impl", CASES)
def test_every_aggregation_carries_its_index_in_both_directions(
        ds, family, impl):
    model, train, evalm = _steps(ds, family, impl)
    agg_ops = {i for i, op in enumerate(model._ops)
               if op.kind in AGG_KINDS}
    assert agg_ops
    seen_train = {(i, way) for cls, i, way in _parsed(train) if cls == AGG}
    # an aggregation of the raw features (SGC, GIN's and SAGE's first)
    # has no backward: nothing upstream of it is a parameter
    needs_bwd = {i for i in agg_ops if _upstream_has_param(model, i)}
    assert {i for i, way in seen_train if way == "fwd"} == agg_ops
    assert {i for i, way in seen_train if way == "bwd"} == needs_bwd
    seen_eval = {(i, way) for cls, i, way in _parsed(evalm) if cls == AGG}
    assert seen_eval == {(i, "fwd") for i in agg_ops}
    assert train["map_from"] == evalm["map_from"] == "loaded"


def _upstream_has_param(model: Model, i: int) -> bool:
    todo, seen = list(model._ops[i].inputs), set()
    while todo:
        j = todo.pop()
        if j in seen:
            continue
        seen.add(j)
        if model._ops[j].param is not None:
            return True
        todo.extend(model._ops[j].inputs)
    return False


@pytest.mark.parametrize("family,impl", CASES)
def test_every_op_kind_maps_to_exactly_one_class(ds, family, impl):
    model, train, evalm = _steps(ds, family, impl)
    classes_of = {}
    for got in (train, evalm):
        for op_name in got["scopes"].values():
            for cls, idx, kind in re.findall(
                    r"roc\.(\w+)\.op(\d+)(?:\.(\w+))?", op_name):
                op = model._ops[int(idx)]
                # the name was built from this very op
                assert op_scope(int(idx), op.kind) == (
                    f"roc.{cls}.op{idx}" + (f".{kind}" if kind else ""))
                classes_of.setdefault(op.kind, set()).add(cls)
    for kind, classes in classes_of.items():
        assert classes == {AGG if kind in AGG_KINDS else DENSE}, kind
    # nothing the model holds went unseen, bar ops XLA folds away whole
    kinds = {op.kind for op in model._ops[1:]}
    assert kinds - set(classes_of) <= {"activation", "add", "dropout"}
    assert {cls for cls, _, _ in _parsed(train)} >= {
        AGG, DENSE, "loss", "opt"}


@pytest.mark.parametrize("family,impl", CASES)
def test_unscoped_instructions_are_a_short_list(ds, family, impl):
    _, train, evalm = _steps(ds, family, impl)
    for got in (train, evalm):
        traced = [n for n in got["scopes"].values() if n.startswith("jit(")]
        loose = _loose(got)
        assert len(loose) <= UNSCOPED_MAX[1], sorted(set(loose))
        assert len(traced) > 10 * len(loose)


@pytest.mark.parametrize("halo,collective", [
    ("gather", "all_gather"), ("ring", "ppermute")])
def test_collectives_sit_under_halo_and_allreduce(ds, halo, collective):
    impl = "flat_sum" if halo == "gather" else "segment"
    _, train, evalm = _steps(ds, "gcn", impl, parts=4, halo=halo)
    found = {}              # by the JAX primitive that ends the op_name
    for op_name in train["scopes"].values():
        prim = op_name.rsplit("/", 1)[-1]
        if prim in ("all_gather", "ppermute", "psum"):
            found.setdefault(prim, set()).add(parse_op_name(op_name))
    assert {cls for cls, _, _ in found[collective]} == {HALO}
    # each halo exchange belongs to an aggregation, in both directions
    assert {way for _, _, way in found[collective]} == {"fwd", "bwd"}
    assert all(idx is not None for _, idx, _ in found[collective])
    assert {cls for cls, _, _ in found["psum"]} == {ALLREDUCE}
    loose = _loose(train)
    assert len(loose) <= UNSCOPED_MAX[4], sorted(set(loose))
    assert ALLREDUCE in {cls for cls, _, _ in _parsed(evalm)}


def test_scopes_live_in_metadata_only(ds):
    """The acceptance check of ISSUE 25: the lowered program (locations
    off, the default) holds no ``roc.`` at all — same instructions, same
    executable, same compile-cache key — while the compiled text shows
    each aggregation under ``jvp(`` and ``transpose(jvp(``."""
    tr = Trainer(build_gcn(LAYERS), ds,
                 TrainConfig(verbose=False, aggr_impl="sectioned"))
    tr.train(epochs=1)
    step = tr._train_step
    assert "roc." not in step._lowered.as_text()
    text = step._compiled.as_text()
    for i, op in enumerate(tr.model._ops):
        if op.kind in AGG_KINDS:
            assert f"/jvp(roc.agg.op{i:02d})/" in text
            assert f"/transpose(jvp(roc.agg.op{i:02d}))/" in text


STALE = """HloModule jit_step, is_scheduled=true

%fused_computation (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %neg = f32[8]{0} negate(%p), metadata={op_name="jit(step)/neg"}
}

ENTRY %main (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0), metadata={op_name="x"}
  ROOT %fusion.1 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/neg"}
}
"""
FRESH = STALE.replace("jit(step)/neg", "jit(step)/jvp(roc.dense.op01.mul)/neg")


class _Canned:
    def __init__(self, text):
        self._text = text

    def as_text(self):
        return self._text


def _observer(text):
    obs = ObservedJit(lambda x: x, name="canned")
    obs._compiled, obs._lowered = _Canned(text), object()
    return obs


def test_stale_cache_entry_takes_the_recompile_path(monkeypatch):
    """A cache entry written before the scopes existed is served with
    its old metadata: the map then comes from one uncached compile."""
    asked = []

    def fake(lowered):
        asked.append(lowered)
        return FRESH

    monkeypatch.setattr(compile_watch, "compile_text_uncached", fake)
    obs = _observer(STALE)
    got = obs.instruction_scopes()
    assert asked == [obs._lowered]
    assert got["map_from"] == "recompiled" and got["module"] == "jit_step"
    assert got["scopes"]["fusion.1"] == (
        "jit(step)/jvp(roc.dense.op01.mul)/neg")
    assert got["scopes"]["p"] == "" and got["text_bytes"] == len(FRESH)


def test_loaded_text_with_scopes_is_read_as_it_is(monkeypatch):
    monkeypatch.setattr(compile_watch, "compile_text_uncached",
                        lambda lowered: pytest.fail("no recompile"))
    got = _observer(FRESH).instruction_scopes()
    assert got["map_from"] == "loaded"
    assert set(got["scopes"]) == {"p", "neg", "x", "fusion.1"}


def test_failed_recompile_degrades_to_the_loaded_text(monkeypatch):
    def boom(lowered):
        raise RuntimeError("no compiler")

    monkeypatch.setattr(compile_watch, "compile_text_uncached", boom)
    got = _observer(STALE).instruction_scopes()
    assert got["map_from"] == "loaded" and not has_scopes(
        " ".join(got["scopes"].values()))
    assert ObservedJit(lambda x: x, name="idle").instruction_scopes() is None


def test_uncached_compile_gives_the_running_programs_map(ds):
    """The real bypass (on XLA:CPU: compiled and loaded) names the same
    instructions under the same scopes as the running executable."""
    tr = Trainer(build_gcn(LAYERS), ds,
                 TrainConfig(verbose=False, aggr_impl="flat_sum"))
    tr.train(epochs=1)
    step = tr._train_step
    again = parse_program_text(
        compile_watch.compile_text_uncached(step._lowered))
    want = step.instruction_scopes()
    assert again["module"] == want["module"]
    assert again["scopes"] == want["scopes"]


@pytest.mark.parametrize("op_name,want", [
    ("jit(step)/jvp(roc.agg.op03)/while/body/gather", (AGG, 3, "fwd")),
    ("jit(step)/transpose(jvp(roc.agg.op03))/while/body/closed_call/add",
     (AGG, 3, "bwd")),
    ("jit(step)/shard_map/transpose(jvp(roc.agg.op06))/roc.halo/all_gather",
     (HALO, 6, "bwd")),
    ("jit(step)/jvp(roc.dense.op01.dropout)/transpose", (DENSE, 1, "fwd")),
    ("jit(step)/roc.opt/mul", ("opt", None, "fwd")),
    ("jit(step)/shard_map/roc.allreduce/psum", (ALLREDUCE, None, "fwd")),
    ("jit(step)/transpose(jvp(roc.loss))/sub", ("loss", None, "bwd")),
    ("jit(step)/shard_map", None), ("params['linear_0']", None), ("", None),
])
def test_parse_op_name(op_name, want):
    assert parse_op_name(op_name) == want
    if want is not None:
        assert want[0] in CLASSES


def test_profile_dir_writes_the_scope_map_beside_the_trace(tmp_path):
    from roc_tpu.train import cli
    prof = tmp_path / "prof"
    rc = cli.main(["--cpu", "-layers", "16-8-4", "-e", "2",
                   "--profile-dir", str(prof)])
    assert rc == 0
    with open(prof / "scopes.train_step.json") as f:
        got = json.load(f)
    assert got["module"].startswith("jit_") and got["map_from"] == "loaded"
    assert {AGG, DENSE, "loss", "opt"} <= {
        p[0] for p in map(parse_op_name, got["scopes"].values()) if p}
    assert any(name.endswith(".xplane.pb")
               for _, _, names in os.walk(prof) for name in names)


@pytest.mark.parametrize("parts", [1, 4])
def test_batch_norm_and_softmax_aggregation_name_their_parts(ds, parts):
    """``roc.bn.stats`` sits inside ``roc.dense.op<i>.batch_norm`` and
    ``roc.sagg.weights`` inside the softmax aggregation's
    ``roc.agg.op<i>``, forward and backward, and neither changes the
    op's class; across partitions the moments' ``psum`` is under
    ``roc.allreduce`` inside the batch_norm's scope — the one
    collective of the step between a layer's dense ops — and the
    shift's all-gather under ``roc.allreduce`` inside the
    aggregation's."""
    from roc_tpu.obs.scopes import (ALLREDUCE_SCOPE, BN_STATS_SCOPE,
                                    SAGG_WEIGHTS_SCOPE)
    model, train, evalm = _steps(ds, "deepergcn", "sectioned", parts)
    kind_of = {i: op.kind for i, op in enumerate(model._ops)}
    names = list(train["scopes"].values())
    for scope, kind, cls in ((BN_STATS_SCOPE, "batch_norm", DENSE),
                             (SAGG_WEIGHTS_SCOPE, "soft_aggregate", AGG)):
        under = [n for n in names if scope in n]
        assert under
        parsed = [parse_op_name(n) for n in under]
        assert {kind_of[i] for _, i, _ in parsed} == {kind}
        assert {way for _, _, way in parsed} == {"fwd", "bwd"}
        plain = [p for n, p in zip(under, parsed)
                 if ALLREDUCE_SCOPE not in n]
        assert plain and {c for c, _, _ in plain} == {cls}
    inside_bn = [n for n in names
                 if ".batch_norm" in n and ALLREDUCE_SCOPE in n]
    inside_agg = [n for n in names
                  if ALLREDUCE_SCOPE in n and "roc.agg.op" in n]
    if parts == 1:
        assert not inside_bn and not inside_agg
    else:
        assert inside_bn and all(BN_STATS_SCOPE in n for n in inside_bn)
        assert any("psum" in n or "all-reduce" in n or "all_reduce" in n
                   for n in inside_bn)
        assert {parse_op_name(n)[0] for n in inside_bn} == {ALLREDUCE}
        assert inside_agg and all(SAGG_WEIGHTS_SCOPE in n
                                  for n in inside_agg)
    # eval reads the running statistics: no moment reduction there
    assert not [n for n in evalm["scopes"].values()
                if BN_STATS_SCOPE in n]
