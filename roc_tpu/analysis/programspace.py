"""Program-space auditor: enumerate the compiled-program set WITHOUT
compiling.

The compile wall is a *program count* problem before it is a compile
speed problem: every ObservedJit slot, every streamed-head block
variant, and every quantized partition shape compiles its own XLA
program, and nothing short of a live run ever said how many programs a
config implies.  This level walks the SAME resolvers the trainers use
(``train/trainer.resolve_config``: fuse / auto-impl probe / memory
autopilot / attention impl — plus prefetch and partition method),
builds the rig trainers (table construction only — jits are lazy,
nothing compiles; the built plans pass through the splitter's
``core/partition.quantize_plan_shapes``, which is what keeps the
enumerated shapes and the trainers' real shapes in agreement), and
abstract-evals each candidate step to its canonical **program key**
``(slot, avals, shardings, donation)`` — the same
``obs/compile_watch.program_key_of`` every ObservedJit ``compile``
event now records, so the static enumeration is held against live
runs exactly (tests/test_programspace.py parity).

Products:

- a per-config **compile budget report** (program count x a coarse
  modeled compile cost), emitted as ``programspace`` obs events and
  rendered by ``roc_tpu.report``;
- [compile-explosion] — program count over the baselined bound for a
  rig config (``scripts/lint_baseline.json`` ``program_budget``,
  shrink-only like every ratchet): the static tripwire for the
  ROADMAP's compile-wall item — a PR that adds a compiled-program
  shape fails HERE, before any chip time;
- [cache-key-drift] — two program keys that differ ONLY by dimensions
  that snap to the same node- or edge-multiple (the
  ``NODE_MULTIPLE``/``EDGE_MULTIPLE`` grid ``quantize_plan_shapes``
  quantizes every plan to; the drift snap checks dims against that
  grid directly — it does not re-run the per-part plan derivation).
  Such a pair means an unquantized shape LEAKED around
  ``quantize_plan_shapes`` into one of the slots: wherever that slot's
  trace is rebuilt at a slightly different size (rebalance, resume,
  serve), the shape lands off the quantization grid and misses the
  persistent compile cache — the recompile class the PR-5 machinery
  exists to avoid.  The cross-slot comparison is the static proxy
  (one enumeration sees each slot once; the leaked dim shows up as
  disagreement BETWEEN slots that share their tensors).
"""

from __future__ import annotations

import hashlib
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

from ..core.partition import EDGE_MULTIPLE, NODE_MULTIPLE, _round_up
from ..obs.events import emit
from .findings import Finding
from .jaxpr_lint import iter_eqns

# rig scale — THE synthetic-rig dimensions; driver.py imports these
# so the auditor and the jaxpr lint stage can never check different
# rigs
_V, _DEG, _F, _C, _H = 256, 6, 48, 6, 24

PROGRAMSPACE_RULES = ("compile-explosion", "cache-key-drift")

# Coarse affine compile-cost model, CPU-rig derived: a trivial jit is
# ~100 ms of fixed XLA pipeline overhead and cost grows roughly
# linearly in traced eqn count at small scale.  The report needs
# ORDERING between configs and a human-scale number, not accuracy —
# the ratchet is on the program COUNT.
COMPILE_MS_BASE = 100.0
COMPILE_MS_PER_EQN = 2.0


@dataclass(frozen=True)
class ProgramEntry:
    """One program the config will compile.  ``observed`` marks slots
    that compile through ObservedJit (the live-parity set); aux
    programs (streamed-head block jits) are counted in the budget but
    leave no ``compile`` event."""

    slot: str
    key: str                      # obs/compile_watch.program_key_of
    leaves: Tuple[Tuple[str, Tuple[int, ...], str], ...]
    observed: bool
    eqns: int

    @property
    def digest(self) -> str:
        return hashlib.sha1(self.key.encode()).hexdigest()[:12]

    @property
    def modeled_compile_ms(self) -> float:
        return COMPILE_MS_BASE + COMPILE_MS_PER_EQN * self.eqns


@dataclass
class ProgramSpace:
    """The enumerated program set of one rig config."""

    config: str
    entries: List[ProgramEntry]
    node_multiple: int = NODE_MULTIPLE
    edge_multiple: int = EDGE_MULTIPLE
    resolved: Dict[str, Any] = field(default_factory=dict)

    @property
    def program_count(self) -> int:
        return len(self.entries)

    def observed_keys(self) -> set:
        return {e.key for e in self.entries if e.observed}

    def modeled_compile_ms(self) -> float:
        return round(sum(e.modeled_compile_ms for e in self.entries), 1)

    def report(self, budget: Optional[int] = None) -> Dict[str, Any]:
        """The compile-budget record: the ``programspace`` event body,
        the report table row, and the ``--json`` payload."""
        rep: Dict[str, Any] = {
            "config": self.config,
            "programs": self.program_count,
            "observed_programs": len(self.observed_keys()),
            "modeled_compile_ms": self.modeled_compile_ms(),
            "slots": [e.slot for e in self.entries],
            "digests": [e.digest for e in self.entries],
            "budget": budget,
        }
        if budget is not None:
            rep["delta"] = self.program_count - budget
        return rep


@dataclass
class RigSpec:
    """One audited rig configuration: a model builder + TrainConfig
    factory + mesh width.  Factories (not instances) because a spec is
    enumerated, parity-tested, and idempotency-checked independently —
    each build must start from a pristine config.

    ``serve`` names a serving backend instead of a trainer: the rig
    then builds a ``roc_tpu/serve`` Predictor (same resolve pass, so
    the idempotency assert still applies) and the enumerated set is
    its bucketed serve-program space — which is how the serve tier's
    programs fall under the SAME ``program_budget`` ratchet and
    prewarm driver as the training steps.  ``quant`` selects the serve
    table encoding (``serve/quant.py``): quantized variants are
    DISTINCT programs with distinct slots (``_q8``/``_qf8``), so they
    get their own rig + budget row instead of inflating the fp32
    rig's."""

    name: str
    model: Callable[[], Any]
    config: Callable[[], Any]
    parts: int = 1
    serve: Optional[str] = None
    quant: str = "off"


def _rig_specs() -> Dict[str, RigSpec]:
    import jax.numpy as jnp

    from ..models.gin import build_gin
    from ..models.sgc import build_sgc
    from ..train.trainer import TrainConfig

    return {
        # GIN through the uniform width-8 FLAT-SUM layout on a
        # 2-device mesh: the sum-path uniform-scan consolidation
        # (ops/aggregate.py aggregate_flat_sum — ONE scan program per
        # aggregation width instead of one per degree bucket), and
        # the quantized-partition-shape config (the PR-5 splitter's
        # node/edge multiples are load-bearing in these program keys)
        "gin_flat8": RigSpec(
            name="gin_flat8",
            model=lambda: build_gin([_F, _H, _C], dropout_rate=0.5),
            config=lambda: TrainConfig(
                verbose=False, symmetric=True, aggr_impl="flat_sum",
                dtype=jnp.float32, compute_dtype=jnp.bfloat16),
            parts=2),
        # SGC with host-streamed features: the config whose program
        # space is NOT just the ObservedJit slots — the streamed head
        # compiles per-block-shape static variants too
        "sgc_stream": RigSpec(
            name="sgc_stream",
            model=lambda: build_sgc([_F, _C], k=2, dropout_rate=0.5),
            config=lambda: TrainConfig(
                verbose=False, symmetric=True, features="host",
                dtype=jnp.float32, compute_dtype=jnp.bfloat16),
            parts=1),
        # the serving tier (roc_tpu/serve): the SGC precomputed-
        # propagation predictor's bucketed program set — one program
        # per microbatch bucket, nothing else.  Enumerated here so a
        # PR that grows the serve program space (a new bucket, an
        # unquantized request shape) trips the compile-explosion
        # ratchet before any chip time, and so `python -m
        # roc_tpu.prewarm --config all` AOT-warms the serve
        # executables alongside the training steps.
        "sgc_serve": RigSpec(
            name="sgc_serve",
            model=lambda: build_sgc([_F, _C], k=2, dropout_rate=0.5),
            config=lambda: TrainConfig(
                verbose=False, symmetric=True, dtype=jnp.float32),
            parts=1, serve="precomputed"),
        # the QUANTIZED serve variant (PR 19): the same predictor
        # under int8 tables — the dequant-in-register bucket programs
        # (`serve_precomputed_akx_q8:{b}`) are a distinct program set
        # with distinct arg avals (int8 codes + fp32 scales), so they
        # ratchet under their own budget row while `sgc_serve` stays
        # at delta +0, and the prewarm driver AOT-warms the quantized
        # executables the export/cold-load path reuses.
        "sgc_serve_q8": RigSpec(
            name="sgc_serve_q8",
            model=lambda: build_sgc([_F, _C], k=2, dropout_rate=0.5),
            config=lambda: TrainConfig(
                verbose=False, symmetric=True, dtype=jnp.float32),
            parts=1, serve="precomputed", quant="int8"),
        # the (parts, model) 2-D mesh rig: gin_flat8's exact program
        # set widened to mesh=2x4 — params/Adam moments model-sharded
        # at rest, the partial-auto steps take the extra partition-
        # index arg, and every param/opt leaf's rendered sharding spec
        # lands in the program keys.  Needs 8 devices (parts * model
        # — rig_required_devices), so single-device CI skips it the
        # same way it skips parts > 1.
        "gin_mesh2d": RigSpec(
            name="gin_mesh2d",
            model=lambda: build_gin([_F, _H, _C], dropout_rate=0.5),
            config=lambda: TrainConfig(
                verbose=False, symmetric=True, aggr_impl="flat_sum",
                mesh="2x4",
                dtype=jnp.float32, compute_dtype=jnp.bfloat16),
            parts=2),
    }


RIG_CONFIGS: Dict[str, RigSpec] = {}


def rig_configs() -> Dict[str, RigSpec]:
    """Lazily built so importing the module never touches jax."""
    if not RIG_CONFIGS:
        RIG_CONFIGS.update(_rig_specs())
    return RIG_CONFIGS


def rig_required_devices(spec: RigSpec) -> int:
    """Total devices this spec's mesh occupies: ``parts * model``
    (``train/trainer.resolve_mesh`` on the spec's own config).  THE
    device guard every rig walker shares — the audit loop here,
    sharding_lint's rig sweep, and the prewarm driver — so a 2-D rig
    is skipped (not crashed) on hosts with too few devices, by the
    same rule everywhere."""
    from ..train.trainer import resolve_mesh
    parts = max(spec.parts, 1)
    _, model = resolve_mesh(spec.config(), num_parts=parts)
    return parts * model


def build_rig_dataset():
    from ..core.graph import synthetic_dataset
    return synthetic_dataset(num_nodes=_V, avg_degree=_DEG, in_dim=_F,
                             num_classes=_C, seed=0)


def build_rig_trainer(spec: RigSpec, dataset=None):
    """The trainer (or, for serve rigs, the Predictor) a live run of
    this spec would construct — table builds only; every jit slot
    stays uncompiled until called."""
    ds = dataset if dataset is not None else build_rig_dataset()
    if spec.serve:
        from ..serve.export import build_predictor
        return build_predictor(spec.model(), ds, spec.config(),
                               backend=spec.serve, quant=spec.quant)
    if spec.parts > 1:
        from ..parallel.distributed import DistributedTrainer
        return DistributedTrainer(spec.model(), ds, spec.parts,
                                  spec.config())
    from ..train.trainer import Trainer
    return Trainer(spec.model(), ds, spec.config())


def _count_eqns(closed_jaxpr) -> int:
    return sum(1 for _ in iter_eqns(closed_jaxpr))


def _entry(slot: str, fn, args, donate: Tuple[int, ...] = (),
           observed: bool = True) -> ProgramEntry:
    """Abstract-eval one candidate program: the key comes from the
    args' avals (the identical derivation ObservedJit applies at first
    compile) and the eqn count from a trace — ``jax.make_jaxpr`` never
    invokes the XLA pipeline, so this is the no-compile walk the
    auditor promises."""
    import jax

    from ..obs.compile_watch import leaf_struct, program_key_of
    key = program_key_of(slot, args, donate)
    # leaf_struct is compile_watch's OWN extraction (the rendered key
    # is built from it), so the drift rule's dimension view and the
    # parity keys can never disagree
    leaves = tuple(leaf_struct(v)
                   for v in jax.tree_util.tree_leaves(args))
    eqns = _count_eqns(jax.make_jaxpr(fn)(*args))
    return ProgramEntry(slot=slot, key=key, leaves=leaves,
                        observed=observed, eqns=eqns)


def _assert_resolve_idempotent(spec: RigSpec, dataset) -> None:
    """The resolve pass must be a fixpoint: re-resolving a resolved
    config changes nothing, hence re-enumerating yields the identical
    program-key set (the round-5 advisor's resolve finding, closed
    structurally).  Asserted on every audit — a resolver edit that
    breaks this would silently fork the auditor from the trainers."""
    from ..train.trainer import resolve_config
    model1, cfg1, _ = resolve_config(spec.model(), dataset,
                                     spec.config(),
                                     num_parts=spec.parts)
    model2, cfg2, _ = resolve_config(model1, dataset, cfg1,
                                     num_parts=spec.parts)
    if cfg1 != cfg2:
        raise AssertionError(
            f"resolve_config is not idempotent for rig "
            f"{spec.name!r}: {cfg1} != {cfg2}")
    if model2 is not model1:
        raise AssertionError(
            f"resolve_config re-rewrote an already-resolved model "
            f"for rig {spec.name!r}")


@dataclass
class Candidate:
    """One candidate compiled program of a trainer's lifecycle: the
    traceable callable + args the auditor abstract-evals to a program
    key, PLUS the zero-arg AOT compile closure (``aot``) the cache
    prewarm driver executes (utils/prewarm.py) — one extraction, two
    consumers, so the enumerated set and the warmed set can never
    drift.  ``aot`` goes through the SAME jitted callable a live run
    compiles (``jit.lower(*args).compile()``), so the persistent-cache
    entry it writes is exactly the one the live process will hit."""

    slot: str
    fn: Any
    args: tuple
    donate: Tuple[int, ...] = ()
    observed: bool = True
    aot: Optional[Callable[[], Any]] = None
    # per-top-level-arg semantic labels for the sharding auditor's
    # ledger/seeding ("params" / "opt_state" / "data" / "tables" /
    # "other"); () = classify by shape alone.  One enumeration, three
    # consumers (program keys, prewarm, replication ledger) — the
    # roles live on the record so they can never drift from the args.
    roles: Tuple[str, ...] = ()


def candidate_programs(tr) -> List["Candidate"]:
    """The exact candidate-program list of a trainer's
    train+eval+predict lifecycle (``run_epoch_loop`` + ``predict()``
    — note predict compiles NOTHING of its own since it reuses the
    eval program's logits output; the multi-process-only
    ``dist_predict_gather`` is out of scope for single-controller
    rigs).  Works on any built trainer, not only the audited rigs."""
    import jax
    import jax.numpy as jnp

    lr = jnp.asarray(0.01, jnp.float32)
    cands: List[Candidate] = []

    if hasattr(tr, "serve_candidates"):          # serve Predictor
        return list(tr.serve_candidates())

    def add(slot, jitfn, args, donate=(), observed=True, roles=()):
        cands.append(Candidate(
            slot=slot, fn=jitfn, args=args, donate=donate,
            observed=observed, roles=roles,
            aot=lambda j=jitfn, a=args: j.lower(*a).compile()))

    if getattr(tr, "pg", None) is not None:       # distributed
        d = tr.data
        fuse = (d.ell_w, d.sect_w, d.ring_w, d.bd_scale)
        graph_args = (d.edge_src, d.edge_dst, d.in_degree, d.ell_idx,
                      d.ell_row_pos, d.ell_row_id, d.ring_idx,
                      d.sect_idx, d.sect_sub_dst, d.bd_tabs, fuse)
        graph_roles = ("tables",) * len(graph_args)
        # 2-D partial-auto steps take the trailing parts-sharded
        # partition-index vector (distributed._build_steps); the
        # enumerated args must carry it or the keys (and make_jaxpr
        # arity) diverge from the live programs
        pids = (() if getattr(tr, "_pids", None) is None
                else (tr._pids,))
        pid_roles = ("data",) * len(pids)
        add("dist_train_step", tr._train_step._jit,
            (tr.params, tr.opt_state, d.feats, d.labels, d.mask)
            + graph_args + (tr.key, lr) + pids, donate=(0, 1),
            roles=("params", "opt_state", "data", "data", "data")
            + graph_roles + ("other", "other") + pid_roles)
        add("dist_eval_step", tr._eval_step._jit,
            (tr.params, d.feats, d.labels, d.mask) + graph_args
            + pids,
            roles=("params", "data", "data", "data") + graph_roles
            + pid_roles)
    elif tr._head is None:                        # plain single-device
        add("train_step", tr._train_step._jit,
            (tr.params, tr.opt_state, tr.key, lr, tr.feats,
             tr.labels, tr.mask, tr.gctx), donate=(0, 1),
            roles=("params", "opt_state", "other", "other", "data",
                   "data", "data", "tables"))
        add("eval_step", tr._eval_step._jit,
            (tr.params, tr.feats, tr.labels, tr.mask, tr.gctx),
            roles=("params", "data", "data", "data", "tables"))
    else:                                         # streamed head
        # abstract stand-ins, never materialized: [V, H] at the >HBM
        # tier is multi-GB, and on a live trainer the aot closures
        # would otherwise pin the buffers alive for the whole warm
        # loop.  leaf_struct renders a
        # ShapeDtypeStruct identically to a default-placed array
        # (spec '-'), and both make_jaxpr and jit.lower accept them,
        # so keys and prewarmed executables are unchanged.
        w0 = tr.params[tr._head_param]
        y = jax.ShapeDtypeStruct(
            (tr.feats_host.shape[0], int(w0.shape[1])),
            jnp.dtype(tr.compute))
        grads = jax.tree_util.tree_map(
            lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype),
            tr.params)
        # y is the streamed-head [V, H] handoff — role "stream", not
        # "data": it carries the FEATURE axis, so the sharding ledger
        # treats it as parts-split AND model-shardable (the 2-D mesh's
        # block path, train/trainer._pin_stream), unlike node-axis
        # data rows
        add("tail_grad", tr._tail_grad._jit,
            (tr.params, y, tr.key, tr.labels, tr.mask, tr.gctx),
            donate=(1,),
            roles=("params", "stream", "other", "data", "data",
                   "tables"))
        add("tail_eval", tr._tail_eval._jit,
            (tr.params, y, tr.labels, tr.mask, tr.gctx),
            roles=("params", "stream", "data", "data", "tables"))
        add("apply_update", tr._apply_update._jit,
            (tr.params, tr.opt_state, grads, lr),
            donate=(0, 1, 2),
            roles=("params", "opt_state", "data", "other"))
        cands.extend(_head_block_candidates(tr, y))
    return cands


def enumerate_programs(spec: RigSpec, dataset=None,
                       trainer=None) -> ProgramSpace:
    """The exact set of distinct programs a train+eval+predict
    lifecycle of ``spec`` compiles — the audited lifecycle is the one
    ``run_epoch_loop`` + ``predict()`` executes, which is also what
    the parity test drives live."""
    ds = dataset if dataset is not None else build_rig_dataset()
    _assert_resolve_idempotent(spec, ds)
    tr = trainer if trainer is not None else build_rig_trainer(
        spec, ds)
    entries = [_entry(c.slot, c.fn, c.args, c.donate, c.observed)
               for c in candidate_programs(tr)]
    # single-device rigs build no partition plan; the drift rule
    # still snaps against the SAME default grid the splitter uses
    nm, em = NODE_MULTIPLE, EDGE_MULTIPLE
    if spec.parts > 1:
        nm, em = tr.pg.node_multiple, tr.pg.edge_multiple
    space = ProgramSpace(
        config=spec.name, entries=entries,
        node_multiple=nm, edge_multiple=em,
        resolved={"aggr_impl": tr.config.aggr_impl,
                  "halo": tr.config.halo,
                  "features": tr.config.features,
                  "remat": tr.config.remat,
                  "partition": tr.config.partition,
                  "parts": spec.parts})
    _check_distinct(space)
    return space


def _head_block_candidates(tr, y) -> List["Candidate"]:
    """The streamed head's per-block jit variants — one program per
    distinct (block rows, train/eval statics) pair: uniform blocks
    share one compile, a ragged tail block adds one, and the forward
    compiles separately for the train (dropout-keyed) and eval paths.
    These are module-level ``jax.jit``s, not ObservedJit slots, so
    they appear in the budget with ``observed=False``.  Their ``aot``
    closures lower the REAL jitted block fns (statics passed
    positionally, the dynamic ``lo`` offset as a traced arg exactly
    like the live call) so the prewarmed executables byte-match the
    live ones in the persistent cache."""
    import jax
    import jax.numpy as jnp

    from ..core.streaming import _head_fwd_block, _head_wgrad_block
    w0 = tr.params[tr._head_param].astype(tr.compute)
    rate = tr._head.rate
    cands: List[Candidate] = []
    # y rows == the audited dataset's node count (NOT the rig
    # constant): enumeration must hold for whatever dataset the
    # trainer was built from
    sizes = sorted({hi - lo
                    for lo, hi in tr._head._blocks(y.shape[0])})
    dW = jax.ShapeDtypeStruct((int(w0.shape[0]), int(y.shape[1])),
                              jnp.dtype(jnp.float32))
    for rows in sizes:
        x = jax.ShapeDtypeStruct((rows, w0.shape[0]),
                                 jnp.dtype(tr.compute))
        for mode, use_mask, key in (("train", True, tr.key),
                                    ("eval", False, None)):
            cands.append(Candidate(
                slot=f"head_fwd_block:{rows}:{mode}",
                fn=(lambda xx, ww, kk, u=use_mask: _head_fwd_block(
                    xx, ww, rate, kk, u)),
                args=(x, w0, key), observed=False,
                roles=("data", "params", "other"),
                aot=(lambda xx=x, kk=key, u=use_mask:
                     _head_fwd_block.lower(
                         xx, w0, rate, kk, u).compile())))
        cands.append(Candidate(
            slot=f"head_wgrad_block:{rows}",
            fn=(lambda dw, xx, dy, kk, r=rows: _head_wgrad_block(
                dw, xx, dy, 0, r, rate, kk, True)),
            args=(dW, x, y, tr.key), observed=False,
            roles=("params", "data", "data", "other"),
            aot=(lambda xx=x, r=rows: _head_wgrad_block.lower(
                dW, xx, y, 0, r, rate, tr.key, True).compile())))
    return cands


def _check_distinct(space: ProgramSpace) -> None:
    keys = [e.key for e in space.entries]
    if len(set(keys)) != len(keys):
        dup = sorted(k for k in set(keys) if keys.count(k) > 1)
        raise AssertionError(
            f"program-space enumeration for {space.config!r} produced "
            f"duplicate keys: {dup[:2]} — two slots would compile the "
            f"same program; the enumeration (or a slot) is wrong")


# --------------------------------------------------------------- rules

def check_compile_explosion(space: ProgramSpace,
                            budget: Optional[int]) -> List[Finding]:
    """[compile-explosion] see module docstring.  ``budget`` is the
    baselined bound (``program_budget`` in scripts/lint_baseline.json,
    shrink-only); None means no bound is recorded yet — the CLI notes
    it and ``--update-baseline`` initializes it."""
    if budget is None or space.program_count <= budget:
        return []
    return [Finding(
        "compile-explosion", f"programspace:{space.config}",
        f"{space.program_count} distinct XLA programs exceed the "
        f"baselined bound {budget} (modeled compile "
        f"{space.modeled_compile_ms() / 1e3:.1f}s) — a new compiled-"
        f"program shape entered this config; consolidate the shape "
        f"(quantize/uniform-scan) or ratchet deliberately by "
        f"hand-editing program_budget",
        key="over-budget",
        detail={"programs": space.program_count, "budget": budget,
                "slots": [e.slot for e in space.entries]})]


def _drift_dims(a: ProgramEntry, b: ProgramEntry, nm: int,
                em: int) -> Optional[List[Tuple[int, int]]]:
    """The differing dims when ``a`` and ``b`` differ ONLY by
    dimensions that snap to the same node- or edge-multiple; None when
    they differ structurally (different programs for real reasons) or
    not at all."""
    if len(a.leaves) != len(b.leaves):
        return None
    diffs: List[Tuple[int, int]] = []
    for (d1, s1, sp1), (d2, s2, sp2) in zip(a.leaves, b.leaves):
        if d1 != d2 or sp1 != sp2 or len(s1) != len(s2):
            return None
        for x, y in zip(s1, s2):
            if x == y:
                continue
            node_tie = _round_up(x, nm) == _round_up(y, nm)
            # the edge-grid snap only counts as drift evidence when
            # the pair is not ALREADY on the node grid: two distinct
            # node-quantized dims (e.g. padded row counts 8 vs 120,
            # or hidden widths that are 8-multiples) land in the same
            # 128-window without any shape having leaked — flagging
            # them would be an unclearable finding, since there is
            # nothing left to quantize
            edge_tie = (_round_up(x, em) == _round_up(y, em)
                        and not (x % nm == 0 and y % nm == 0))
            if node_tie or edge_tie:
                diffs.append((x, y))
            else:
                return None
    return diffs or None


def check_cache_key_drift(space: ProgramSpace) -> List[Finding]:
    """[cache-key-drift] see module docstring.  Aux per-block
    programs (``observed=False`` — the streamed head's
    per-block-shape jit variants) are exempt on both sides of a pair:
    a ragged tail block legitimately differs from the uniform blocks
    by exactly a row count, and block sizes are not partition shapes —
    quantize_plan_shapes cannot (and should not) snap them, so
    flagging the pair would be a guaranteed false positive the gate
    could never clear."""
    out: List[Finding] = []
    es = [e for e in space.entries if e.observed]
    for i in range(len(es)):
        for j in range(i + 1, len(es)):
            diffs = _drift_dims(es[i], es[j], space.node_multiple,
                                space.edge_multiple)
            if diffs is None:
                continue
            ex = ", ".join(f"{x} vs {y}" for x, y in diffs[:3])
            out.append(Finding(
                "cache-key-drift", f"programspace:{space.config}",
                f"program keys of {es[i].slot!r} and {es[j].slot!r} "
                f"differ only by unquantized dimensions ({ex}) that "
                f"snap to the same node/edge multiple "
                f"({space.node_multiple}/{space.edge_multiple}) — an "
                f"unquantized shape leaked into one slot, and every "
                f"rebuild of it at a nearby size will miss the "
                f"persistent compile cache; route the shape through "
                f"core/partition.quantize_plan_shapes",
                key=f"drift|{es[i].slot}|{es[j].slot}"))
    return out


# --------------------------------------------------------------- stage

def audit_program_space(select: Optional[List[str]] = None,
                        program_budget: Optional[Dict[str, int]] = None,
                        extras: Optional[Dict[str, Any]] = None
                        ) -> List[Finding]:
    """Run the auditor over every rig config the backend can host.
    Emits one ``programspace`` event per config; when ``extras`` is a
    dict, appends the report records under ``extras['programspace']``
    (the CLI's budget print + ``--json`` payload)."""
    import jax

    budget = program_budget or {}
    findings: List[Finding] = []
    ds = None
    for name, spec in rig_configs().items():
        if rig_required_devices(spec) > len(jax.devices()):
            continue
        if ds is None:   # one synthetic rig dataset for every config
            ds = build_rig_dataset()
        space = enumerate_programs(spec, dataset=ds)
        rep = space.report(budget=budget.get(name))
        rep["keys"] = [e.key for e in space.entries]
        emit("programspace",
             f"program space {name}: {rep['programs']} programs "
             f"(modeled compile {rep['modeled_compile_ms'] / 1e3:.1f}s"
             f", baseline {rep['budget']})",
             console=False,
             **{k: v for k, v in rep.items() if k != "keys"})
        if extras is not None:
            extras.setdefault("programspace", []).append(rep)
        if select is None or "compile-explosion" in select:
            findings.extend(
                check_compile_explosion(space, budget.get(name)))
        if select is None or "cache-key-drift" in select:
            findings.extend(check_cache_key_drift(space))
    return findings
