"""Sharding & replication auditor — roc-lint level seven.

The ROADMAP's top open item is the ``(parts, model)`` 2-D mesh: today
every layer materializes full-width ``[V_p, F]`` activations and
replicates all parameters, so the F axis is dead parallelism.  In the
GSPMD/pjit lineage that refactor will follow, shardings are
*propagated* — which means a single unconstrained op silently
re-gathers to full width and the compiled program wastes the mesh
without any test failing.  This level makes that class of silent
regression a ratcheted static gate BEFORE the refactor lands, the
same contract PR 3/6/12 applied to donation bugs, compile explosions,
and concurrency races.

The auditor walks the SAME :class:`~.programspace.Candidate` records
the program-space auditor enumerates (both trainers' step jaxprs, the
streamed-head block programs, the serve predictor's bucket programs),
seeds per-dimension mesh-axis specs on the candidate's inputs, and
abstractly propagates them through every eqn of the traced jaxpr —
no compilation, no chip time.  Three products:

- a per-step **replication ledger**: for every large input buffer
  (params, opt state, activations/data, edge/halo tables) which mesh
  axes it is split over, which it is replicated over, and the
  per-device bytes implied — checked against ``core/memory.py``'s
  plan the way ``hlo_lint`` checks bytes-accessed;
- ratcheted **rules** (shrink-only baseline/pragma contract):

  - ``replication-budget`` — the ledger's total replicated bytes per
    step on the canonical candidate mesh vs the ratcheted
    ``replication_budget`` in ``scripts/lint_baseline.json`` (the
    2-D-mesh analogue of PR 6's ``program_budget``: a PR that adds a
    replicated buffer fails here, and F-sharding work ratchets the
    bound down); plus a loose ledger-vs-plan excess check;
  - ``full-width-materialization`` — ops whose abstract-eval output
    is unsplit along a sharded-input axis (the implicit re-gather);
  - ``sharding-mismatch`` — jit in/out shardings or
    ``with_sharding_constraint``s that force an implicit
    all-gather/reshard on the hot path;
  - ``donation-under-sharding`` — donated buffers whose donor/donee
    shardings differ, silently voiding the aliasing the PR-3
    donation fixes bought;

- a **mesh-portability report**: the same propagation run against
  *abstract candidate meshes* — the feature dims seeded over the
  future ``model`` axis — enumerating every ``(parts, model)`` shape
  of the 8-virtual-device rig (1x8, 2x4, 4x2, 8x1): which ops are
  already mesh-agnostic, which sites would pin the F axis replicated
  (op, layer, bytes), and the modeled per-device HBM at each shape
  (``core/memory.per_axis_plan_bytes``).  Emitted as ``sharding``
  events and rendered by ``python -m roc_tpu.report --sharding`` —
  the 2-D-mesh PR starts from a machine-checked worklist instead of
  a hunch.

Live-mesh semantics vs simulation: findings come from the LIVE rig
semantics (the real 1-D parts mesh, plus any ``sharding_constraint``
/ jit sharding the code actually carries — today none, so the
baseline is EMPTY and stays so until the 2-D work begins, exactly
like the compile-explosion ratchet before a new program shape).  The
``model``-axis seeding is confined to the portability REPORT, whose
sites are a migration worklist, not regressions.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import (Any, Callable, Dict, Iterable, List, Optional,
                    Sequence, Set, Tuple)

from ..obs.events import emit
from ..parallel import (MODEL_AXIS, PARTS_AXIS, candidate_mesh_shapes,
                        mesh_axes, model_shard_spec)
from .findings import Finding

SHARDING_RULES = ("replication-budget", "full-width-materialization",
                  "sharding-mismatch", "donation-under-sharding")

# the candidate mesh the replication ratchet is measured on: the
# middle (parts, model) factorization of the 8-virtual-device rig —
# big enough on both axes that "replicated over model" and
# "replicated over parts" both cost real bytes
CANONICAL_SHAPE = (2, 4)

# ledger-vs-plan excess factor (the hlo-bytes-model analogue):
# deliberately loose — the ledger counts live input buffers, the plan
# estimates peak residency; only order-of-magnitude disagreement
# indicates the step holds far more than the plan modeled
PLAN_EXCESS_FACTOR = 4.0

# buffers below this never enter the ledger (rng keys, scalars, tiny
# metadata) — they are noise at every scale the rules care about
LEDGER_MIN_BYTES = 1024

# a "full-width" site must be at least the per-device activation
# block to report: elems >= V*F / total mesh devices

Spec = Tuple[Optional[str], ...]


def _rep(rank: int) -> Spec:
    return (None,) * rank


@dataclass
class Site:
    """One propagation incident: a place where a mesh-axis split dies
    (``full-width`` / ``unknown-op`` / ``boundary``) or two shardings
    disagree (``reshard``)."""

    kind: str
    op: str
    shape: Tuple[int, ...]
    dtype: str
    lost: Tuple[str, ...]
    layer: int
    src: str

    @property
    def elems(self) -> int:
        n = 1
        for d in self.shape:
            n *= int(d)
        return n

    def bytes(self, itemsize: Optional[int] = None) -> int:
        if itemsize is None:
            try:
                import numpy as np
                itemsize = int(np.dtype(self.dtype).itemsize)
            except TypeError:
                itemsize = 4
        return self.elems * itemsize

    @property
    def key(self) -> str:
        return (f"{self.kind}|{self.op}|{self.dtype}"
                f"{list(self.shape)}|{','.join(self.lost)}")

    def record(self, shapes: Sequence[Tuple[int, int]],
               has_vertex_dim: bool) -> Dict[str, Any]:
        """The report/JSON form, with the modeled per-device bytes of
        the materialized tensor at each candidate mesh shape: once
        the split dies, the tensor is full along the lost axis — only
        the surviving vertex split still divides it."""
        per_shape = {}
        for p, m in shapes:
            div = p if has_vertex_dim else 1
            per_shape[f"{p}x{m}"] = self.bytes() // max(div, 1)
        return {"kind": self.kind, "op": self.op,
                "shape": list(self.shape), "dtype": self.dtype,
                "lost": list(self.lost), "layer": self.layer,
                "src": self.src, "bytes": self.bytes(),
                "per_device_bytes": per_shape}


def _pspec_names(pspec) -> Dict[int, Tuple[str, ...]]:
    """A shard_map eqn's PartitionSpec as {dim: mesh axis names}."""
    out: Dict[int, Tuple[str, ...]] = {}
    for d, entry in enumerate(pspec):
        if entry is not None:
            out[d] = entry if isinstance(entry, tuple) else (entry,)
    return out


def _src_of(eqn) -> str:
    """Best-effort ``file:line`` of the user frame that traced this
    eqn — informational only (fingerprints never embed it).  Frames
    inside the analysis package are skipped: the auditor's own
    ``make_jaxpr`` call is never the interesting site."""
    try:
        from jax._src import source_info_util
        for frame in source_info_util.user_frames(eqn.source_info):
            fname = str(frame.file_name).replace("\\", "/")
            # only frames of the audited tree count, and never the
            # audit/report entry points themselves — an eqn created
            # by jax machinery with no library frame (the shard_map
            # boundary) reports no site rather than a wrong one
            if ("/roc_tpu/" not in fname or "/analysis/" in fname
                    or fname.endswith("/report.py")):
                continue
            return f"{fname.rsplit('/', 1)[-1]}:{frame.start_line}"
    except Exception:  # noqa: BLE001 - private API, best effort
        pass
    return ""


# ------------------------------------------------------------ engine

# shape-preserving (broadcast-free at the jaxpr level — jax inserts
# explicit broadcast_in_dim) n-ary ops: output spec = join of inputs
_ELEMENTWISE = {
    "add", "sub", "mul", "div", "rem", "pow", "atan2", "max", "min",
    "and", "or", "xor", "not", "neg", "sign", "floor", "ceil",
    "round", "exp", "exp2", "expm1", "log", "log1p", "sqrt", "rsqrt",
    "cbrt", "logistic", "tanh", "tan", "sin", "cos", "asin", "acos",
    "atan", "sinh", "cosh", "asinh", "acosh", "atanh", "erf", "erfc",
    "erf_inv", "abs", "convert_element_type", "bitcast_convert_type",
    "is_finite", "eq", "ne", "ge", "gt", "le", "lt", "select_n",
    "clamp", "nextafter", "real", "imag", "conj", "square",
    "reciprocal", "integer_pow", "shift_left",
    "shift_right_logical", "shift_right_arithmetic",
    "population_count", "clz", "copy", "stop_gradient",
    "threefry2x32", "random_bits", "random_wrap", "random_unwrap",
    "random_fold_in", "random_seed", "random_clone", "erf_inv",
}

# spec-transparent containers: propagate into the sub-jaxpr with
# end-aligned invar mapping (handles cond's leading index operand and
# custom_vjp's nondiff prefixes), outputs end-aligned back
_CONTAINER = {"jit", "closed_call", "core_call", "call", "remat",
              "remat2", "checkpoint", "custom_jvp_call",
              "custom_vjp_call", "custom_jvp_call_jaxpr",
              "custom_vjp_call_jaxpr", "custom_lin"}

# value-preserving collectives: the spec rides through unchanged
_SPEC_KEEP_COLLECTIVES = {"psum", "pmax", "pmin", "ppermute",
                          "psum_invariant", "pbroadcast"}

# known ops whose outputs we simply stop tracking, WITHOUT charging a
# full-width site: index/bookkeeping ops whose outputs are never
# activation-scale in this tree, or ops jax lowers around the hot
# path (rng plumbing, device placement)
_QUIET = {"iota", "rng_bit_generator", "axis_index", "device_put",
          "copy_p", "create_token", "eq_to", "platform_index",
          "top_k", "approx_top_k", "reduce_precision", "nan_to_num",
          "squeeze_shard", "dimension_size"}


class Propagator:
    """Abstract sharding-spec propagation over one ClosedJaxpr.

    ``axis_sizes`` maps mesh-axis name -> size (axes of size 1 are
    still tracked — structure, not arithmetic).  ``scale_elems`` is
    the reporting floor for materialization sites (the per-device
    activation block); spec deaths below it are tracked but not
    reported.  Incidents land in ``self.sites``; per-op preservation
    stats in ``self.ops_total`` / ``self.ops_agnostic``.
    """

    def __init__(self, axis_sizes: Dict[str, int], scale_elems: int,
                 record: bool = True):
        self.axis_sizes = dict(axis_sizes)
        self.scale_elems = max(int(scale_elems), 1)
        self.record = record
        self.sites: List[Site] = []
        self.ops_total = 0
        self.ops_agnostic = 0
        self.layer = 0
        self._site_keys: Set[str] = set()
        # distinct large intermediates seen during the walk — the
        # "activations" rows of the replication ledger: (shape,
        # dtype, spec, inside-shard_map) -> occurrence count
        self.acts: Dict[Tuple, int] = {}
        self._sm_depth = 0

    # ---- bookkeeping

    def _note(self, kind: str, eqn, aval, lost: Iterable[str]) -> None:
        lost = tuple(sorted(set(lost)))
        if not lost or not self.record:
            return
        shape = tuple(int(d) for d in getattr(aval, "shape", ()))
        n = 1
        for d in shape:
            n *= d
        if n < self.scale_elems:
            return
        site = Site(kind=kind, op=eqn.primitive.name, shape=shape,
                    dtype=str(getattr(aval, "dtype", "?")),
                    lost=lost, layer=self.layer, src=_src_of(eqn))
        if site.key not in self._site_keys:
            self._site_keys.add(site.key)
            self.sites.append(site)

    @staticmethod
    def _axes_of(specs: Iterable[Spec]) -> Set[str]:
        return {a for s in specs for a in s if a is not None}

    # ---- spec algebra

    def _join(self, eqn, specs: List[Spec], shapes: List[Tuple[int, ...]]
              ) -> Spec:
        """Trailing-aligned elementwise join: per dim take the agreed
        split; a genuine conflict (two different axes on one dim) is a
        reshard site and resolves to None."""
        rank = max((len(s) for s in shapes), default=0)
        out: List[Optional[str]] = [None] * rank
        for spec, shape in zip(specs, shapes):
            off = rank - len(shape)
            for d, a in enumerate(spec):
                if a is None:
                    continue
                od = off + d
                if out[od] is None:
                    out[od] = a
                elif out[od] != a:
                    self._note("reshard", eqn,
                               eqn.outvars[0].aval, (a, out[od]))
                    out[od] = None
        return tuple(out)

    # ---- main walk

    def run(self, closed_jaxpr, in_specs: Sequence[Spec]
            ) -> List[Spec]:
        jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
        env: Dict[Any, Spec] = {}
        for v, s in zip(jaxpr.invars, in_specs):
            env[v] = tuple(s)
        for v in getattr(jaxpr, "constvars", ()):
            env[v] = _rep(len(getattr(v.aval, "shape", ())))
        self._walk(jaxpr, env)
        return [self._read(env, v) for v in jaxpr.outvars]

    def _read(self, env: Dict[Any, Spec], v) -> Spec:
        if hasattr(v, "val"):          # Literal
            return _rep(len(getattr(getattr(v, "aval", None),
                                    "shape", ())))
        return env.get(v, _rep(len(getattr(v.aval, "shape", ()))))

    def _write(self, env: Dict[Any, Spec], eqn,
               out_specs: Sequence[Optional[Spec]]) -> None:
        for v, s in zip(eqn.outvars, out_specs):
            aval = getattr(v, "aval", None)
            shape = tuple(int(d) for d in getattr(aval, "shape", ()))
            rank = len(shape)
            if s is None:
                s = _rep(rank)
            s = tuple(s)
            if len(s) != rank:      # defensive: never mis-rank a var
                s = _rep(rank)
            env[v] = s
            if self.record and eqn.primitive.name not in _CONTAINER \
                    and eqn.primitive.name != "shard_map":
                n = 1
                for d in shape:
                    n *= d
                if n >= self.scale_elems:
                    key = (shape, str(getattr(aval, "dtype", "?")),
                           s, self._sm_depth > 0)
                    self.acts[key] = self.acts.get(key, 0) + 1

    def _walk(self, jaxpr, env: Dict[Any, Spec]) -> None:
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "dot_general":
                out_aval = getattr(eqn.outvars[0], "aval", None)
                n = 1
                for d in getattr(out_aval, "shape", ()):
                    n *= int(d)
                if n >= self.scale_elems:
                    # activation-scale matmul = one layer boundary;
                    # sites report the count as their "layer"
                    self.layer += 1
            specs = [self._read(env, v) for v in eqn.invars]
            shapes = [tuple(int(d) for d in
                            getattr(getattr(v, "aval", None),
                                    "shape", ()))
                      for v in eqn.invars]
            had_split = bool(self._axes_of(specs))
            # containers (jit/scan/shard_map/...) are wrappers, not
            # ops: their BODIES are walked and counted, and a
            # shard_map boundary pin is already a reported site —
            # charging the wrapper eqn would double-book it
            wrapper = (eqn.primitive.name in _CONTAINER
                       or eqn.primitive.name in ("shard_map", "scan",
                                                 "while", "cond"))
            if not wrapper:
                self.ops_total += 1
            out = self._eqn(eqn, specs, shapes, env)
            self._write(env, eqn, out)
            if wrapper:
                continue
            if had_split:
                kept = self._axes_of(
                    [self._read(env, v) for v in eqn.outvars])
                # agnostic = the splits survived, or the op is a
                # legitimate consumer (reduction/contraction); an op
                # that KILLED a split any other way is the
                # would-replicate population
                if kept or self._consumes(eqn):
                    self.ops_agnostic += 1
            else:
                self.ops_agnostic += 1

    @staticmethod
    def _consumes(eqn) -> bool:
        """True for ops that legitimately consume a split (reductions
        over the split dim, contractions) — losing it there is not a
        portability defect."""
        return eqn.primitive.name in ("reduce_sum", "reduce_max",
                                      "reduce_min", "reduce_prod",
                                      "reduce_and", "reduce_or",
                                      "dot_general", "argmax",
                                      "argmin")

    # ---- per-primitive transfer rules

    def _eqn(self, eqn, specs: List[Spec],
             shapes: List[Tuple[int, ...]], env) -> List[Optional[Spec]]:
        name = eqn.primitive.name
        n_out = len(eqn.outvars)

        if name in _ELEMENTWISE:
            return [self._join(eqn, specs, shapes)] * n_out
        if name == "optimization_barrier":
            return list(specs)[:n_out] + [None] * (n_out - len(specs))
        if name == "dot_general":
            return [self._dot_general(eqn, specs, shapes)]
        if name == "broadcast_in_dim":
            return [self._broadcast(eqn, specs[0])]
        if name == "reshape":
            return [self._reshape(eqn, specs[0], shapes[0])]
        if name == "transpose":
            perm = eqn.params["permutation"]
            return [tuple(specs[0][p] for p in perm)]
        if name == "squeeze":
            drop = set(eqn.params.get("dimensions", ()))
            return [tuple(a for d, a in enumerate(specs[0])
                          if d not in drop)]
        if name == "expand_dims":
            add = set(eqn.params.get("dimensions", ()))
            out_rank = len(specs[0]) + len(add)
            it = iter(specs[0])
            return [tuple(None if d in add else next(it)
                          for d in range(out_rank))]
        if name in ("reduce_sum", "reduce_max", "reduce_min",
                    "reduce_prod", "reduce_and", "reduce_or",
                    "argmax", "argmin"):
            axes = set(eqn.params.get("axes", ()))
            return [tuple(a for d, a in enumerate(specs[0])
                          if d not in axes)] * n_out
        if name in ("cumsum", "cumprod", "cummax", "cummin",
                    "cumlogsumexp"):
            ax = eqn.params.get("axis", 0)
            out = list(specs[0])
            if out[ax] is not None:
                self._note("full-width", eqn, eqn.outvars[0].aval,
                           (out[ax],))
                out[ax] = None
            return [tuple(out)]
        if name == "slice":
            return [self._slice(eqn, specs[0], shapes[0])]
        if name == "dynamic_slice":
            return [self._dynamic_slice(eqn, specs[0], shapes[0])]
        if name == "dynamic_update_slice":
            return [self._dus(eqn, specs, shapes)]
        if name == "gather":
            return [self._gather(eqn, specs, shapes)]
        if name.startswith("scatter"):
            return [self._scatter(eqn, specs, shapes)]
        if name == "concatenate":
            dim = eqn.params["dimension"]
            joined = list(self._join(eqn, specs, shapes))
            if dim < len(joined):
                joined[dim] = None
            return [tuple(joined)]
        if name == "pad":
            cfg = eqn.params.get("padding_config", ())
            out = list(specs[0]) + [None] * (len(cfg) - len(specs[0]))
            for d, (lo, hi, interior) in enumerate(cfg):
                if lo or hi or interior:
                    out[d] = None
            return [tuple(out)]
        if name in ("sort",):
            dim = eqn.params.get("dimension", -1)
            outs = []
            for s in specs[:n_out]:
                o = list(s)
                if o and o[dim] is not None:
                    self._note("full-width", eqn,
                               eqn.outvars[0].aval, (o[dim],))
                if o:
                    o[dim] = None
                outs.append(tuple(o))
            return outs + [None] * (n_out - len(outs))
        if name == "rev":
            return [specs[0]]
        if name == "split":
            ax = eqn.params.get("axis", 0)
            out = list(specs[0])
            if ax < len(out):
                out[ax] = None
            return [tuple(out)] * n_out
        if name == "all_gather":
            dim = eqn.params.get("all_gather_dimension", 0)
            out = list(specs[0])
            ax = eqn.params.get("axis_name")
            axes = ax if isinstance(ax, tuple) else (ax,)
            out = [None if a in axes else a for a in out]
            if dim < len(out):
                out[dim] = None
            return [tuple(out)] * n_out
        if name in _SPEC_KEEP_COLLECTIVES:
            return list(specs)[:n_out] + [None] * (n_out - len(specs))
        if name == "all_to_all":
            return [None] * n_out
        if name == "sharding_constraint":
            return [self._constraint(eqn, specs[0])]
        if name == "shard_map":
            return self._shard_map(eqn, specs, shapes)
        if name == "scan":
            return self._scan(eqn, specs)
        if name == "while":
            return self._while(eqn, specs)
        if name == "cond":
            return self._cond(eqn, specs)
        if name in _CONTAINER:
            return self._container(eqn, specs)
        if name in _QUIET:
            return [None] * n_out
        # unknown primitive holding a split: the exact "single
        # unconstrained op" GSPMD failure mode — the split dies and
        # everything downstream re-gathers to full width
        if self._axes_of(specs):
            for v in eqn.outvars:
                self._note("unknown-op", eqn, v.aval,
                           self._axes_of(specs))
        return [None] * n_out

    def _dot_general(self, eqn, specs, shapes) -> Spec:
        ((lc, rc), (lb, rb)) = eqn.params["dimension_numbers"]
        ls, rs = specs[0], specs[1]
        lfree = [d for d in range(len(shapes[0]))
                 if d not in lc and d not in lb]
        rfree = [d for d in range(len(shapes[1]))
                 if d not in rc and d not in rb]
        out: List[Optional[str]] = []
        for dl, dr in zip(lb, rb):
            a = ls[dl] if ls[dl] is not None else rs[dr]
            out.append(a)
        out.extend(ls[d] for d in lfree)
        out.extend(rs[d] for d in rfree)
        # one axis shards at most one dim: first occurrence wins
        seen: Set[str] = set()
        for i, a in enumerate(out):
            if a is None:
                continue
            if a in seen:
                out[i] = None
            else:
                seen.add(a)
        return tuple(out)

    def _broadcast(self, eqn, spec: Spec) -> Spec:
        bd = eqn.params["broadcast_dimensions"]
        shape = eqn.params["shape"]
        in_shape = tuple(getattr(eqn.invars[0].aval, "shape", ()))
        out: List[Optional[str]] = [None] * len(shape)
        for i, od in enumerate(bd):
            if i < len(spec) and in_shape[i] == shape[od]:
                out[od] = spec[i]
        return tuple(out)

    def _reshape(self, eqn, spec: Spec,
                 in_shape: Tuple[int, ...]) -> Spec:
        out_shape = tuple(int(d) for d in eqn.params["new_sizes"])
        out: List[Optional[str]] = [None] * len(out_shape)
        # leading/trailing alignment: dims preserved verbatim keep
        # their spec; anything reshaped through the middle loses it
        i = 0
        while (i < len(in_shape) and i < len(out_shape)
               and in_shape[i] == out_shape[i]):
            if i < len(spec):
                out[i] = spec[i]
            i += 1
        j = 0
        while (j < len(in_shape) - i and j < len(out_shape) - i
               and in_shape[-1 - j] == out_shape[-1 - j]):
            out[len(out_shape) - 1 - j] = spec[len(in_shape) - 1 - j]
            j += 1
        # a merge whose OUTER (major) factor carried the split keeps
        # it on the merged dim (row-major shards stay contiguous)
        lost = {a for d, a in enumerate(spec)
                if a is not None and a not in out}
        for d, a in enumerate(spec):
            if a is None or a in out:
                continue
            if (d < len(in_shape) and i <= d
                    and i < len(out_shape)
                    and out_shape[i] % in_shape[d] == 0
                    and d == i):
                out[i] = a
                lost.discard(a)
        if lost:
            self._note("full-width", eqn, eqn.outvars[0].aval, lost)
        return tuple(out)

    def _slice(self, eqn, spec: Spec,
               in_shape: Tuple[int, ...]) -> Spec:
        starts = eqn.params["start_indices"]
        limits = eqn.params["limit_indices"]
        out = list(spec)
        for d, (s, l) in enumerate(zip(starts, limits)):
            if (l - s) != in_shape[d] and out[d] is not None:
                self._note("full-width", eqn, eqn.invars[0].aval,
                           (out[d],))
                out[d] = None
        return tuple(out)

    def _dynamic_slice(self, eqn, spec: Spec,
                       in_shape: Tuple[int, ...]) -> Spec:
        sizes = eqn.params["slice_sizes"]
        out = list(spec)
        for d, sz in enumerate(sizes):
            if sz != in_shape[d] and out[d] is not None:
                self._note("full-width", eqn, eqn.invars[0].aval,
                           (out[d],))
                out[d] = None
        return tuple(out)

    def _dus(self, eqn, specs, shapes) -> Spec:
        op, upd = specs[0], specs[1]
        out = list(op)
        for d in range(min(len(shapes[0]), len(shapes[1]))):
            if shapes[1][d] != shapes[0][d] and out[d] is not None:
                self._note("full-width", eqn, eqn.invars[0].aval,
                           (out[d],))
                out[d] = None
            elif out[d] is None and d < len(upd):
                out[d] = upd[d]
        return tuple(out)

    def _gather(self, eqn, specs, shapes) -> Spec:
        dn = eqn.params["dimension_numbers"]
        sizes = eqn.params["slice_sizes"]
        op_spec, op_shape = specs[0], shapes[0]
        out_rank = len(getattr(eqn.outvars[0].aval, "shape", ()))
        # indexing across a split dim re-gathers the operand
        for d in dn.start_index_map:
            if (d < len(op_spec) and op_spec[d] is not None
                    and sizes[d] != op_shape[d]):
                self._note("full-width", eqn, eqn.invars[0].aval,
                           (op_spec[d],))
        collapsed = set(dn.collapsed_slice_dims)
        window_ops = [d for d in range(len(op_shape))
                      if d not in collapsed]
        out: List[Optional[str]] = [None] * out_rank
        for i, od in enumerate(dn.offset_dims):
            if i < len(window_ops):
                src = window_ops[i]
                if (sizes[src] == op_shape[src]
                        and src < len(op_spec)):
                    out[od] = op_spec[src]
        return tuple(out)

    def _scatter(self, eqn, specs, shapes) -> Spec:
        dn = eqn.params["dimension_numbers"]
        op_spec = list(specs[0])
        upd_spec = specs[2] if len(specs) > 2 else _rep(0)
        for d in dn.scatter_dims_to_operand_dims:
            if d < len(op_spec) and op_spec[d] is not None:
                self._note("full-width", eqn, eqn.invars[0].aval,
                           (op_spec[d],))
                op_spec[d] = None
        inserted = set(dn.inserted_window_dims)
        window_ops = [d for d in range(len(shapes[0]))
                      if d not in inserted]
        for i, ud in enumerate(dn.update_window_dims):
            if i < len(window_ops) and ud < len(upd_spec):
                dst = window_ops[i]
                if op_spec[dst] is None:
                    op_spec[dst] = upd_spec[ud]
        return tuple(op_spec)

    def _constraint(self, eqn, spec: Spec) -> Spec:
        want = _named_sharding_spec(
            eqn.params.get("sharding"),
            len(getattr(eqn.outvars[0].aval, "shape", ())))
        if want is None:
            return spec
        for d, (have, w) in enumerate(zip(spec, want)):
            if have is not None and w != have:
                self._note("reshard", eqn, eqn.invars[0].aval,
                           (have,))
        return want

    def _shard_map(self, eqn, specs, shapes) -> List[Optional[Spec]]:
        body = eqn.params["jaxpr"]
        in_names = [_pspec_names(p) for p in eqn.params["in_specs"]]
        out_names = [_pspec_names(p) for p in eqn.params["out_specs"]]
        body_in: List[Spec] = []
        for i, (spec, names) in enumerate(zip(specs, in_names)):
            names = dict(names or {})
            consumed = {a for axes in names.values() for a in axes}
            inner = []
            for d, a in enumerate(spec):
                if a is None:
                    inner.append(None)
                elif a in (names.get(d) or ()):
                    inner.append(None)        # split consumed locally
                elif a in consumed:
                    inner.append(None)
                else:
                    # the boundary pins this dim replicated: entering
                    # forces an all-gather of the split axis
                    self._note("boundary", eqn,
                               getattr(eqn.invars[i], "aval", None),
                               (a,))
                    inner.append(None)
            body_in.append(tuple(inner))
        self._sm_depth += 1
        try:
            prop_out = self._sub(body, body_in)
        finally:
            self._sm_depth -= 1
        outs: List[Optional[Spec]] = []
        for i, v in enumerate(eqn.outvars):
            names = dict((out_names[i] if i < len(out_names)
                          else {}) or {})
            rank = len(getattr(v.aval, "shape", ()))
            spec = list(prop_out[i] if i < len(prop_out)
                        else _rep(rank))
            spec += [None] * (rank - len(spec))
            for d, axes in names.items():
                if axes and d < rank:
                    spec[d] = axes[0]
            outs.append(tuple(spec[:rank]))
        return outs

    def _scan(self, eqn, specs) -> List[Optional[Spec]]:
        body = eqn.params["jaxpr"]
        nc = eqn.params.get("num_consts", 0)
        ncar = eqn.params.get("num_carry", 0)
        consts, carry, xs = (specs[:nc], specs[nc:nc + ncar],
                             specs[nc + ncar:])
        xs_in: List[Spec] = []
        for s in xs:
            if s and s[0] is not None:
                # scanning over a split dim is a sequential
                # cross-shard walk — the split cannot survive
                self._note("full-width", eqn, eqn.outvars[0].aval
                           if eqn.outvars else None, (s[0],))
            xs_in.append(tuple(s[1:]))
        cur = list(carry)
        for _ in range(2):                      # carry fixpoint
            sub = Propagator(self.axis_sizes, self.scale_elems,
                             record=False)
            out = sub.run(body, list(consts) + cur + xs_in)
            new_carry = [tuple(a if a == b else None
                               for a, b in zip(c, o))
                         if len(c) == len(o) else _rep(len(c))
                         for c, o in zip(cur, out[:ncar])]
            if new_carry == cur:
                break
            cur = new_carry
        out = self._sub(body, list(consts) + cur + xs_in)
        outs: List[Optional[Spec]] = list(out[:ncar])
        for s in out[ncar:]:
            outs.append((None,) + tuple(s))
        return outs

    def _while(self, eqn, specs) -> List[Optional[Spec]]:
        body = eqn.params.get("body_jaxpr")
        cn = eqn.params.get("cond_nconsts", 0)
        bn = eqn.params.get("body_nconsts", 0)
        carry = list(specs[cn + bn:])
        consts = list(specs[cn:cn + bn])
        cur = carry
        for _ in range(2):
            sub = Propagator(self.axis_sizes, self.scale_elems,
                             record=False)
            out = sub.run(body, consts + cur)
            new = [tuple(a if a == b else None for a, b in zip(c, o))
                   if len(c) == len(o) else _rep(len(c))
                   for c, o in zip(cur, out)]
            if new == cur:
                break
            cur = new
        return self._sub(body, consts + cur)

    def _cond(self, eqn, specs) -> List[Optional[Spec]]:
        branches = eqn.params.get("branches", ())
        outs: Optional[List[Spec]] = None
        for br in branches:
            got = self._sub(br, specs[1:])
            if outs is None:
                outs = [tuple(s) for s in got]
            else:
                outs = [tuple(a if a == b else None
                              for a, b in zip(x, y))
                        if len(x) == len(y) else _rep(len(x))
                        for x, y in zip(outs, got)]
        return outs or [None] * len(eqn.outvars)

    def _container(self, eqn, specs) -> List[Optional[Spec]]:
        inner = None
        for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
            if key in eqn.params:
                inner = eqn.params[key]
                break
        if inner is None:
            return [None] * len(eqn.outvars)
        body = getattr(inner, "jaxpr", inner)
        n_in = len(body.invars)
        aligned = list(specs)[-n_in:] if n_in else []
        while len(aligned) < n_in:
            aligned.insert(0, _rep(len(getattr(
                body.invars[n_in - len(aligned) - 1].aval,
                "shape", ()))))
        got = self._sub(inner, aligned)
        n_out = len(eqn.outvars)
        got = got[-n_out:] if len(got) >= n_out else got
        return list(got) + [None] * (n_out - len(got))

    def _sub(self, closed_jaxpr, in_specs: Sequence[Spec]
             ) -> List[Spec]:
        """Propagate a sub-jaxpr sharing this propagator's site and
        op accounting."""
        jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
        env: Dict[Any, Spec] = {}
        fixed = []
        for v, s in zip(jaxpr.invars, in_specs):
            rank = len(getattr(v.aval, "shape", ()))
            s = tuple(s)
            fixed.append(s if len(s) == rank else _rep(rank))
        for v, s in zip(jaxpr.invars, fixed):
            env[v] = s
        for v in getattr(jaxpr, "constvars", ()):
            env[v] = _rep(len(getattr(v.aval, "shape", ())))
        self._walk(jaxpr, env)
        return [self._read(env, v) for v in jaxpr.outvars]


def _named_sharding_spec(sharding, rank: int) -> Optional[Spec]:
    """Our per-dim Spec from a jax NamedSharding(-ish) object; None
    when the sharding carries no named spec (unspecified/GSPMD)."""
    pspec = getattr(sharding, "spec", None)
    if pspec is None:
        return None
    out: List[Optional[str]] = []
    try:
        entries = tuple(pspec)
    except TypeError:
        return None
    for e in entries[:rank]:
        if e is None:
            out.append(None)
        elif isinstance(e, (tuple, list)):
            out.append(str(e[0]) if e else None)
        else:
            out.append(str(e))
    out += [None] * (rank - len(out))
    return tuple(out)


# ------------------------------------------------- seeding + ledger

@dataclass
class RigDims:
    """The semantic dimension vocabulary of one audited rig: which
    sizes mean "vertex axis" and which mean "feature axis" — the
    bridge between raw avals and mesh-axis seeds."""

    vertex_sizes: Set[int]
    feat_sizes: Set[int]
    parts_traced: int = 1        # stacked leading dim of dist data
    scale_elems: int = 1


def rig_dims(tr, ds) -> RigDims:
    """Derive the vocabulary from a built trainer + dataset: vertex
    sizes from the dataset/partition plan, feature sizes from the
    parameter matrices (class width excluded — the C axis stays
    replicated by design, it is F/H parallelism under audit)."""
    import jax
    V = int(ds.graph.num_nodes)
    C = int(ds.num_classes)
    vs = {V, V + 1}    # +1: dummy-row variants (propagation tables)
    parts = 1
    pg = getattr(tr, "pg", None)
    if pg is not None:
        parts = int(pg.num_parts)
        vs.update({int(pg.part_nodes),
                   int(parts * pg.part_nodes),
                   int(parts * pg.part_nodes + 1)})
    fh = getattr(tr, "feats_host", None)
    if fh is not None:
        vs.add(int(fh.shape[0]))
    feats: Set[int] = set()
    for leaf in jax.tree_util.tree_leaves(tr.params):
        shape = getattr(leaf, "shape", ())
        if len(shape) >= 1:
            feats.update(int(d) for d in shape)
    feats -= {C}
    feats = {d for d in feats if d >= 8}
    F = max(feats) if feats else 1
    return RigDims(vertex_sizes=vs, feat_sizes=feats,
                   parts_traced=parts,
                   scale_elems=max(V * F // 8, 1))


def seed_leaf(shape: Tuple[int, ...], role: str, dims: RigDims,
              model_axis: bool) -> Spec:
    """Per-dimension mesh-axis seed for one input buffer.

    Live semantics: only the dist rigs' stacked leading dim carries
    ``parts`` (the mesh that actually exists).  Portability
    simulation (``model_axis=True``) additionally seeds the LAST
    feature-sized dim of float buffers over ``model`` — the 2-D
    design's feature shards — matching at most one dim per axis."""
    spec: List[Optional[str]] = [None] * len(shape)
    if (dims.parts_traced > 1 and role in ("data", "tables")
            and shape and int(shape[0]) == dims.parts_traced):
        spec[0] = PARTS_AXIS
    if model_axis:
        for d in range(len(shape) - 1, -1, -1):
            if spec[d] is None and int(shape[d]) in dims.feat_sizes:
                spec[d] = MODEL_AXIS
                break
    return tuple(spec)


def _leaf_roles(cand) -> List[Tuple[Any, str]]:
    """(leaf, role) per flattened arg leaf, aligned with the traced
    jaxpr's invars (make_jaxpr flattens the same way)."""
    import jax
    out: List[Tuple[Any, str]] = []
    roles = cand.roles or ("other",) * len(cand.args)
    for arg, role in zip(cand.args, roles):
        for leaf in jax.tree_util.tree_leaves(arg):
            out.append((leaf, role))
    return out


def _leaf_bytes(leaf) -> int:
    import numpy as np
    shape = tuple(getattr(leaf, "shape", ()))
    n = 1
    for d in shape:
        n *= int(d)
    return n * int(np.dtype(getattr(leaf, "dtype", "float32")).itemsize)


def ledger_entries(cand, dims: RigDims,
                   shape: Tuple[int, int]) -> List[Dict[str, Any]]:
    """The replication ledger of one candidate program on one
    ``(parts, model)`` mesh shape, as it stands TODAY: the vertex
    axis is genuinely sharded (the partitioner/shard_map machinery
    exists), and params/opt-state/stream buffers with a
    ``model``-divisible dim are F-sharded at rest (the
    ``put_replicated``/jit-shardings path).  Graph data and the
    feature-less dispatch tables remain replicated over ``model`` —
    the permanent residents of that column.  Sorted largest-first."""
    parts, model = int(shape[0]), int(shape[1])
    out: List[Dict[str, Any]] = []
    for leaf, role in _leaf_roles(cand):
        lshape = tuple(int(d) for d in getattr(leaf, "shape", ()))
        nbytes = _leaf_bytes(leaf)
        if nbytes < LEDGER_MIN_BYTES:
            continue
        has_vertex = (any(d in dims.vertex_sizes for d in lshape)
                      or (dims.parts_traced > 1 and lshape
                          and lshape[0] == dims.parts_traced))
        split, replicated = [], []
        div = 1
        if parts > 1:
            if has_vertex and role in ("data", "tables"):
                split.append(PARTS_AXIS)
                div *= parts
            else:
                replicated.append(PARTS_AXIS)
        if model > 1:
            # params / opt moments / the streamed-head handoff are
            # model-sharded at rest when a dim divides; everything
            # else (graph data, dispatch tables) stays replicated
            mspec = (model_shard_spec(lshape, model)
                     if role in ("params", "opt_state", "stream")
                     else None)
            if mspec is not None:
                split.append(MODEL_AXIS)
                div *= model
            else:
                replicated.append(MODEL_AXIS)
        out.append({
            "role": role,
            "shape": list(lshape),
            "dtype": str(getattr(leaf, "dtype", "?")),
            "bytes": nbytes,
            "split": split,
            "replicated": replicated,
            "per_device_bytes": nbytes // div,
        })
    out.sort(key=lambda e: (-e["bytes"], e["role"], str(e["shape"])))
    return out


def activation_entries(acts: Dict[Tuple, int], dims: RigDims,
                       shape: Tuple[int, int]) -> List[Dict[str, Any]]:
    """Ledger rows for the large INTERMEDIATES the live propagation
    saw (distinct shape/dtype/spec) — the ``[V_p, F]`` activations
    the ROADMAP names.  A tensor living inside a shard_map body is
    per-shard by construction (split over parts); everything is
    replicated over ``model`` today, same convention as the input
    rows."""
    import numpy as np
    parts, model = int(shape[0]), int(shape[1])
    out: List[Dict[str, Any]] = []
    for (tshape, dtype, spec, in_sm), count in acts.items():
        try:
            itemsize = int(np.dtype(dtype).itemsize)
        except TypeError:
            itemsize = 4
        n = 1
        for d in tshape:
            n *= int(d)
        nbytes = n * itemsize
        if nbytes < LEDGER_MIN_BYTES:
            continue
        has_vertex = any(d in dims.vertex_sizes for d in tshape)
        split, replicated = [], []
        div = 1
        if parts > 1:
            if in_sm or has_vertex:
                split.append(PARTS_AXIS)
                div *= parts
            else:
                replicated.append(PARTS_AXIS)
        if model > 1:
            replicated.append(MODEL_AXIS)
        out.append({
            "role": "activations", "shape": list(tshape),
            "dtype": dtype, "bytes": nbytes, "count": count,
            "split": split, "replicated": replicated,
            "per_device_bytes": nbytes // div,
        })
    return out


def union_ledger(per_cand: List[List[Dict[str, Any]]]
                 ) -> List[Dict[str, Any]]:
    """One ledger for the whole step lifecycle: candidates share
    buffers (params appear in train AND eval), so distinct
    ``(role, shape, dtype)`` triples are counted once, largest
    first."""
    seen: Set[Tuple] = set()
    out: List[Dict[str, Any]] = []
    for entries in per_cand:
        for e in entries:
            key = (e["role"], tuple(e["shape"]), e["dtype"],
                   tuple(e["split"]), tuple(e["replicated"]))
            if key in seen:
                continue
            seen.add(key)
            out.append(e)
    out.sort(key=lambda e: (-e["bytes"], e["role"], str(e["shape"])))
    return out


def replicated_bytes(entries: List[Dict[str, Any]]) -> int:
    """The ratchet quantity: per-device bytes of every ledger buffer
    replicated over at least one >1 mesh axis — the bytes the 2-D
    mesh exists to reclaim.  Static shapes only, so the number is
    bit-reproducible across runs."""
    return sum(e["per_device_bytes"] for e in entries
               if e["replicated"])


# ------------------------------------------------------------- rules

def check_replication_budget(config: str, measured: int,
                             budget: Optional[int]) -> List[Finding]:
    """[replication-budget] the ledger's replicated bytes per step on
    the canonical candidate mesh exceed the baselined bound
    (``replication_budget`` in scripts/lint_baseline.json,
    shrink-only).  None = no bound recorded yet — the CLI notes it
    and ``--update-baseline`` initializes it."""
    if budget is None or measured <= budget:
        return []
    return [Finding(
        "replication-budget", f"sharding:{config}",
        f"{measured} replicated bytes/step on the "
        f"{CANONICAL_SHAPE[0]}x{CANONICAL_SHAPE[1]} candidate mesh "
        f"exceed the baselined bound {budget} — a new replicated "
        f"buffer entered this config; shard it (or ratchet "
        f"deliberately by hand-editing replication_budget)",
        key="over-budget",
        detail={"replicated_bytes": measured, "budget": budget})]


def check_plan_excess(config: str, ledger_per_device: int,
                      plan_bytes: Optional[int],
                      factor: float = PLAN_EXCESS_FACTOR
                      ) -> List[Finding]:
    """[replication-budget] (key=plan-excess) the ledger's per-device
    residency exceeds ``factor`` x the core/memory.py plan estimate —
    the step holds far more live bytes than the plan modeled, the
    ledger analogue of hlo-bytes-model."""
    if not plan_bytes or ledger_per_device <= factor * plan_bytes:
        return []
    return [Finding(
        "replication-budget", f"sharding:{config}",
        f"ledger per-device bytes {ledger_per_device} exceed "
        f"{factor:g}x the core/memory.py plan estimate "
        f"({plan_bytes} B) — the step's resident buffers blew past "
        f"the plan",
        key="plan-excess",
        detail={"ledger_per_device": ledger_per_device,
                "plan_bytes": plan_bytes, "factor": factor})]


def findings_from_sites(config: str, slot: str,
                        sites: List[Site]) -> List[Finding]:
    """Map live-semantics propagation incidents to findings:
    full-width/unknown-op/boundary -> full-width-materialization,
    reshard -> sharding-mismatch."""
    out: List[Finding] = []
    unit = f"sharding:{config}:{slot}"
    for s in sites:
        if s.kind == "reshard":
            out.append(Finding(
                "sharding-mismatch", unit,
                f"{s.op} forces an implicit reshard of "
                f"{s.dtype}{list(s.shape)} (axes {', '.join(s.lost)} "
                f"disagree) on the hot path"
                + (f" [{s.src}]" if s.src else ""),
                key=s.key))
        else:
            out.append(Finding(
                "full-width-materialization", unit,
                f"{s.op} loses the {'/'.join(s.lost)} split of "
                f"{s.dtype}{list(s.shape)} (layer {s.layer}) — the "
                f"output re-gathers to full width"
                + (f" [{s.src}]" if s.src else ""),
                key=s.key))
    return out


def check_donation(config: str, cand, in_specs: List[Spec],
                   out_specs: List[Spec], jaxpr) -> List[Finding]:
    """[donation-under-sharding] a donated input whose matching
    output carries a different propagated sharding: XLA only aliases
    buffers with identical layouts, so the donation silently degrades
    to a copy — doubling residency exactly where the donation fixes
    (PR 3) reclaimed it."""
    import jax
    out: List[Finding] = []
    if not cand.donate:
        return out
    flat_specs: List[Tuple[Any, Spec, int]] = []   # (leaf, spec, arg)
    idx = 0
    for ai, arg in enumerate(cand.args):
        for leaf in jax.tree_util.tree_leaves(arg):
            flat_specs.append((leaf, in_specs[idx], ai))
            idx += 1
    out_sigs = []
    for v, spec in zip(jaxpr.jaxpr.outvars, out_specs):
        a = getattr(v, "aval", None)
        if a is not None:
            out_sigs.append((tuple(a.shape), str(a.dtype), spec))
    for leaf, spec, ai in flat_specs:
        if ai not in cand.donate:
            continue
        shape = tuple(getattr(leaf, "shape", ()))
        dtype = str(getattr(leaf, "dtype", "?"))
        if _leaf_bytes(leaf) < LEDGER_MIN_BYTES:
            continue
        matches = [s for (sh, dt, s) in out_sigs
                   if sh == shape and dt == dtype]
        if not matches or any(tuple(m) == tuple(spec)
                              for m in matches):
            continue
        out.append(Finding(
            "donation-under-sharding", f"sharding:{config}:{cand.slot}",
            f"donated arg {ai} ({dtype}{list(shape)}, spec "
            f"{list(spec)}) only matches outputs with different "
            f"sharding ({[list(m) for m in matches[:2]]}) — the "
            f"donation degrades to a copy under sharding",
            key=f"donate|{ai}|{dtype}{list(shape)}"))
    return out


# -------------------------------------------------------- rig audit

def audit_candidate(config: str, cand, dims: RigDims,
                    select: Optional[List[str]]
                    ) -> Tuple[List[Finding], Dict[str, Any],
                               Dict[Tuple, int]]:
    """One candidate program: live-semantics findings, the
    portability record (model-axis simulation), and the live walk's
    large-intermediate census (the ledger's activation rows)."""
    import jax
    findings: List[Finding] = []
    jaxpr = jax.make_jaxpr(cand.fn)(*cand.args)
    axes = {PARTS_AXIS: dims.parts_traced, MODEL_AXIS: 1}

    live_in = [seed_leaf(tuple(getattr(leaf, "shape", ())), role,
                         dims, model_axis=False)
               for leaf, role in _leaf_roles(cand)]
    live = Propagator(axes, dims.scale_elems)
    live_out = live.run(jaxpr, live_in)
    if select is None or "full-width-materialization" in select \
            or "sharding-mismatch" in select:
        fs = findings_from_sites(config, cand.slot, live.sites)
        if select is not None:
            fs = [f for f in fs if f.rule in select]
        findings.extend(fs)
    if select is None or "donation-under-sharding" in select:
        findings.extend(check_donation(config, cand, live_in,
                                       live_out, jaxpr))

    sim_in = [seed_leaf(tuple(getattr(leaf, "shape", ())), role,
                        dims, model_axis=True)
              for leaf, role in _leaf_roles(cand)]
    sim = Propagator(mesh_axes(CANONICAL_SHAPE), dims.scale_elems)
    sim.run(jaxpr, sim_in)
    record = {
        "slot": cand.slot,
        "ops": sim.ops_total,
        "mesh_agnostic_ops": sim.ops_agnostic,
        "sites": [s.record(candidate_mesh_shapes(),
                           has_vertex_dim=any(
                               d in dims.vertex_sizes
                               for d in s.shape))
                  for s in sim.sites],
    }
    return findings, record, live.acts


def audit_rig(name: str, spec, tr, ds,
              budget: Optional[int],
              select: Optional[List[str]]
              ) -> Tuple[List[Finding], Dict[str, Any]]:
    from ..core.memory import per_axis_plan_bytes
    from .programspace import candidate_programs
    dims = rig_dims(tr, ds)
    findings: List[Finding] = []
    cands = candidate_programs(tr)
    slots: List[Dict[str, Any]] = []
    all_acts: Dict[Tuple, int] = {}
    for cand in cands:
        fs, rec, acts = audit_candidate(name, cand, dims, select)
        findings.extend(fs)
        slots.append(rec)
        for k, n in acts.items():
            all_acts[k] = all_acts.get(k, 0) + n

    # ONE ledger for the step lifecycle: distinct input buffers
    # across every candidate (params appear once, not per slot) plus
    # the distinct large intermediates the live walk saw
    entries = union_ledger(
        [ledger_entries(c, dims, CANONICAL_SHAPE) for c in cands]
        + [activation_entries(all_acts, dims, CANONICAL_SHAPE)])
    measured = replicated_bytes(entries)
    live_shape = (dims.parts_traced, 1)
    live_entries = union_ledger(
        [ledger_entries(c, dims, live_shape) for c in cands]
        + [activation_entries(all_acts, dims, live_shape)])
    ledger_per_device = sum(e["per_device_bytes"]
                            for e in live_entries)
    plan_bytes = getattr(tr, "_modeled_bytes", None)
    if select is None or "replication-budget" in select:
        findings.extend(check_replication_budget(name, measured,
                                                 budget))
        findings.extend(check_plan_excess(name, ledger_per_device,
                                          plan_bytes))

    # mesh-portability: modeled per-device HBM at every (parts,
    # model) shape of the rig, from the planner's per-axis model
    shapes = []
    for p, m in candidate_mesh_shapes():
        ax = per_axis_plan_bytes(
            int(ds.graph.num_nodes), int(ds.graph.num_edges),
            tr.model._ops, parts=p, model=m,
            halo=getattr(tr.config, "halo", "gather"),
            features=getattr(tr.config, "features", "hbm"),
            remat=bool(getattr(tr.config, "remat", False)))
        shapes.append({"parts": p, "model": m,
                       "per_device_bytes": ax["total"]["per_device"],
                       "components": {
                           k: {"per_device": v["per_device"],
                               "replicated": v.get("replicated", [])}
                           for k, v in ax.items() if k != "total"}})

    n_sites = sum(len(s["sites"]) for s in slots)
    report = {
        "config": name,
        "parts": dims.parts_traced,
        "canonical_shape": list(CANONICAL_SHAPE),
        "replicated_bytes": measured,
        "budget": budget,
        "ledger_per_device_bytes": ledger_per_device,
        "plan_bytes": plan_bytes,
        "ledger": entries[:16],
        "slots": slots,
        "full_width_sites": n_sites,
        "mesh_shapes": shapes,
    }
    if budget is not None:
        report["delta"] = measured - budget
    return findings, report


# ------------------------------------------------------------ stage

def audit_sharding(select: Optional[List[str]] = None,
                   replication_budget: Optional[Dict[str, int]] = None,
                   extras: Optional[Dict[str, Any]] = None
                   ) -> List[Finding]:
    """Level-seven entry point: audit every rig config the backend
    can host (the same registry the program-space auditor walks).
    Emits one ``sharding`` event per config; when ``extras`` is a
    dict, appends the report records under ``extras['sharding']``."""
    import jax

    budget = replication_budget or {}
    findings: List[Finding] = []
    ds = None
    from .programspace import build_rig_dataset, build_rig_trainer, \
        rig_configs, rig_required_devices
    for name, spec in rig_configs().items():
        if rig_required_devices(spec) > len(jax.devices()):
            continue
        if ds is None:
            ds = build_rig_dataset()
        tr = build_rig_trainer(spec, ds)
        fs, report = audit_rig(name, spec, tr, ds,
                               budget=budget.get(name),
                               select=select)
        findings.extend(fs)
        emit("sharding",
             f"sharding audit {name}: {report['replicated_bytes']} "
             f"replicated B/step on "
             f"{CANONICAL_SHAPE[0]}x{CANONICAL_SHAPE[1]} (baseline "
             f"{report['budget']}), {report['full_width_sites']} "
             f"full-width site(s) in the portability sim",
             console=False,
             **{k: v for k, v in report.items()
                if k not in ("ledger", "slots", "mesh_shapes")},
             sites=[s for slot in report["slots"]
                    for s in slot["sites"]],
             mesh_shapes=report["mesh_shapes"])
        if extras is not None:
            extras.setdefault("sharding", []).append(report)
    return findings
