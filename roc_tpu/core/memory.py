"""Memory placement policy: estimate per-device HBM and choose a plan.

The reference actively manages device-memory residency: a 4-slot
framebuffer cache sized from ``maxHidden`` with best-fit slot
assignment (``resourcemanager.cc:29-57``, ``load_task.cu:365-374``),
backed by zero-copy host memory for everything that doesn't fit
(``types.cu:22-32``).  The TPU analog is a *plan*, not a cache: XLA
owns HBM, so the policy's job is to pick, before compilation, which
combination of mechanisms keeps the step's peak footprint inside the
budget:

- ``halo``: one-shot ``all_gather`` (fast, materializes the global
  [V, H] feature matrix per device) vs the ``ppermute`` ring (O(V/P)
  peak, parallel/ring.py);
- ``features``: HBM-resident input features vs host-resident features
  streamed through the first layer (core/streaming.py — the direct
  analog of the reference's ZC->FB staging);
- ``remat``: compute each run of ops between two aggregations again in
  the backward instead of keeping its insides (one checkpoint a run,
  ``models/builder.py Model.apply``).

What a step keeps for its backward is read off the model's op list by
ONE rule for every family (:func:`op_residuals`): the plan charges what
this op list keeps, so a second ``linear`` reading a kept input, or a
``lerp``, costs what it costs.

:func:`choose_memory_plan` estimates the footprint of each viable
combination (cheapest-first) and returns the first that fits, so a
graph sized past the gather budget trains via ring or streaming with
no user flags — the reference needs no flags for its cache either.
The decision is echoed at trainer setup like the reference's config
print (``gnn.cc:48-60``).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

# Default usable fraction of physical HBM (XLA reserves workspace,
# and the estimate is deliberately coarse).
_USABLE = 0.85
_DEFAULT_HBM = 16 * 1024**3  # v5e physical per chip
_MASK_BYTES = 1              # a dropout / ReLU predicate, per element
_STAT_BYTES = 4              # fp32 by-products (softmax, attention)

# the model op kinds that aggregate over edges (obs/scopes.py AGG_KINDS:
# this module imports nothing, so the tuple is repeated); under remat
# they are the boundaries of the runs computed again
AGG_KINDS = ("scatter_gather", "fused_aggregate", "gat",
             "transformer_attention", "rel_aggregate", "soft_aggregate")


def op_residuals(i: int, op: Any, itemsize: int
                 ) -> List[Tuple[Tuple[str, int], int, int]]:
    """What model op ``i`` keeps from its forward for its backward, by
    the op's kind alone — the ONE rule the plan charges activations by,
    for every model family: ``[(key, width, bytes per element), ...]``.
    ``key`` names the array, so that an array two ops keep is charged
    once: ``("t", j)`` is model tensor ``j`` (op ``j``'s output, in the
    compute dtype), ``("m", i)`` a by-product of op ``i`` itself.

    | kind | keeps |
    | --- | --- |
    | ``linear`` | its input (for dW; dX needs W alone); its output too under a fused activation |
    | ``dropout`` (rate > 0) | the keep mask, a byte an element |
    | ``activation`` | its output (ReLU's sign, sigmoid's and ELU's value) |
    | ``add``, ``lerp``, ``indegree_norm`` | nothing: linear in their inputs with constants |
    | ``mul`` | both inputs; ``scale_add``: the scaled one (for d eps) |
    | ``scatter_gather`` SUM / AVG, ``fused_aggregate`` | nothing: the backward is the same sum over the cotangent (a fused ReLU keeps the output) |
    | ``scatter_gather`` MAX / MIN | input and output (where the max sat) |
    | ``gat`` | input, output and the fp32 row sums of the hand-written backward |
    | ``rel_linear``, ``root_linear`` | their input (for the dW of each relation / kind); a stacked input is charged at its own height (``row_scale``), the loss program's cut last layer (``Model.loss_cut``) at its cut ones |
    | ``rel_aggregate``, ``typed_input`` | nothing: the relation sum's backward is the pass over the transposed table, the assembly a concatenation |
    | ``batch_norm`` | its input (the hand-written backward computes ``xhat`` again from it) and two float32 ``[F]`` vectors, mean and ``1 / sqrt(var + eps)``, which weigh nothing a vertex row |
    | ``soft_aggregate`` | its input (``e`` is computed again from it and the kept ``[F]`` shift) and the float32 denominator a vertex row |
    | ``transformer_attention`` | its three inputs (``q``, the ``[k | v]`` table, ``r``: both backward passes recompute the scores from the first two, the gate reads the third) and, float32 a vertex row, the attention's output ``m`` (``K * d``), the row max and denominator (``2 K``) and the gate ``beta`` (1) |
    | ``layer_norm`` | its input and two float32 scalars a row (mean, ``1 / sqrt(var + eps)``) |
    """
    def t(j):
        return ("t", j)

    kind, attrs = op.kind, getattr(op, "attrs", None) or {}
    if kind in ("rel_linear", "root_linear"):
        return [(t(op.inputs[0]), attrs["in_dim"], itemsize)]
    if kind == "linear":
        out = [(t(op.inputs[0]), attrs.get("in_dim", op.dim), itemsize)]
        if attrs.get("activation", "none") != "none":
            out.append((t(i), op.dim, itemsize))
        return out
    if kind == "dropout":
        return ([(("m", i), op.dim, _MASK_BYTES)]
                if attrs.get("rate", 0) > 0 else [])
    if kind == "activation":
        return [(t(i), op.dim, itemsize)]
    if kind == "mul":
        return [(t(j), op.dim, itemsize) for j in op.inputs]
    if kind == "scale_add":
        return [(t(op.inputs[1]), op.dim, itemsize)]
    if kind == "fused_aggregate":
        return ([(t(i), op.dim, itemsize)]
                if attrs.get("activation", "none") != "none" else [])
    if kind == "scatter_gather":
        if attrs.get("aggr", "sum") in ("max", "min"):
            return [(t(op.inputs[0]), op.dim, itemsize),
                    (t(i), op.dim, itemsize)]
        return []
    if kind == "gat":
        return [(t(op.inputs[0]), op.dim, itemsize),
                (t(i), op.dim, itemsize), (("m", i), op.dim, _STAT_BYTES)]
    if kind == "batch_norm":
        return [(t(op.inputs[0]), op.dim, itemsize)]
    if kind == "soft_aggregate":
        return [(t(op.inputs[0]), op.dim, itemsize),
                (("m", i), op.dim, _STAT_BYTES)]
    if kind == "transformer_attention":
        heads, dh = attrs["heads"], attrs["head_width"]
        return [(t(op.inputs[0]), heads * dh, itemsize),
                (t(op.inputs[1]), 2 * heads * dh, itemsize),
                (t(op.inputs[2]), op.dim, itemsize),
                (("m", i), heads * dh + 2 * heads + 1, _STAT_BYTES)]
    if kind == "layer_norm":
        return [(t(op.inputs[0]), op.dim, itemsize),
                (("m", i), 2, _STAT_BYTES)]
    return []


def remat_segments(ops: Sequence[Any]) -> List[Tuple[int, int]]:
    """``[(lo, hi), ...]``: the maximal runs ``ops[lo:hi]`` of
    consecutive model ops that do not aggregate over edges — what
    ``Model.apply(remat=True)`` makes one checkpoint each,
    and what the plan charges a step under remat by.  The ONE home of
    the boundary rule."""
    out, lo = [], None
    for i in range(1, len(ops) + 1):
        inside = i < len(ops) and ops[i].kind not in AGG_KINDS
        if inside and lo is None:
            lo = i
        elif not inside and lo is not None:
            out.append((lo, i))
            lo = None
    return out


def saved_for_backward(ops: Sequence[Any], itemsize: int,
                       remat: bool = False
                       ) -> Tuple[List[Tuple[int, int, int]], int]:
    """``(kept, recompute_row_bytes)``: the arrays a train step holds
    from its forward into its backward, as ``[(op index, arrays, bytes
    per vertex row), ...]`` — one entry per op that is the first to
    keep an array, so a second ``linear`` reading a kept input, or a
    ``lerp``, adds nothing — and the bytes per vertex row the backward
    holds besides while it computes one run again (0 without remat).

    Without remat: every op's :func:`op_residuals`, and the loss's (the
    logits and their fp32 softmax).  Under remat
    (:func:`remat_segments`): what each run reads from outside itself,
    its dropout masks (the key does not pass the run's barrier, so XLA
    folds the second draw into the first and keeps the mask: a byte an
    element, and no random bits drawn twice), the aggregations' own
    residuals and the loss's; and, one run at a time, that run's other
    residuals — the largest is charged.  The input features (tensor 0)
    are the plan's ``features`` component and are not charged again.

    Not charged: the cotangent a ``linear``'s weight gradient waits
    for.  With memory to spare XLA puts those products off to the end
    of the backward (the TPU compiler's buffer assignment at 4 and 16
    layers: 2 bytes an element a layer more than this rule), under
    pressure it does not (the chip at 48 layers: PERF.md section 6,
    PR 33)."""
    seen = {("t", 0)}
    kept: List[Tuple[int, int, int]] = []

    def scaled(k, w, b):
        # an array as tall as the op that made it: a typed model's
        # stacked tensors hold ``row_scale`` rows a vertex (the input,
        # tensor 0, its feature rows alone); 1 everywhere else
        scale = (getattr(ops[k[1]], "attrs", None) or {}).get(
            "row_scale", 1)
        return w * b if scale == 1 else round(w * b * scale, 1)

    def charge(i, items):
        new = [(k, w, b) for k, w, b in dict.fromkeys(items)
               if k not in seen]
        seen.update(k for k, _, _ in new)
        if new:
            kept.append((i, len(new),
                         sum(scaled(k, w, b) for k, w, b in new)))

    runs = dict(remat_segments(ops)) if remat else {}
    inside = {k for lo, hi in runs.items() for k in range(lo, hi)}
    for i in range(1, len(ops)):
        if i in runs:
            charge(i, [(("t", j), ops[j].dim, itemsize)
                       for op in ops[i:runs[i]] for j in op.inputs
                       if j < i])
        if i not in inside or ops[i].kind == "dropout":
            charge(i, op_residuals(i, ops[i], itemsize))
    if len(ops) > 1:
        last = len(ops) - 1
        # the loss's fp32 softmax covers the rows that can carry a
        # label: all of them, or a typed model's kind 0
        # (``label_scale`` on its input op; gone from a cut op list,
        # whose last op is those rows already: ``row_scale``)
        labelled = (getattr(ops[0], "attrs", None) or {}).get(
            "label_scale", 1)
        charge(last, [(("t", last), ops[last].dim, itemsize),
                      (("m", last), ops[last].dim,
                       _STAT_BYTES * labelled)])
    recompute = 0
    for lo, hi in runs.items():
        items = dict.fromkeys(
            r for k in range(lo, hi)
            for r in op_residuals(k, ops[k], itemsize)
            if r[0] not in seen)
        recompute = max(recompute,
                        sum(scaled(k, w, b) for k, w, b in items))
    return kept, recompute


def param_elems(ops: Sequence[Any]) -> int:
    """Trainable scalars of the op list: every ``linear``'s matrix
    (and its bias where it has one), every ``gat``'s two attention
    vectors, every ``transformer_attention``'s gate vector, every
    ``scale_add``'s eps, every ``layer_norm``'s and ``batch_norm``'s
    scale and shift — a ``batch_norm``'s running statistics are state,
    not parameters: no gradient, no Adam moments, no compute copy, and
    8 bytes a channel that no plan needs; of a typed model the
    embedding tables' rows, a matrix a relation and a matrix and a bias
    a kind."""
    n = 0
    for op in ops:
        if op.kind == "typed_input":
            n += op.attrs["embed_rows"] * op.dim
        elif op.kind == "rel_linear":
            n += (op.attrs["n_rel"] * op.attrs["in_dim"]
                  * op.attrs["out_dim"])
        elif op.kind == "root_linear":
            n += op.attrs["n_kinds"] * (op.attrs["in_dim"] + 1) * op.dim
        elif op.kind == "linear":
            n += (op.attrs["in_dim"]
                  + bool(op.attrs.get("bias"))) * op.dim
        elif op.kind in ("batch_norm", "layer_norm"):
            n += 2 * op.dim
        elif op.kind == "transformer_attention":
            n += 3 * op.dim
        elif op.kind == "gat":
            n += 2 * op.dim
        elif op.kind == "scale_add":
            n += 1
    return n


def model_depth(ops: Sequence[Any]) -> Dict[str, int]:
    return {"aggregating_ops": sum(op.kind in AGG_KINDS for op in ops),
            "linear_ops": sum(op.kind == "linear" for op in ops)}


def charged_table_bytes(aggr_impl: str, uses_attention: bool,
                        uses_max_aggregation: bool,
                        a_budget_bytes: Optional[int]) -> int:
    """The impl-specific resident-table bytes the memory plan must
    charge on top of the generic ``E*4`` term — today the bdense
    A-table, whose worst case is exactly the planner's device-byte cap
    (``bdense_a_budget``).  ONE home for the rule (it used to live
    duplicated in ``modeled_step_bytes`` and the autopilot, round-5
    advisor): attention/MAX models never keep the table — their impl
    is rewritten away from bdense by ``resolve_attention_impl`` — and
    an uncapped budget is unmodelable (0 here; the occupancy echo is
    the warning there)."""
    keeps_bdense = (aggr_impl == "bdense"
                    and not uses_attention
                    and not uses_max_aggregation)
    return (a_budget_bytes or 0) if keeps_bdense else 0


def detect_hbm_bytes() -> int:
    """Per-device HBM budget: ``memory_stats()['bytes_limit']`` scaled
    by the usable fraction.  The CPU backend (tests, virtual-device
    rigs) reports no stats and plans against the v5e default so its
    plans match the chip's; an accelerator that does not report its
    limit is an error, not a 16 GiB guess."""
    import jax
    dev = jax.devices()[0]
    stats = dev.memory_stats()
    if dev.platform == "cpu" and not stats:
        return int(_DEFAULT_HBM * _USABLE)
    limit = int((stats or {}).get("bytes_limit", 0))
    if limit <= 0:
        raise RuntimeError(
            f"{dev.platform} device {dev.device_kind!r} reports no "
            f"memory_stats()['bytes_limit'] (got {stats!r}); pass "
            f"hbm_bytes explicitly")
    return int(limit * _USABLE)


@dataclass
class MemoryPlan:
    """A chosen residency/exchange configuration + its evidence."""
    halo: str            # "gather" | "ring"
    features: str        # "hbm" | "host"
    remat: bool
    fits: bool           # False = even the last-resort plan over budget
    est_bytes: int       # estimate for the chosen plan
    budget_bytes: int
    candidates: Dict[str, int]  # plan-name -> estimated bytes
    reason: str

    @property
    def name(self) -> str:
        return (f"halo={self.halo} features={self.features} "
                f"remat={self.remat}")

    def echo(self) -> str:
        """Human-readable decision line (the ``# `` console prefix is
        added by the event log's console sink)."""
        gib = 1024**3
        return (f"memory plan: {self.name} — est "
                f"{self.est_bytes / gib:.2f} GiB of "
                f"{self.budget_bytes / gib:.2f} GiB budget; {self.reason}")


def plan_components(num_nodes: int, num_edges: int,
                    ops: Sequence[Any], num_parts: int = 1,
                    dtype_bytes: int = 4, halo: str = "gather",
                    features: str = "hbm", remat: bool = False,
                    ring_padding: float = 1.7,
                    extra_table_bytes: int = 0,
                    param_bytes: int = 4,
                    scan_rows: int = 0, kept=None) -> Dict[str, int]:
    """Coarse per-device peak-HBM estimate for one train step, by
    component: ``params_opt`` (master parameters, Adam's two moments,
    the gradients, the compute-dtype copy), ``features``, ``tables``,
    ``activations`` (:func:`saved_for_backward`) and ``transient`` (the
    halo's gathered matrix or the ring's two buffers; a scan layout's
    chunk, ``scan_rows`` sub-rows of 8 gathered rows and their sum,
    ``core/ell.py scan_chunk_rows``; under remat the run being computed
    again).

    ``ops`` is the model's op list (``Model._ops``: ``kind``,
    ``inputs``, ``dim``, ``attrs``), first the input.  Deliberately
    simple — the policy needs ordering between plans and a slope in
    depth, not byte-exact numbers; of a layout's scratch it knows one
    chunk, not what XLA reserves beside it (PERF.md section 5).

    ``extra_table_bytes`` covers impl-specific resident tables the
    generic ``E*4`` term misses — today the bdense A-table, whose
    worst case is exactly ``bdense_a_budget`` (the planner's device-
    byte cap).  ``kept``: :func:`saved_for_backward`'s result where the
    caller has it already."""
    V_p = -(-num_nodes // num_parts)
    E_p = -(-num_edges // num_parts)
    b = dtype_bytes
    F = ops[0].dim

    def scale(op):
        return (getattr(op, "attrs", None) or {}).get("row_scale", 1)

    def held(op):
        # a softmax aggregation gathers a [V, 2F] table (numerator and
        # denominator side by side) and sums into as wide a carry
        return op.dim * (2 if op.kind == "soft_aggregate" else 1)

    # the widest array a step holds whole, in elements a vertex row (a
    # typed model's stacked tensors are taller than V)
    h_max = max(held(op) * scale(op) for op in ops)
    w = param_elems(ops)
    out = {"params_opt": w * (4 * param_bytes + b)}
    # input features: resident, or one streamed block + dY reuse (a
    # typed model's input holds the rows of the kinds that have any)
    out["features"] = int((V_p * scale(ops[0]) if features == "hbm"
                           else 65536) * F * b)
    # edge tables: ELL idx ~ E_p int32 (+ row positions)
    out["tables"] = E_p * 4 + V_p * 4 + extra_table_bytes
    if halo == "ring":
        out["tables"] += int(2 * E_p * 4 * ring_padding)  # src+dst flat
    kept, recompute = kept or saved_for_backward(ops, b, remat)
    out["activations"] = int(V_p * sum(row for _, _, row in kept))
    if features != "hbm":
        # the streamed head's insides never sit whole on the device
        head = {i for i, op in enumerate(ops[:3]) if i}
        out["activations"] -= V_p * sum(
            row for i, _, row in kept if i in head and ops[i].dim == F)
    # halo transient: the gathered global matrix vs two ring buffers
    out["transient"] = int((num_parts if halo == "gather" else 2)
                           * V_p * h_max * b + V_p * recompute
                           + scan_rows * 9 * max(held(op) for op in ops)
                           * b)
    return out


def estimate_plan_bytes(num_nodes: int, num_edges: int,
                        ops: Sequence[Any], **kw) -> int:
    """:func:`plan_components`, summed."""
    return sum(plan_components(num_nodes, num_edges, ops, **kw).values())


def describe_plan(num_nodes: int, num_edges: int, ops: Sequence[Any],
                  **kw) -> Dict[str, Any]:
    """The resolved plan for the run manifest's ``memory_plan``: the
    estimate by component, what each op was charged (``[op index, kind,
    arrays, bytes per vertex row]``), remat, and the model's depth."""
    remat = bool(kw.get("remat", False))
    kept, recompute = saved_for_backward(
        ops, kw.get("dtype_bytes", 4), remat)
    comps = plan_components(num_nodes, num_edges, ops,
                            kept=(kept, recompute), **kw)
    return {"est_bytes": sum(comps.values()), "components": comps,
            "saved": [[i, ops[i].kind, n, row] for i, n, row in kept],
            "saved_arrays": sum(n for _, n, _ in kept),
            "recompute_row_bytes": recompute, "remat": remat,
            "remat_runs": len(remat_segments(ops)) if remat else 0,
            **model_depth(ops)}


def per_axis_plan_bytes(num_nodes: int, num_edges: int,
                        ops: Sequence[Any], parts: int = 1,
                        model: int = 1, dtype_bytes: int = 4,
                        halo: str = "gather", features: str = "hbm",
                        remat: bool = False, ring_padding: float = 1.7
                        ) -> Dict[str, Dict[str, int]]:
    """Per-component, per-mesh-axis byte attribution of one train
    step on an abstract ``(parts, model)`` mesh — the planner-side
    half of the sharding auditor's replication ledger
    (analysis/sharding_lint.py) and the "modeled per-device HBM"
    column of the mesh-portability report.

    :func:`plan_components`' accounting (whose per-device numbers at
    ``parts`` partitions these are, times ``parts``), with each
    component reporting WHICH axes divide it: params/opt-state and
    activations split over ``model`` on their feature axis (the 2-D
    design's pjit'd dense ops), vertex-scale tensors split over
    ``parts``, edge/halo index tables split over ``parts`` only —
    they carry no feature axis, so the model axis REPLICATES them,
    and the ledger must say so rather than divide by the whole mesh.

    Returns ``{component: {"bytes": total, "parts_div": p,
    "model_div": m, "per_device": total // (p*m)}}`` plus a
    ``"total"`` row; ``replicated`` in a component marks the axes
    (divisor 1 while the mesh axis is >1) it is replicated over."""
    parts = max(parts, 1)
    c = plan_components(num_nodes, num_edges, ops, num_parts=parts,
                        dtype_bytes=dtype_bytes, halo=halo,
                        features=features, remat=remat,
                        ring_padding=ring_padding,
                        param_bytes=dtype_bytes)

    def comp(total: int, parts_div: int, model_div: int
             ) -> Dict[str, int]:
        per_dev = int(total) // max(parts_div * model_div, 1)
        rep = []
        if parts > 1 and parts_div == 1:
            rep.append("parts")
        if model > 1 and model_div == 1:
            rep.append("model")
        return {"bytes": int(total), "parts_div": parts_div,
                "model_div": model_div, "per_device": per_dev,
                "replicated": rep}

    # params + Adam m/v: feature-axis (model) sharded on the 2-D
    # mesh, replicated over parts either way (the reference reads
    # weights whole in every task); every other component is a
    # partition's share, so the whole is ``parts`` of them.  Edge/halo
    # index tables carry no feature axis: the model axis replicates
    # them.  The gathered whole-region matrix is per-device [P * V_p,
    # h] — replicated over parts BY DESIGN (that is what a gather is)
    out = {"params_opt": comp(c["params_opt"], 1, model),
           "features": comp(c["features"] * parts, parts, model),
           "tables": comp(c["tables"] * parts, parts, 1),
           "activations": comp(c["activations"] * parts, parts, model),
           "transient": comp(c["transient"] * parts, parts, model)}
    total = sum(v["bytes"] for v in out.values())
    per_dev = sum(v["per_device"] for v in out.values())
    out["total"] = {"bytes": int(total), "per_device": int(per_dev),
                    "replicated": sorted({a for v in out.values()
                                          for a in v.get("replicated",
                                                         [])})}
    return out


def choose_memory_plan(num_nodes: int, num_edges: int,
                       ops: Sequence[Any], num_parts: int = 1,
                       dtype_bytes: int = 4,
                       hbm_bytes: Optional[int] = None,
                       head_streamable: bool = True,
                       extra_table_bytes: int = 0,
                       param_bytes: int = 4,
                       scan_rows: int = 0) -> MemoryPlan:
    """First-fit over plans ordered cheapest-compute-first.

    Order: gather/hbm -> gather/hbm+remat -> ring (P>1, +-remat) ->
    host-streamed features (P==1, head_streamable models).  The ring is
    the distributed answer to >HBM (SURVEY §5), host streaming the
    single-device one (the reference's ZC tier, ``types.cu:22-32``).
    If nothing fits, the last candidate is returned with
    ``fits=False`` — the caller proceeds (estimates are pessimistic)
    with the warning in the echo."""
    budget = hbm_bytes if hbm_bytes is not None else detect_hbm_bytes()
    cands: List = [("gather/hbm", "gather", "hbm", False),
                   ("gather/hbm/remat", "gather", "hbm", True)]
    if num_parts > 1:
        cands += [("ring/hbm", "ring", "hbm", False),
                  ("ring/hbm/remat", "ring", "hbm", True)]
    elif head_streamable:
        cands += [("gather/host", "gather", "host", False),
                  ("gather/host/remat", "gather", "host", True)]
    est = {}
    for name, halo, feats, remat in cands:
        est[name] = estimate_plan_bytes(
            num_nodes, num_edges, ops, num_parts=num_parts,
            dtype_bytes=dtype_bytes, param_bytes=param_bytes,
            scan_rows=scan_rows if halo == "gather" else 0,
            halo=halo, features=feats, remat=remat,
            # ring runs never build the bdense A-table (the ring
            # tables fully describe the aggregation) — charging them
            # would push ring plans into remat for phantom bytes
            extra_table_bytes=(extra_table_bytes
                               if halo == "gather" else 0))
    for name, halo, feats, remat in cands:
        if est[name] <= budget:
            return MemoryPlan(
                halo=halo, features=feats, remat=remat, fits=True,
                est_bytes=est[name], budget_bytes=budget,
                candidates=est,
                reason=f"first fit of {len(cands)} candidates")
    name, halo, feats, remat = cands[-1]
    return MemoryPlan(
        halo=halo, features=feats, remat=remat, fits=False,
        est_bytes=est[name], budget_bytes=budget, candidates=est,
        reason="NO plan fits the budget — proceeding with the smallest "
               "(estimates are pessimistic); expect allocator pressure")
