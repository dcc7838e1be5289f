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
- ``remat``: recompute activations in backward instead of saving them
  (``jax.checkpoint``).

:func:`choose_memory_plan` estimates the footprint of each viable
combination (cheapest-first) and returns the first that fits, so a
graph sized past the gather budget trains via ring or streaming with
no user flags — the reference needs no flags for its cache either.
The decision is echoed at trainer setup like the reference's config
print (``gnn.cc:48-60``).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

# Activation-liveness factors: a GCN-family layer keeps roughly this
# many [V_p, H] intermediates alive for backward (dropout out, linear
# out, two norms, aggregation out, relu out) without remat; with
# jax.checkpoint the layer boundaries survive plus the saved
# aggregation outputs (the default save_aggregates policy,
# train/trainer.py remat_policy — recomputing the halo gather + CSR
# sum would dominate the remat overhead).
_ACT_FACTOR_SAVED = 6
_ACT_FACTOR_REMAT_SAVE_AGG = 3   # layer boundaries + saved aggregates
_ACT_FACTOR_REMAT_FULL = 2       # layer boundaries only
# Default usable fraction of physical HBM (XLA reserves workspace,
# and the estimate is deliberately coarse).
_USABLE = 0.85
_DEFAULT_HBM = 16 * 1024**3  # v5e physical per chip


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


def estimate_plan_bytes(num_nodes: int, num_edges: int,
                        layer_dims: Sequence[int], num_parts: int = 1,
                        dtype_bytes: int = 4, halo: str = "gather",
                        features: str = "hbm", remat: bool = False,
                        ring_padding: float = 1.7,
                        remat_policy: str = "save_aggregates",
                        extra_table_bytes: int = 0) -> int:
    """Coarse per-device peak-HBM estimate for one train step.

    ``layer_dims`` is the CLI layer spec (in-dim, hidden..., classes).
    Deliberately simple and slightly pessimistic — the policy needs
    ordering between plans, not byte-exact numbers.

    ``extra_table_bytes`` covers impl-specific resident tables the
    generic ``E*4`` term misses — today the bdense A-table, whose
    worst case is exactly ``bdense_a_budget`` (the planner's device-
    byte cap)."""
    V_p = -(-num_nodes // num_parts)
    E_p = -(-num_edges // num_parts)
    b = dtype_bytes
    F = layer_dims[0]
    hiddens = list(layer_dims[1:])
    h_max = max(hiddens + [F])

    # replicated params + Adam m/v
    w = sum(layer_dims[i] * layer_dims[i + 1]
            for i in range(len(layer_dims) - 1))
    total = 3 * w * b

    # input features
    if features == "hbm":
        total += V_p * F * b
    else:
        total += 65536 * F * b  # one streamed block + dY reuse

    # edge tables: ELL idx ~ E_p int32 (+ row positions)
    total += E_p * 4 + V_p * 4 + extra_table_bytes
    if halo == "ring":
        total += int(2 * E_p * 4 * ring_padding)  # src+dst flat tables

    # live activations
    if remat:
        act = (_ACT_FACTOR_REMAT_FULL if remat_policy == "full"
               else _ACT_FACTOR_REMAT_SAVE_AGG)
    else:
        act = _ACT_FACTOR_SAVED
    act_bytes = sum(V_p * h * b * act for h in hiddens)
    if features == "hbm":
        # first dropout output is [V_p, F]
        act_bytes += V_p * F * b * (1 if remat else 2)
    total += act_bytes

    # halo transient: the gathered global matrix vs two ring buffers
    if halo == "gather":
        total += num_parts * V_p * h_max * b
    else:
        total += 2 * V_p * h_max * b
    return total


def per_axis_plan_bytes(num_nodes: int, num_edges: int,
                        layer_dims: Sequence[int], parts: int = 1,
                        model: int = 1, dtype_bytes: int = 4,
                        halo: str = "gather", features: str = "hbm",
                        remat: bool = False,
                        remat_policy: str = "save_aggregates",
                        ring_padding: float = 1.7
                        ) -> Dict[str, Dict[str, int]]:
    """Per-component, per-mesh-axis byte attribution of one train
    step on an abstract ``(parts, model)`` mesh — the planner-side
    half of the sharding auditor's replication ledger
    (analysis/sharding_lint.py) and the "modeled per-device HBM"
    column of the mesh-portability report.

    Same coarse accounting as :func:`estimate_plan_bytes` (whose
    ``parts``-only totals this reproduces at ``model=1``), but each
    component reports WHICH axes divide it: params/opt-state and
    activations split over ``model`` on their feature axis (the 2-D
    design's pjit'd dense ops), vertex-scale tensors split over
    ``parts``, edge/halo index tables split over ``parts`` only —
    they carry no feature axis, so the model axis REPLICATES them,
    and the ledger must say so rather than divide by the whole mesh.

    Returns ``{component: {"bytes": total, "parts_div": p,
    "model_div": m, "per_device": total // (p*m)}}`` plus a
    ``"total"`` row; ``replicated`` in a component marks the axes
    (divisor 1 while the mesh axis is >1) it is replicated over."""
    V_p = -(-num_nodes // max(parts, 1))
    E_p = -(-num_edges // max(parts, 1))
    b = dtype_bytes
    F = layer_dims[0]
    hiddens = list(layer_dims[1:])
    h_max = max(hiddens + [F])
    w = sum(layer_dims[i] * layer_dims[i + 1]
            for i in range(len(layer_dims) - 1))

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

    out: Dict[str, Dict[str, int]] = {}
    # params + Adam m/v: feature-axis (model) sharded on the 2-D
    # mesh, replicated over parts either way (the reference reads
    # weights whole in every task)
    out["params"] = comp(w * b, 1, model)
    out["opt_state"] = comp(2 * w * b, 1, model)
    if features == "hbm":
        out["features"] = comp(num_nodes * F * b, parts, model)
    else:
        out["features"] = comp(65536 * F * b * parts, parts, model)
    # edge/halo index tables: int32 per edge + row positions — no
    # feature axis, so the model axis replicates them
    tab = E_p * 4 * parts + V_p * 4 * parts
    if halo == "ring":
        tab += int(2 * E_p * 4 * ring_padding) * parts
    out["tables"] = comp(tab, parts, 1)
    if remat:
        act = (_ACT_FACTOR_REMAT_FULL if remat_policy == "full"
               else _ACT_FACTOR_REMAT_SAVE_AGG)
    else:
        act = _ACT_FACTOR_SAVED
    act_bytes = sum(num_nodes * h * b * act for h in hiddens)
    if features == "hbm":
        act_bytes += num_nodes * F * b * (1 if remat else 2)
    out["activations"] = comp(act_bytes, parts, model)
    # halo transient: the gathered whole-region matrix is per-device
    # [P * V_p, h] — replicated over parts BY DESIGN (that is what a
    # gather is), feature-sharded over model; the ring keeps two
    # block buffers instead
    if halo == "gather":
        out["halo"] = comp(parts * V_p * h_max * b * parts, parts,
                           model)
    else:
        out["halo"] = comp(2 * V_p * h_max * b * parts, parts, model)
    total = sum(c["bytes"] for c in out.values())
    per_dev = sum(c["per_device"] for c in out.values())
    out["total"] = {"bytes": int(total), "per_device": int(per_dev),
                    "replicated": sorted({a for c in out.values()
                                          for a in c.get("replicated",
                                                         [])})}
    return out


def choose_memory_plan(num_nodes: int, num_edges: int,
                       layer_dims: Sequence[int], num_parts: int = 1,
                       dtype_bytes: int = 4,
                       hbm_bytes: Optional[int] = None,
                       head_streamable: bool = True,
                       remat_policy: str = "save_aggregates",
                       extra_table_bytes: int = 0
                       ) -> MemoryPlan:
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
            num_nodes, num_edges, layer_dims, num_parts, dtype_bytes,
            halo=halo, features=feats, remat=remat,
            remat_policy=remat_policy,
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
