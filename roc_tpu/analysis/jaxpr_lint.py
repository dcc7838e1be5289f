"""Rules over ClosedJaxprs of the trainers' step functions.

Each lint *unit* is one traced program (train step, eval step, the
recorded-op model graph) plus the static context a rule needs to tell
intended from unintended: the configured compute dtype, the
dataset's [V, F] scale, the halo mode, donation thresholds.  Rules
walk the whole nesting (jit / shard_map / custom_vjp / scan bodies)
— an anti-pattern inside a remat body is still an anti-pattern.

The thresholds are *scale-relative*, not absolute: "[V, F]-scale"
means the full per-device activation footprint, so the same rules
bite on a 256-node CI fixture and a 233M-edge production graph.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, Iterator, List, Optional, Tuple

from .findings import Finding

# int32 overflow hazard threshold (rule jaxpr-int32-overflow)
_INT32_LIMIT = 2 ** 31

# host-callback primitive names (jax.debug.print traces to
# debug_print, jax.debug.callback to debug_callback)
_CALLBACK_PRIMS = ("debug_print", "debug_callback", "pure_callback",
                   "io_callback")

_COLLECTIVE_GATHERS = ("all_gather", "all_gather_invariant",
                       "all_to_all")


@dataclass
class JaxprUnit:
    """One traced program under lint.

    ``jaxpr`` is a ClosedJaxpr (``jax.make_jaxpr(fn)(*args)``).
    ``compute_dtype`` is the dtype the config says activations run in
    (the bf16-upcast rule only arms when it is 'bfloat16');
    ``vf_elems`` the full activation element count (V*F) the
    scale-relative rules compare against; ``donate_min_bytes`` the
    buffer size past which a non-donated update-shaped argument is
    worth flagging (the driver passes the largest parameter leaf);
    ``index_bound`` the conservative max value of integer inputs
    (node ids — defaults to V)."""

    name: str
    jaxpr: Any
    compute_dtype: str = "float32"
    num_nodes: int = 0
    vf_elems: int = 0
    halo: str = "gather"
    donate_min_bytes: int = 1 << 20
    index_bound: Optional[int] = None
    # mesh size for shard_map'd units: avals inside the body are
    # block-LOCAL, so vf_elems must be the PER-DEVICE V/P * F there,
    # and the sanctioned whole-region gather is mesh_parts * vf_elems
    mesh_parts: int = 1
    detail: Dict[str, Any] = field(default_factory=dict)

    @property
    def unit(self) -> str:
        return f"jaxpr:{self.name}"


def _inner_jaxprs(eqn) -> Iterator[Any]:
    """Jaxprs nested in an eqn's params (jit/shard_map/custom_vjp/
    scan/remat bodies), whatever the param key."""
    for v in eqn.params.values():
        for item in (v if isinstance(v, (tuple, list)) else (v,)):
            if hasattr(item, "jaxpr") and hasattr(
                    getattr(item, "jaxpr"), "eqns"):
                yield item.jaxpr          # ClosedJaxpr
            elif hasattr(item, "eqns"):
                yield item                # raw Jaxpr


def iter_eqns(closed_jaxpr) -> Iterator[Any]:
    """Every eqn in the program, depth-first across all nesting."""
    stack = [closed_jaxpr.jaxpr]
    while stack:
        j = stack.pop()
        for eqn in j.eqns:
            yield eqn
            stack.extend(_inner_jaxprs(eqn))


def _aval(v):
    return getattr(v, "aval", None)


def _elems(aval) -> int:
    n = 1
    for d in getattr(aval, "shape", ()):
        n *= int(d)
    return n


def _shape_str(aval) -> str:
    return (f"{getattr(aval, 'dtype', '?')}"
            f"{list(getattr(aval, 'shape', ()))}")


# --------------------------------------------------------------- rules

def check_f32_upcast(u: JaxprUnit) -> List[Finding]:
    """[jaxpr-f32-upcast] ``convert_element_type`` bf16 -> f32 of an
    activation-scale tensor inside a bf16-configured path: the mixed-
    precision contract is that features/activations stay bf16 through
    the sandwich — a [V, F]-scale upcast silently doubles the HBM
    traffic the mode exists to halve.  Class-width tensors (the fp32
    loss/softmax reduction, [V, C] with C << F) stay sanctioned by the
    scale threshold."""
    out: List[Finding] = []
    if u.compute_dtype != "bfloat16" or not u.vf_elems:
        return out
    for eqn in iter_eqns(u.jaxpr):
        if eqn.primitive.name != "convert_element_type":
            continue
        src = _aval(eqn.invars[0])
        dst = _aval(eqn.outvars[0])
        if (src is None or dst is None
                or str(src.dtype) != "bfloat16"
                or str(dst.dtype) != "float32"):
            continue
        if _elems(src) >= u.vf_elems:
            out.append(Finding(
                "jaxpr-f32-upcast", u.unit,
                f"bf16 -> f32 upcast of activation-scale tensor "
                f"{_shape_str(src)} (>= V*F = {u.vf_elems} elems) in "
                f"a bf16-configured path",
                key=f"upcast|{_shape_str(src)}"))
    return out


def check_host_callback(u: JaxprUnit) -> List[Finding]:
    """[jaxpr-host-callback] host callbacks / debug prints under jit:
    each one is a device->host round trip per step, serializing the
    dispatch pipeline (and on multi-host rigs, desynchronizing
    SPMD programs)."""
    out: List[Finding] = []
    for eqn in iter_eqns(u.jaxpr):
        name = eqn.primitive.name
        if name in _CALLBACK_PRIMS or name.endswith("_callback"):
            out.append(Finding(
                "jaxpr-host-callback", u.unit,
                f"host callback primitive '{name}' inside the jitted "
                f"step (per-step device->host round trip)",
                key=f"callback|{name}"))
    return out


def check_non_donated(u: JaxprUnit) -> List[Finding]:
    """[jaxpr-non-donated] a large argument whose aval matches an
    output aval but is not donated: params/opt-state-sized buffers
    passed undonated double their HBM residency for the whole step
    (XLA must keep the input alive while writing the update).

    Only the DISPATCH-BOUNDARY jit is judged — the single top-level
    jit eqn of a traced jitted callable.  Donation is a caller-side
    contract at that boundary; inner library jits are inlined by XLA,
    which reuses their buffers without any donate_argnums.

    Value-and-grad recognition: jax's ``value_and_grad`` convention
    puts the scalar value FIRST and the gradients after it — a
    grad-shaped output of such a jaxpr is a *cotangent* of its primal
    argument, not an update of it, and the caller by construction
    still needs the primal afterwards (the optimizer apply consumes
    params AND grads), so donation is not the fix and flagging it was
    the rule's one known false positive (the retired ``tail_grad``
    baseline entry).  An update-style step (params first, or no
    leading scalar) is judged exactly as before.

    A scalar PARAM that happens to flatten first (e.g. a learned-eps
    GIN) must not disarm the rule for update steps: the echo guard
    below refuses the exemption when the first two output avals
    mirror the first two input avals in order — an update step echoes
    its input prefix (params head INCLUDING the scalar), while
    value_and_grad's leading scalar is the loss, whose successor is
    the first *cotangent* and so tracks the primal's leaf 0, not
    leaf 1.

    Known limit of the convention heuristic: a hand-written update
    step returning ``(loss, new_params, new_opt_state)`` — scalar
    FIRST — would be exempted too, since avals alone cannot separate
    cotangents from updated buffers (adam state is param-shaped, so
    even cross-arg matching can't).  This repo's steps return loss
    LAST (the flagged surface), and every step slot is a fixed,
    linted unit in driver.py — a new scalar-first update slot should
    keep that convention or donate explicitly."""
    out: List[Finding] = []
    top = [e for e in u.jaxpr.jaxpr.eqns
           if e.primitive.name == "jit"]
    if len(top) != 1 or len(u.jaxpr.jaxpr.eqns) != 1:
        return out
    for eqn in top:
        donated = eqn.params.get("donated_invars")
        if donated is None:
            continue
        out_sigs = []
        for v in eqn.outvars:
            a = _aval(v)
            out_sigs.append((tuple(a.shape), str(a.dtype))
                            if a is not None else None)
        in_sigs = []
        for v in eqn.invars[:2]:
            a = _aval(v)
            in_sigs.append((tuple(a.shape), str(a.dtype))
                           if a is not None else None)
        # an output prefix that mirrors the input prefix in ORDER is
        # an update-step echo, not (value, grads...) — see docstring
        echo_prefix = (len(out_sigs) >= 2 and len(in_sigs) == 2
                       and None not in in_sigs
                       and out_sigs[0] == in_sigs[0]
                       and out_sigs[1] == in_sigs[1])
        value_and_grad_like = (
            bool(out_sigs) and out_sigs[0] is not None
            and out_sigs[0][0] == () and "float" in out_sigs[0][1]
            and len(out_sigs) > 1 and not echo_prefix)
        for pos, (var, don) in enumerate(zip(eqn.invars, donated)):
            if don:
                continue
            a = _aval(var)
            if a is None:
                continue
            sig = (tuple(a.shape), str(a.dtype))
            nbytes = _elems(a) * getattr(a.dtype, "itemsize", 4)
            matches = [i for i, s in enumerate(out_sigs) if s == sig]
            if not matches or nbytes < u.donate_min_bytes:
                continue
            if value_and_grad_like and all(i > 0 for i in matches):
                continue    # cotangents of a (value, grads...) jaxpr
            out.append(Finding(
                "jaxpr-non-donated", u.unit,
                f"arg {pos} ({_shape_str(a)}, {nbytes} B) matches "
                f"an output aval but is not donated — its HBM "
                f"residency is doubled across the step; add it to "
                f"donate_argnums",
                key=f"nondonated|{pos}|{_shape_str(a)}"))
    return out


def check_collective_materialize(u: JaxprUnit) -> List[Finding]:
    """[jaxpr-collective-materialize] cross-shard materialization of
    activation-scale tensors: a psum whose operand is [V, F]-scale
    (the symmetric-vjp design exists precisely so gradients re-run the
    forward gather instead), any all-gather under halo='ring' (the
    ring's whole point is never materializing [V, F] per device), or
    a gather landing MORE than the designed whole-region [V, F]."""
    out: List[Finding] = []
    if not u.vf_elems:
        return out
    for eqn in iter_eqns(u.jaxpr):
        name = eqn.primitive.name
        if name == "psum":
            for var in eqn.invars:
                a = _aval(var)
                if a is not None and _elems(a) >= u.vf_elems:
                    out.append(Finding(
                        "jaxpr-collective-materialize", u.unit,
                        f"psum of activation-scale tensor "
                        f"{_shape_str(a)} (>= V*F = {u.vf_elems}) — "
                        f"an implicit cross-shard materialization; "
                        f"the symmetric custom-vjp aggregation path "
                        f"avoids this",
                        key=f"psum|{_shape_str(a)}"))
        elif name in _COLLECTIVE_GATHERS:
            a = _aval(eqn.outvars[0])
            if a is None:
                continue
            n = _elems(a)
            whole_region = u.vf_elems * max(u.mesh_parts, 1)
            if u.halo == "ring" and n >= u.vf_elems:
                out.append(Finding(
                    "jaxpr-collective-materialize", u.unit,
                    f"{name} materializes {_shape_str(a)} under "
                    f"halo='ring' — the ring exists to keep per-device "
                    f"peak at O(V/P * F)",
                    key=f"ring-gather|{name}|{_shape_str(a)}"))
            elif n >= 2 * whole_region:
                out.append(Finding(
                    "jaxpr-collective-materialize", u.unit,
                    f"{name} materializes {_shape_str(a)} — larger "
                    f"than the designed whole-region [V, F] gather "
                    f"({whole_region} elems)",
                    key=f"gather|{name}|{_shape_str(a)}"))
    return out


def _int_limit(dtype) -> Optional[int]:
    s = str(dtype)
    if s == "int32":
        return 2 ** 31
    if s == "uint32":
        return 2 ** 32
    if s == "int16":
        return 2 ** 15
    if s == "uint16":
        return 2 ** 16
    return None     # int64/unknown: not a hazard we track


def check_int32_overflow(u: JaxprUnit) -> List[Finding]:
    """[jaxpr-int32-overflow] index arithmetic whose STATIC bound
    exceeds the result dtype's range: a conservative max-abs-value
    propagation over the integer eqns (literals exact, iota = size-1,
    integer inputs bounded by ``index_bound`` — node ids can't exceed
    V).  At billion-edge scale ``row * F + col`` flattening in int32
    silently wraps; this catches it at trace time, plus int64->int32
    truncations of already-overflowing bounds."""
    out: List[Finding] = []
    bound_default = u.index_bound if u.index_bound is not None \
        else max(u.num_nodes, 1)

    def run(jaxpr, bounds: Dict[Any, int]) -> None:
        def get(v) -> Optional[int]:
            if hasattr(v, "val"):         # Literal
                try:
                    return int(abs(int(v.val)))
                except (TypeError, ValueError, OverflowError):
                    return None
            return bounds.get(v)

        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if name == "jit" or _is_container(eqn):
                for inner in _inner_jaxprs(eqn):
                    inner_bounds: Dict[Any, int] = {}
                    for iv, ov in zip(getattr(inner, "invars", ()),
                                      eqn.invars):
                        b = get(ov)
                        if b is not None:
                            inner_bounds[iv] = b
                    seed_int_invars(inner, inner_bounds)
                    run(inner, inner_bounds)
                continue
            if not eqn.outvars:
                continue
            oav = _aval(eqn.outvars[0])
            odt = getattr(oav, "dtype", None)
            is_int = odt is not None and "int" in str(odt)
            ins = [get(v) for v in eqn.invars]
            res: Optional[int] = None
            arith = False
            if name == "iota":
                dim = eqn.params.get("dimension", 0)
                shape = eqn.params.get("shape", (1,))
                res = max(int(shape[dim]) - 1, 0)
            elif name in ("mul", "dot_general") and is_int:
                arith = True
                if None not in ins[:2]:
                    res = ins[0] * ins[1]
                    if name == "dot_general":
                        k = _elems(_aval(eqn.invars[0])) or 1
                        res *= k
            elif name in ("add", "sub") and is_int:
                arith = True
                if None not in ins[:2]:
                    res = ins[0] + ins[1]
            elif name == "reduce_sum" and is_int:
                arith = True
                if ins[0] is not None:
                    n = _elems(_aval(eqn.invars[0]))
                    res = ins[0] * max(n, 1)
            elif name in ("max", "min", "concatenate"):
                known = [b for b in ins if b is not None]
                res = max(known) if known else None
            elif name in ("broadcast_in_dim", "reshape", "squeeze",
                          "transpose", "expand_dims", "slice",
                          "dynamic_slice", "rev", "copy",
                          "stop_gradient", "gather", "take"):
                res = ins[0]
            elif name == "convert_element_type":
                res = ins[0]
                lim = _int_limit(odt) if is_int else None
                if res is not None and lim and res >= lim:
                    out.append(Finding(
                        "jaxpr-int32-overflow", u.unit,
                        f"narrowing convert to {odt} truncates: "
                        f"static bound {res} >= {lim}",
                        key=f"narrow|{odt}|{_shape_str(oav)}"))
            if arith and res is not None:
                lim = _int_limit(odt)
                if lim and res >= lim:
                    out.append(Finding(
                        "jaxpr-int32-overflow", u.unit,
                        f"{name} on {odt} has static bound {res} >= "
                        f"{lim} — index arithmetic overflows; compute "
                        f"in int64 (or rescale) before narrowing",
                        key=f"overflow|{name}|{odt}|{_shape_str(oav)}"))
            if res is not None:
                for ov in eqn.outvars:
                    bounds[ov] = res

    def seed_int_invars(jaxpr, bounds) -> None:
        for v in getattr(jaxpr, "invars", ()):
            a = _aval(v)
            if v not in bounds and a is not None \
                    and "int" in str(getattr(a, "dtype", "")):
                bounds[v] = bound_default

    def _is_container(eqn) -> bool:
        return any(True for _ in _inner_jaxprs(eqn))

    top = u.jaxpr.jaxpr
    bounds: Dict[Any, int] = {}
    seed_int_invars(top, bounds)
    run(top, bounds)
    return out


JAXPR_RULES = {
    "jaxpr-f32-upcast": check_f32_upcast,
    "jaxpr-host-callback": check_host_callback,
    "jaxpr-non-donated": check_non_donated,
    "jaxpr-collective-materialize": check_collective_materialize,
    "jaxpr-int32-overflow": check_int32_overflow,
}


def run_jaxpr_lint(units: List[JaxprUnit],
                   select: Optional[List[str]] = None
                   ) -> List[Finding]:
    findings: List[Finding] = []
    for unit in units:
        for name, rule in JAXPR_RULES.items():
            if select is not None and name not in select:
                continue
            findings.extend(rule(unit))
    return findings
