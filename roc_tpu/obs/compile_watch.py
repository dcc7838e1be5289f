"""Compile observer: what XLA actually built, vs what we modeled.

Wraps a jitted step function so its first execution goes through the
explicit AOT path (``lower()`` then ``compile()``), capturing:

- lowering + compile wall time (a slow first step attributed to
  lowering vs compiling vs running);
- ``cost_analysis()`` — flops and bytes accessed per step, the inputs
  to MFU/throughput derivation downstream;
- ``memory_analysis()`` — XLA's actual argument/output/temp sizes,
  whose sum approximates peak HBM for the executable;
- the delta between that actual peak and ``core/memory.py``'s modeled
  budget — warning loudly when the plan undershoots reality (the
  planner-vs-residency disagreement the round-5 advisor flagged).

Steady-state calls route through the compiled executable (the AOT
compile would otherwise be thrown away and paid twice).  Every
introspection step degrades gracefully: a backend without
``cost_analysis`` still trains, it just reports nulls
(tests/test_obs.py gates this).
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, Optional, Tuple

from .events import emit
from .heartbeat import Heartbeat
from .scopes import has_scopes, parse_program_text


def leaf_struct(x) -> Tuple[str, Tuple[int, ...], str]:
    """Structured signature of one flattened argument leaf:
    ``(dtype, dims, spec)`` — the fields a jit cache key (and the
    persistent compile cache) actually specializes on.  Sharding spec
    renders only for NamedSharding (single-device default placements
    collapse to '-'); non-array leaves collapse to
    ``('py', (), repr(x))``.  THE one extraction behind both the
    rendered program key (:func:`program_key_of`, below) and the
    program-space auditor's dimension-level drift rule
    (``analysis/programspace.py`` imports this) — a signature change
    here changes both sides together, so they cannot drift."""
    aval = getattr(x, "aval", x)
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return ("py", (), repr(x))
    spec = "-"
    sh = getattr(x, "sharding", None)
    if sh is not None and hasattr(sh, "spec"):
        spec = ",".join("None" if s is None else str(s)
                        for s in tuple(sh.spec))
        spec = spec or "-"
    return (str(dtype), tuple(int(d) for d in shape), spec)


def _leaf_sig(x) -> str:
    """``dtype[d0,d1,...]@spec`` rendering of :func:`leaf_struct`."""
    dtype, dims, spec = leaf_struct(x)
    if dtype == "py":
        return f"py:{spec}"
    return f"{dtype}[{','.join(str(d) for d in dims)}]@{spec}"


def program_key_of(name: str, args,
                   donate_argnums: Tuple[int, ...] = ()) -> str:
    """THE canonical compiled-program identity:
    ``slot|leaf sigs|donate=...``.  Computed by :class:`ObservedJit`
    at first compile (the ``program_key`` field of every ``compile``
    event) AND by the program-space auditor
    (``roc_tpu/analysis/programspace.py``) from the abstract avals —
    the same function on both sides is what makes static-vs-live
    program-set parity checkable at all.  Donated argnums are part of
    the key because donation changes the executable's aliasing (two
    otherwise-identical programs with different donation are distinct
    compiles)."""
    import jax
    leaves = jax.tree_util.tree_leaves(args)
    sig = ";".join(_leaf_sig(v) for v in leaves)
    don = ",".join(str(int(i)) for i in donate_argnums)
    return f"{name}|{sig}|donate={don}"


def cost_summary(compiled) -> Dict[str, Optional[float]]:
    """{'flops', 'bytes_accessed'} from ``cost_analysis()`` (a flat
    dict); None fields when the backend does not implement it."""
    out: Dict[str, Optional[float]] = {"flops": None,
                                       "bytes_accessed": None}
    try:
        ca = compiled.cost_analysis()
    except Exception:  # noqa: BLE001 - introspection is best-effort
        return out
    for key, field in (("flops", "flops"),
                       ("bytes accessed", "bytes_accessed")):
        v = (ca or {}).get(key)
        if v is not None and float(v) >= 0:
            out[field] = float(v)
    return out


def memory_summary(compiled) -> Dict[str, Optional[int]]:
    """Byte sizes from ``memory_analysis()`` (CompiledMemoryStats).
    ``peak_bytes`` approximates the executable's device footprint:
    arguments + outputs + temporaries, minus donated aliases."""
    out: Dict[str, Optional[int]] = {
        "peak_bytes": None, "argument_bytes": None,
        "output_bytes": None, "temp_bytes": None,
        "generated_code_bytes": None}
    try:
        ma = compiled.memory_analysis()
        if ma is None:
            return out
        parts = {}
        for field, attr in (("argument_bytes", "argument_size_in_bytes"),
                            ("output_bytes", "output_size_in_bytes"),
                            ("temp_bytes", "temp_size_in_bytes"),
                            ("generated_code_bytes",
                             "generated_code_size_in_bytes")):
            v = getattr(ma, attr, None)
            if v is not None:
                parts[field] = int(v)
                out[field] = int(v)
        alias = int(getattr(ma, "alias_size_in_bytes", 0) or 0)
        if parts:
            out["peak_bytes"] = max(
                0, parts.get("argument_bytes", 0)
                + parts.get("output_bytes", 0)
                + parts.get("temp_bytes", 0) - alias)
    except Exception:  # noqa: BLE001 - introspection is best-effort
        pass
    return out


def compile_text_uncached(lowered) -> str:
    """The optimized HLO text of ``lowered`` from a fresh XLA compile:
    past JAX's in-memory and persistent caches, with the options
    ``lowered.compile()`` would use, and WITHOUT loading the result
    where the backend can compile alone (the TPU can; a loaded program
    reserves its temporaries, and a second step program does not fit
    beside a live Reddit-scale trainer).  Where it cannot (XLA:CPU has
    no stand-alone compiler) the program is compiled and loaded.

    There is no public way to ask for either, so this follows
    ``jax._src.interpreters.pxla.UnloadedMeshExecutable.from_hlo`` down
    to ``compiler.backend_compile`` (jax 0.9)."""
    import jax
    import numpy as np
    from jax._src import compiler
    from jax._src.interpreters import pxla
    comp = lowered._lowering            # pxla.MeshComputation
    args = comp.compile_args
    devices = comp._device_list
    ins, outs = (tuple(pxla.maybe_concretize_mesh(s, devices)
                       for s in args[k])
                 for k in ("in_shardings", "out_shardings"))
    prop_in, prop_out = pxla.get_prop_to_input_output(
        ins, outs, len(args["ordered_effects"]))
    options = pxla.create_compile_options(
        comp._hlo, None, args["spmd_lowering"], args["tuple_args"],
        args["auto_spmd_lowering"], prop_in, prop_out, args["backend"],
        np.array(list(devices), dtype=object), args["pmap_nreps"],
        dict(comp._compiler_options_kvs))
    try:
        exe = compiler.backend_compile(args["backend"], comp._hlo,
                                       devices, options)
    except jax.errors.JaxRuntimeError:
        exe = compiler.backend_compile_and_load(
            args["backend"], comp._hlo, devices, options,
            args["host_callbacks"])
    return exe.hlo_modules()[0].to_string()


# Per-chip peak dense FLOP/s (bf16 MXU path — the precision the
# production configs run), keyed by device_kind substring.  MFU is a
# *style* of utilization number: a coarse, stable denominator for
# round-over-round comparison, not a vendor-exact ceiling.  CPU rigs
# have no entry — the mfu field is simply absent there.
PEAK_FLOPS_BY_KIND = {
    "v5 lite": 197e12,
    "v5e": 197e12,
    "v5p": 459e12,
    "v6 lite": 918e12,
    "v6e": 918e12,
    "v4": 275e12,
}


def peak_flops_per_s(device_kind: Optional[str] = None
                     ) -> Optional[float]:
    """Peak FLOP/s for ``device_kind`` (default: the current backend's
    first device).  None off-TPU — callers drop the MFU field rather
    than fabricate a denominator; a TPU kind missing from the table
    is an error, not a silent absence."""
    if device_kind is None:
        import jax
        device_kind = jax.devices()[0].device_kind
    kind = (device_kind or "").lower()
    for key, val in PEAK_FLOPS_BY_KIND.items():
        if key in kind:
            return val
    if "tpu" in kind:
        raise ValueError(
            f"no peak FLOP/s for TPU kind {device_kind!r} in "
            f"PEAK_FLOPS_BY_KIND (obs/compile_watch.py)")
    return None


def _fmt_bytes(n: Optional[float]) -> str:
    if n is None:
        return "?"
    if n >= 1 << 28:
        return f"{n / 1024**3:.2f}GiB"
    if n >= 1 << 17:
        return f"{n / 1024**2:.1f}MiB"
    return f"{n / 1024:.1f}KiB"


class ObservedJit:
    """``jax.jit`` with first-compile telemetry.

    Drop-in for the trainer step slots: construct with the step
    *implementation* (it calls ``jax.jit`` itself) or with
    ``jitfn=`` for an already-wrapped callable (shard_map steps).
    ``modeled_bytes`` is the memory plan's estimate for this step;
    when XLA's actual peak exceeds it the event warns unconditionally.
    """

    # actual peak this far above the model warns even with verbose off
    # — both gates must trip: the ratio (the model missed a TERM, not
    # a rounding) and an absolute floor (at toy scale, fixed XLA
    # overheads dominate any estimate and the warning would be noise)
    UNDERSHOOT_WARN_RATIO = 1.1
    UNDERSHOOT_WARN_MIN_BYTES = 256 << 20

    def __init__(self, fn: Optional[Callable] = None, *,
                 name: str, jitfn: Optional[Callable] = None,
                 donate_argnums: Tuple[int, ...] = (),
                 modeled_bytes: Optional[int] = None,
                 verbose: bool = False):
        import jax
        if jitfn is None:
            jitfn = jax.jit(fn, donate_argnums=donate_argnums)
        self._jit = jitfn
        self.name = name
        # recorded for introspection (roc_tpu/analysis maps jaxpr
        # invars back to donated argnums); with jitfn= the caller
        # passes the argnums its own jax.jit was built with
        self.donate_argnums = donate_argnums
        self.modeled_bytes = modeled_bytes
        self.verbose = verbose
        self.cost: Optional[Dict[str, Any]] = None  # last compile event
        self._compiled = None
        self._lowered = None       # kept for instruction_scopes()
        self._degraded = False

    # expose the underlying jit's AOT surface for callers that poke it
    def lower(self, *args, **kw):
        return self._jit.lower(*args, **kw)

    def _observe(self, args) -> None:
        t0 = time.perf_counter()
        with Heartbeat(f"compile:{self.name}"):
            lowered = self._jit.lower(*args)
            t1 = time.perf_counter()
            compiled = lowered.compile()
            t2 = time.perf_counter()
        fields: Dict[str, Any] = {
            "name": self.name,
            "lower_s": round(t1 - t0, 3),
            "compile_s": round(t2 - t1, 3),
            "modeled_bytes": self.modeled_bytes,
            # the canonical program identity — what the program-space
            # auditor's static enumeration is held against
            # (analysis/programspace.py parity check)
            "program_key": program_key_of(self.name, args,
                                          self.donate_argnums),
        }
        fields.update(cost_summary(compiled))
        fields.update(memory_summary(compiled))
        peak = fields.get("peak_bytes")
        undershoot = False
        if peak is not None and self.modeled_bytes:
            fields["model_delta_bytes"] = int(peak - self.modeled_bytes)
            fields["model_actual_ratio"] = round(
                peak / self.modeled_bytes, 3)
            undershoot = (
                peak > self.modeled_bytes * self.UNDERSHOOT_WARN_RATIO
                and peak - self.modeled_bytes
                > self.UNDERSHOOT_WARN_MIN_BYTES)
        flops = fields.get("flops")
        msg = (f"compile {self.name}: lower {fields['lower_s']}s + "
               f"compile {fields['compile_s']}s, "
               f"flops={flops:.3g} " if flops is not None else
               f"compile {self.name}: lower {fields['lower_s']}s + "
               f"compile {fields['compile_s']}s, flops=? ")
        msg += (f"peak={_fmt_bytes(peak)} "
                f"(modeled {_fmt_bytes(self.modeled_bytes)})")
        emit("compile", msg, console=self.verbose, **fields)
        if undershoot:
            emit("compile",
                 f"memory plan undershoots XLA actual for "
                 f"{self.name}: modeled "
                 f"{_fmt_bytes(self.modeled_bytes)} < actual "
                 f"{_fmt_bytes(peak)} "
                 f"({fields['model_actual_ratio']:.2f}x) — the "
                 f"autopilot's budget accounting is missing a term",
                 warning=True, name=self.name)
        self.cost = fields
        self._compiled = compiled
        self._lowered = lowered

    def instruction_scopes(self) -> Optional[Dict[str, Any]]:
        """``{"module", "scopes": {instruction name: op_name},
        "map_from", "text_bytes"}`` of the program this observer runs
        (``obs/scopes.py parse_program_text``): what joins a device
        trace, whose events are named by instruction, to the program
        scopes.  None before the first call and after a degrade.
        Nothing on the hot path calls this; it costs nothing until
        asked.

        ``map_from`` is ``"loaded"`` when the running executable's own
        text carries the scopes.  A persistent-cache entry written
        before a scope existed is served unchanged (metadata is not in
        the cache key), and its text has none: the same lowered program
        is then compiled once more past the cache
        (:func:`compile_text_uncached` — not loaded, so it needs no
        device memory beside the live programs) and ``map_from`` is
        ``"recompiled"``.  One ``compile`` event says which."""
        if self._compiled is None:
            return None
        t0 = time.perf_counter()
        try:
            text = self._compiled.as_text() or ""
        except Exception:  # noqa: BLE001 - introspection is best-effort
            text = ""
        map_from = "loaded"
        if not has_scopes(text):
            try:
                with Heartbeat(f"scopes:{self.name}"):
                    text = compile_text_uncached(self._lowered)
                map_from = "recompiled"
            except Exception as e:  # noqa: BLE001 - best-effort too
                emit("compile",
                     f"instruction scopes of {self.name}: the loaded "
                     f"program's text has no roc. scope and the "
                     f"uncached compile failed: {type(e).__name__}: "
                     f"{str(e)[:300]}", warning=True, name=self.name)
        got = parse_program_text(text)
        got.update(map_from=map_from, text_bytes=len(text))
        took = time.perf_counter() - t0
        emit("compile",
             f"instruction scopes of {self.name}: {map_from}, "
             f"{len(got['scopes'])} instructions, {took:.1f}s",
             console=self.verbose, name=self.name, scopes_from=map_from,
             scopes_s=round(took, 3), instructions=len(got["scopes"]))
        return got

    def _degrade(self, e: BaseException):
        self._degraded = True
        self._compiled = None
        self._lowered = None
        emit("compile",
             f"compile observer disabled for {self.name}: "
             f"{type(e).__name__}: {e}",
             console=self.verbose, name=self.name, degraded=True)

    def __call__(self, *args):
        if self._degraded:
            return self._jit(*args)
        if self._compiled is None:
            # ONLY the observation may degrade.  The executions below
            # stay outside the degrade path: their failures are the
            # step's own (and with donated args a retry through
            # self._jit could consume already-deleted buffers and mask
            # the real error).
            try:
                self._observe(args)
            except Exception as e:  # noqa: BLE001 - degrade, not die
                self._degrade(e)
                return self._jit(*args)
            return self._compiled(*args)
        try:
            # steady state: no per-step signature walk — the AOT
            # executable validates avals itself, far cheaper than a
            # host-side pytree compare in the very loop this observer
            # exists to measure
            return self._compiled(*args)
        except (TypeError, ValueError) as e:
            # aval/binding mismatch (new shapes/dtypes): raised before
            # any execution, args intact — re-observe once under the
            # new signature.  Device-side failures (JaxRuntimeError)
            # propagate untouched above.
            try:
                self._observe(args)
            except Exception:  # noqa: BLE001 - degrade on the ORIGINAL
                self._degrade(e)
                return self._jit(*args)
            return self._compiled(*args)
