"""Rules over the optimized HLO text + ``cost_analysis`` capture.

The compile observer (obs/compile_watch.py) already lowers and
compiles the step AOT; this layer inspects what XLA actually built:

- [hlo-large-copy] ``copy`` / ``transpose`` instructions materializing
  activation-scale ([V, F]) FLOAT tensors OUTSIDE fusions — each one
  is a full HBM round trip the fusion pipeline failed to elide (layout
  mismatches at custom-call/donation boundaries are the usual cause).
  Integer tensors are not activations: XLA:CPU's rolled threefry loop
  copies its [V, F] u32 dropout state between rounds, which says
  nothing about the step's data movement.
- [hlo-bytes-model] executable-level ``bytes accessed`` exceeding the
  core/memory.py plan estimate by a configurable factor — the static
  analog of ObservedJit's modeled-vs-actual warning, catching
  catastrophic traffic blowups (an accidental [V, V] materialization,
  a gather that stopped fusing) before a chip run pays for them.
"""

from __future__ import annotations

import re
from typing import List, Optional

from .findings import Finding

# `  %x.1 = f32[192,48]{1,0} copy(...)` / `transpose(`; shape groups:
# dtype, comma-dims
_COPY_RE = re.compile(
    r"=\s*([a-z][a-z0-9]*)\[([0-9,]*)\][^ ]*\s+(copy|transpose)\(")
# computation headers: `%fused_computation.3 (param_0: ...) -> ... {`
# and `ENTRY %main ... {`
_COMP_RE = re.compile(r"^\s*(ENTRY\s+)?%?([\w.\-]+)\s*\([^)]*\)\s*->")


def _shape_elems(dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n


def check_large_copy(unit: str, hlo_text: str, copy_min_elems: int
                     ) -> List[Finding]:
    """Flag un-fused copy/transpose of float tensors >=
    ``copy_min_elems`` elements.  Instructions inside
    ``fused_computation`` bodies are skipped — there the transpose is
    folded into the fusion's reads/writes, not a separate
    materialization."""
    out: List[Finding] = []
    in_fusion = False
    for line in hlo_text.splitlines():
        header = _COMP_RE.match(line)
        if header and line.rstrip().endswith("{"):
            in_fusion = "fused" in header.group(2)
            continue
        if in_fusion:
            continue
        m = _COPY_RE.search(line)
        if not m:
            continue
        dtype, dims, op = m.groups()
        n = _shape_elems(dims)
        if dtype.startswith(("f", "bf")) and n >= copy_min_elems:
            out.append(Finding(
                "hlo-large-copy", unit,
                f"un-fused {op} materializes {dtype}[{dims}] "
                f"({n} elems >= activation scale {copy_min_elems}) — "
                f"a full HBM round trip the fusion pipeline missed",
                key=f"{op}|{dtype}[{dims}]"))
    return out


def check_bytes_model(unit: str, bytes_accessed: Optional[float],
                      modeled_bytes: Optional[int],
                      factor: float = 32.0) -> List[Finding]:
    """Flag executables whose measured traffic exceeds ``factor`` x
    the memory model's step estimate.  The factor is deliberately
    loose: bytes-accessed counts every pass over every buffer, so
    legitimate multi-pass aggregation runs a small multiple of
    residency — only order-of-magnitude blowups indicate a
    materialization bug."""
    if not bytes_accessed or not modeled_bytes:
        return []    # introspection unavailable: nothing to hold
    if bytes_accessed <= factor * modeled_bytes:
        return []
    return [Finding(
        "hlo-bytes-model", unit,
        f"bytes accessed {bytes_accessed:.3g} exceeds {factor:g}x the "
        f"core/memory.py estimate ({modeled_bytes} B) — the step is "
        f"moving far more data than the plan modeled",
        key="bytes-model")]
