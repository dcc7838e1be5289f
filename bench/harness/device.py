"""The device as JAX reports it, and the rule that a measurement path
which finds no chip fails.

``--rehearsal`` is for this benchmark's own tests: it holds JAX to the
CPU (with as many virtual devices as the cell has chips) and runs the
same control flow, but then the platform reads ``cpu`` and every timing
and memory value in the result is ``null`` — a CPU number never sits
under a device metric's name.
"""

from __future__ import annotations

import os
import sys
from typing import Any, Dict, List, Optional


class NoChip(Exception):
    """JAX found no accelerator, or fewer chips than the cell asks."""


def claim(chips: int, rehearsal: bool) -> List[Any]:
    """First JAX use of the process: returns the ``chips`` devices the
    cell runs on, or raises :class:`NoChip`."""
    if rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{max(chips, 1)}").strip()
    import jax
    if rehearsal:
        jax.config.update("jax_platforms", "cpu")
    try:
        devs = jax.devices()
    except RuntimeError as e:      # no backend at all
        raise NoChip(f"JAX found no device: {e}") from e
    plat = devs[0].platform
    if plat == "cpu" and not rehearsal:
        raise NoChip(f"no accelerator: JAX found platform {plat!r} "
                     f"({devs[0].device_kind!r} x {len(devs)}); nothing "
                     f"was run")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chip(s), JAX found "
                     f"{len(devs)} ({plat})")
    return devs[:chips]


def describe(devs: List[Any], memory_peak_bytes: Optional[int]
             ) -> Dict[str, Any]:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": memory_peak_bytes}


def memory_stats(devs: List[Any]) -> List[Optional[Dict[str, int]]]:
    """Per device, every integer the allocator reports
    (``bytes_in_use``, ``peak_bytes_in_use``, ``bytes_limit``, ...), or
    None where the backend reports none (the CPU)."""
    out: List[Optional[Dict[str, int]]] = []
    for d in devs:
        s = d.memory_stats()
        out.append(None if not s else {
            k: int(v) for k, v in s.items() if isinstance(v, int)})
    return out


def peak_bytes(stats: List[Optional[Dict[str, int]]]) -> Optional[int]:
    """The peak on the fullest chip: the allocator's
    ``peak_bytes_in_use`` PLUS its ``peak_bytes_reserved``.

    ``peak_bytes_in_use`` counts buffers (arguments, outputs, what the
    host put there).  It does not count the region a TPU program
    reserves "at the bottom of memory" for its temporaries while it is
    loaded; the allocator reports that apart, as ``bytes_reserved``.
    PR 22's probe (``probes/memory_headroom.py``) showed the region is
    real: a Reddit-shape step with 1.4 GB "in use" refused to load once
    4 GiB more were held — "Attempting to reserve 11.71G at the bottom
    of memory ... There are 10.53G free".  What is left for anything
    else is ``bytes_limit`` less both, which is what
    ``largest_free_block_bytes`` shows.  The two peaks need not have
    been reached at the same instant, so the sum can overstate a
    little; where a backend reports no reservation it adds 0."""
    peaks = [s["peak_bytes_in_use"] + s.get("peak_bytes_reserved", 0)
             for s in stats if s and "peak_bytes_in_use" in s]
    return max(peaks) if peaks else None


def say(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
