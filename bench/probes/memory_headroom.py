"""Probe ``memory_headroom``: which memory number is real?

Three sources disagreed about one Reddit-shape train step (PERF.md, PR
21): the program's plan (2.24 GiB), XLA's ``memory_analysis``
(1.28 GB arguments + 12.39 GB temporaries) and
``memory_stats()["peak_bytes_in_use"]`` (1.42 GB).  This probe asks the
chip itself, on the live trainer, after the measurements:

1. *idle headroom*: allocate 1 GiB buffers until allocation fails.  If
   the allocator's ``bytes_in_use`` is the truth while nothing runs,
   the count matches ``bytes_limit - bytes_in_use``.
2. *headroom while a step runs*: hold ``H`` GiB and run the eval
   program, then one train step, for rising ``H``; the largest ``H``
   under which each still runs bounds what the program really needs:
   ``bytes_limit - H - bytes_in_use`` is at least its live temporaries.
   If XLA's 12.4 GB of temporaries were all live, the step would fail
   with more than ~2 GiB held.

Run it with ``--probe memory_headroom`` (it prints a ``{"probe": ...}``
line).  A failed train step may have consumed its donated buffers, so
the probe runs last and the trainer is not used after it.
"""

from __future__ import annotations

from typing import Any, Dict, List

from harness import device

GIB = 1 << 30
HOLD_GIB = (1, 2, 4, 6, 8, 10, 11, 12, 13)


def _stats(dev) -> Dict[str, int]:
    return device.memory_stats([dev])[0] or {}


def _hold(n_gib: int, dev) -> List[Any]:
    """``n_gib`` buffers of 1 GiB on ``dev``, or as many as fit."""
    import jax
    import jax.numpy as jnp
    held: List[Any] = []
    try:
        for _ in range(n_gib):
            held.append(jax.block_until_ready(jax.device_put(
                jnp.zeros((GIB,), dtype=jnp.uint8), dev)))
    except Exception:  # noqa: BLE001 - RESOURCE_EXHAUSTED ends the fill
        pass
    return held


def probe(run) -> Dict[str, Any]:
    dev, tr = run.devs[0], run.trainer
    out: Dict[str, Any] = {"before": _stats(dev)}
    held = _hold(64, dev)
    out["idle_allocatable_gib"] = len(held)
    out["idle_stats_full"] = _stats(dev)
    del held
    rows = []
    eval_alive = train_alive = True
    for h in HOLD_GIB:
        if not (eval_alive or train_alive):
            break
        held = _hold(h, dev)
        row: Dict[str, Any] = {"asked_gib": h, "held_gib": len(held)}
        if eval_alive:
            try:
                tr.evaluate()
                row["eval"] = "ran"
            except Exception as e:  # noqa: BLE001 - the finding itself
                row["eval"] = f"{type(e).__name__}: {str(e)[:160]}"
                eval_alive = False
        if train_alive:
            try:
                tr.train(epochs=1)
                tr.sync()
                row["train"] = "ran"
            except Exception as e:  # noqa: BLE001
                row["train"] = f"{type(e).__name__}: {str(e)[:160]}"
                train_alive = False   # donated buffers may be gone
        row["stats"] = _stats(dev)
        rows.append(row)
        del held
    out["held_while_running"] = rows
    out["after"] = _stats(dev)
    return out
