"""Run manifest: the event that makes a run self-describing.

Emitted once at trainer setup (both trainers), up front in the event
stream: what code (git sha, jax version), what hardware (device
topology), what data (V/E/name), and — most importantly — what the
framework DECIDED (resolved ``aggr_impl``/``aggr_fuse``/halo/
features/remat, memory-plan echo, bdense occupancy).  The scattered
stderr echoes stay (console sink), but the manifest is the one record
a post-mortem can trust to describe the run that actually executed,
not the flags that were requested.
"""

from __future__ import annotations

import os
from typing import Any, Dict, Optional

from .events import _jsonable, emit

_REPO_ROOT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", ".."))


def git_sha(repo_root: str = _REPO_ROOT) -> Optional[str]:
    """HEAD commit sha without shelling out (works in sandboxes where
    git itself is absent); None when not a git checkout."""
    try:
        head_path = os.path.join(repo_root, ".git", "HEAD")
        with open(head_path) as f:
            head = f.read().strip()
        if head.startswith("ref:"):
            ref = head.split(None, 1)[1]
            ref_path = os.path.join(repo_root, ".git", *ref.split("/"))
            if os.path.exists(ref_path):
                with open(ref_path) as f:
                    return f.read().strip()
            packed = os.path.join(repo_root, ".git", "packed-refs")
            with open(packed) as f:
                for line in f:
                    if line.strip().endswith(ref):
                        return line.split()[0]
            return None
        return head
    except OSError:
        return None


def _config_dict(config) -> Dict[str, Any]:
    import dataclasses
    d = dataclasses.asdict(config)
    # dtypes serialize by name, not repr of the type object
    for k in ("dtype", "compute_dtype"):
        if d.get(k) is not None:
            try:
                import jax.numpy as jnp
                d[k] = str(jnp.dtype(d[k]))
            except Exception:  # noqa: BLE001 - name is best-effort
                d[k] = str(d[k])
    return _jsonable(d)


def run_manifest(config=None, dataset=None, model=None,
                 num_parts: int = 1,
                 extra: Optional[Dict[str, Any]] = None,
                 agg_window: Optional[Dict[str, Any]] = None,
                 console: bool = True) -> Dict[str, Any]:
    """Assemble + emit the ``manifest`` event; returns the fields.
    ``agg_window`` (``GraphContext.agg_window()``, and with it
    ``GraphContext.attention_plan()``) joins ``resolved``: the chunk
    scan's window rows per section and the carry's height, so a run
    says how far the windowed scatter engaged; ``agg_lane_pad``, one
    ``[op, F, Fp]`` per sum-aggregating op (the model's width and the
    lane-padded width its scan runs at); ``agg_chunk_rows``, one
    ``[n_chunks, seg_rows]`` per section of the sum scan's tables, and
    ``agg_slot_fill``, stored edges over the slots a pass gathers (how
    far ``core/ell.py fit_chunks`` engaged); ``agg_seg_sum``, the
    ``[T, B]`` (or null) each scanned table's chunk step sums its
    partials at on the MXU, and ``agg_carry_updates``, ``[before,
    after]`` rows a pass adds into its carry without and with it (how
    far ``ops/aggregate.py scan_seg_sum`` engaged); ``agg_gather_sum``,
    ``[form, slots]`` a scanned table (``"fused"``: the kernel sums a
    sub-row's 8 slots in VMEM; ``"two_pass"``: they go through HBM;
    ``ops/aggregate.py gather_sum_form``); one ``attention`` entry
    per attention op (heads, head width, layout, passes over the edge
    tables, slots a pass, carry rows) and one ``attention_backward``
    entry (the gradient rule, its edge passes, the whole-array
    cotangents it scatters into); and ``memory_plan``
    (``train/trainer.py modeled_plan``): the memory plan's estimate
    for the resolved configuration by component (parameters + Adam,
    features, tables, kept activations, transient), what each model op
    was charged (``[op, kind, arrays, bytes per vertex row]``), remat
    and the runs it computes again, and the model's depth
    (aggregating ops, ``linear`` ops).

    Everything is best-effort: a missing backend or detached checkout
    degrades to nulls, never to an exception at trainer setup."""
    fields: Dict[str, Any] = {"git_sha": git_sha()}
    try:
        import jax
        fields["jax_version"] = jax.__version__
        fields["process_index"] = jax.process_index()
        fields["process_count"] = jax.process_count()
        # pin the clock tuple's proc for every later event: the env
        # default (JAX_PROCESS_ID) is right under explicit launchers,
        # but jax's own process_index is authoritative once known
        from .events import set_clock_identity
        set_clock_identity(proc=fields["process_index"])
        devs = jax.devices()
        fields["device_count"] = len(devs)
        fields["platform"] = devs[0].platform if devs else None
        fields["device_kinds"] = sorted(
            {d.device_kind for d in devs})
    except Exception as e:  # noqa: BLE001 - backendless manifest
        fields["backend_error"] = repr(e)
    # which host data path this run is on: the native library decides
    # what aggr_impl='auto' can choose (native.py status)
    from .. import native
    fields["native"] = native.status()
    if config is not None:
        fields["config"] = _config_dict(config)
        fields["resolved"] = {
            "aggr_impl": getattr(config, "aggr_impl", None),
            "aggr_fuse": getattr(config, "aggr_fuse", None),
            "halo": getattr(config, "halo", None),
            "features": getattr(config, "features", None),
            "remat": getattr(config, "remat", None),
            "num_parts": num_parts,
            **(agg_window or {}),
        }
    if dataset is not None:
        g = dataset.graph
        fields["dataset"] = {"name": dataset.name,
                             "num_nodes": int(g.num_nodes),
                             "num_edges": int(g.num_edges),
                             "num_classes": int(dataset.num_classes)}
    if model is not None:
        try:
            fields["model"] = {
                "ops": [op.kind for op in model._ops],
                "fused_aggregates": model.num_fused_aggregates(),
            }
        except Exception:  # noqa: BLE001 - shape of _ops may evolve
            pass
    if extra:
        fields.update(_jsonable(extra))
    msg = (f"run manifest: platform={fields.get('platform')} "
           f"devices={fields.get('device_count')} "
           f"jax={fields.get('jax_version')} "
           f"sha={(fields.get('git_sha') or 'none')[:12]}")
    emit("manifest", msg, console=console, **fields)
    return fields
