"""Tracing / profiling / observability subsystem.

The reference has no profiling subsystem of its own — only Legion log
categories and commented-out ``Realm::Clock`` micro-timers
(``activation_kernel.cu:40,62-63``, ``gnn.cc:796-805``; SURVEY.md §5
calls this a gap to fill, not copy).  The TPU-native equivalents:

- :func:`trace` — context manager around ``jax.profiler`` emitting a
  TensorBoard/Perfetto trace directory (the analog of Legion's
  ``-lg:prof`` logs).
- :class:`annotate` — ``jax.profiler.TraceAnnotation`` wrapper: a named
  span on the trace's HOST plane, on the profiler's clock.  What the
  device did is not named by it: on this stack (jax 0.9, libtpu 0.0.34)
  the device plane's ``XLA Ops`` events are named by HLO instruction
  (``%fusion.28 = ...``) and carry no scope or category.  Which model
  op an instruction belongs to is in the compiled program's text; the
  CLI writes that map beside a ``--profile-dir`` trace as
  ``scopes.<program>.json`` (``obs/scopes.py``,
  ``ObservedJit.instruction_scopes``).
- :class:`EpochTimer` — honest wall-clock epoch timing, plus named
  per-phase spans (train burst / eval / streamed-head sub-phases)
  recorded with the same barrier (:func:`sync`).
- :class:`MetricsLog` — structured training-metrics history with JSONL
  export; the rebuild of the reference's stdout-only ``PerfMetrics``
  prints (``softmax_kernel.cu:141-152``) as a queryable artifact.

The structured event bus lives in ``roc_tpu/obs`` — this module stays
the low-level timing layer it feeds.
"""

from __future__ import annotations

import contextlib
import json
import time
from dataclasses import dataclass, field
from typing import Any, Dict, Iterator, List, Optional

import numpy as np


@contextlib.contextmanager
def trace(log_dir: Optional[str]) -> Iterator[None]:
    """Profile the enclosed block into ``log_dir`` (TensorBoard trace
    format).  No-op when ``log_dir`` is falsy, so call sites can thread
    a config value through unconditionally."""
    if not log_dir:
        yield
        return
    import jax
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def annotate(name: str):
    """Named span in profiler traces (forward/backward/update/eval)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


def sync(x: Any) -> None:
    """Device barrier: block until every array in the pytree ``x`` is
    computed (chip_smoke.py checks it against a host fetch)."""
    import jax
    jax.block_until_ready(x)


@dataclass
class EpochTimer:
    """Wall-clock per-epoch timer with warmup separation and named
    per-phase spans.

    The first ``warmup`` laps (compile + cache effects) are recorded but
    excluded from the summary statistics.  ``span(name)`` records a
    phase (train burst, eval, halo exchange, streamed head
    forward/wgrad, optimizer update) into its own series — host wall
    time, like :func:`annotate`'s spans on a trace's host plane —
    summarized by :meth:`span_summary` as p50/p90 per phase.
    """

    warmup: int = 1
    laps_ms: List[float] = field(default_factory=list)
    spans_ms: Dict[str, List[float]] = field(default_factory=dict)
    # span-lap records for the cross-process timeline merger
    # (obs/timeline.py): ``(name, mono_start_s, dur_ms)`` per lap,
    # drained by :meth:`take_timeline` into periodic ``timeline``
    # events (train/trainer.py run_epoch_loop)
    timeline: List[tuple] = field(default_factory=list)
    # route spans through jax.profiler.TraceAnnotation too, so a
    # --profile-dir trace carries the same named phases, on its host
    # plane, as the host timeline lanes; off by default (annotate
    # imports jax)
    annotate: bool = False
    _t0: Optional[float] = None

    def start(self) -> None:
        self._t0 = time.perf_counter()

    def stop(self, sync_on: Any = None) -> float:
        assert self._t0 is not None, "start() not called"
        if sync_on is not None:
            sync(sync_on)
        ms = (time.perf_counter() - self._t0) * 1e3
        self.laps_ms.append(ms)
        self._t0 = None
        return ms

    @contextlib.contextmanager
    def lap(self, sync_on: Any = None) -> Iterator[None]:
        self.start()
        try:
            yield
        finally:
            self.stop(sync_on=sync_on)

    @contextlib.contextmanager
    def span(self, name: str, sync_on: Any = None) -> Iterator[None]:
        """Record one lap of the named phase.  To barrier on work
        dispatched INSIDE the span, pass ``sync_on`` as a zero-arg
        callable resolved at span exit (``sync_on=lambda: self.params``)
        — a plain array argument is evaluated at ``with``-entry and can
        only barrier on something that already existed, which is NOT an
        end-of-phase mark for the span's own work.  Independent of the
        epoch lap state: spans may nest inside or across :meth:`lap`
        regions.

        With :attr:`annotate` set, the span body also runs inside a
        ``jax.profiler.TraceAnnotation`` of the same name, so a
        ``--profile-dir`` trace shows, on its host plane and its own
        clock, the phases the host timeline shows.  The device plane
        is not touched by this: its operations are named by HLO
        instruction, and ``scopes.<program>.json`` beside the trace
        maps those to the program scopes (module docstring)."""
        ann = annotate(name) if self.annotate else None
        if ann is not None:
            ann.__enter__()
        mono0 = time.monotonic()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            if sync_on is not None:
                sync(sync_on() if callable(sync_on) else sync_on)
            if ann is not None:
                ann.__exit__(None, None, None)
            ms = (time.perf_counter() - t0) * 1e3
            self.spans_ms.setdefault(name, []).append(ms)
            self.timeline.append((name, mono0, ms))

    def note_span(self, name: str, dur_ms: float,
                  mono_end: Optional[float] = None) -> None:
        """Record a span lap measured OUTSIDE :meth:`span` (the epoch
        loop's compile/train/eval laps, the staging pool's per-block
        waits): appends to both the p50/p90 series and the timeline
        records, with the start back-derived from ``mono_end``."""
        if mono_end is None:
            mono_end = time.monotonic()
        self.spans_ms.setdefault(name, []).append(dur_ms)
        self.timeline.append((name, mono_end - dur_ms / 1e3, dur_ms))

    def take_timeline(self) -> List[tuple]:
        """Drain the accumulated timeline span records (the epoch loop
        flushes them into one ``timeline`` event per eval)."""
        out, self.timeline = self.timeline, []
        return out

    def summary(self) -> Dict[str, float]:
        steady = self.laps_ms[self.warmup:] or self.laps_ms
        arr = np.asarray(steady, dtype=np.float64)
        return {
            "laps": len(self.laps_ms),
            "warmup_ms": float(sum(self.laps_ms[:self.warmup])),
            "mean_ms": float(arr.mean()) if arr.size else 0.0,
            "median_ms": float(np.median(arr)) if arr.size else 0.0,
            "p90_ms": float(np.percentile(arr, 90)) if arr.size else 0.0,
            "min_ms": float(arr.min()) if arr.size else 0.0,
        }

    def span_summary(self) -> Dict[str, Dict[str, float]]:
        """Per-phase ``{n, total_ms, p50_ms, p90_ms}`` over every
        recorded span lap (no warmup exclusion: phases that run once —
        first compile — must still show up)."""
        out: Dict[str, Dict[str, float]] = {}
        for name, laps in self.spans_ms.items():
            arr = np.asarray(laps, dtype=np.float64)
            out[name] = {
                "n": int(arr.size),
                "total_ms": float(arr.sum()),
                "p50_ms": float(np.median(arr)),
                "p90_ms": float(np.percentile(arr, 90)),
            }
        return out


class MetricsLog:
    """Append-only training metrics history with JSONL export.  The
    file handle opens lazily on first :meth:`log` (constructing many
    trainers must not accumulate descriptors).  Context-manager use
    guarantees :meth:`close` on exceptions:

    >>> with MetricsLog(path) as log: ...
    """

    def __init__(self, path: Optional[str] = None):
        self.path = path
        self.records: List[Dict[str, float]] = []
        self._fh = None

    def log(self, record: Dict[str, Any]) -> None:
        rec = {k: (float(v) if isinstance(v, (int, float, np.floating,
                                              np.integer)) else v)
               for k, v in record.items()}
        # clock tuple (obs/events.py): metrics records merge into the
        # same cross-process timeline as the event streams, so they
        # carry the same (wall, monotonic, host, proc) stamps — never
        # overriding fields the caller measured itself
        from ..obs.events import clock_identity
        rec.setdefault("t", round(time.time(), 3))
        rec.setdefault("mono", round(time.monotonic(), 6))
        for k, v in clock_identity().items():
            rec.setdefault(k, v)
        self.records.append(rec)
        if self.path:
            if self._fh is None:
                self._fh = open(self.path, "a")
            self._fh.write(json.dumps(rec) + "\n")
            self._fh.flush()

    def close(self) -> None:
        if self._fh:
            self._fh.close()
            self._fh = None

    def __enter__(self) -> "MetricsLog":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def last(self) -> Optional[Dict[str, float]]:
        return self.records[-1] if self.records else None
