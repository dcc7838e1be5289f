"""``device_idle_share`` (``device`` layer, %): over the traced stretch,
1 - (union of the intervals in which an operation ran on the chip) /
(length of the stretch on the host's clock), on the most idle chip.
Source: the profiler's device trace (``harness/trace.py``)."""

from harness import trace


def read(run):
    if run.trace is None:
        return None
    share = trace.idle_share(run.trace, run.trace_window_s)
    return None if share is None else 100.0 * share
