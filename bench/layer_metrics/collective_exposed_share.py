"""``collective_exposed_share`` (``distribution`` layer, %): the part of
the collectives' device time during which no other operation ran on
that chip, over the traced stretch's length — the share of the epoch
the halo exchange and gradient reduction cost when nothing hides
them.  Source: the device trace."""

from harness import trace


def read(run):
    if run.trace is None or run.trace_window_s <= 0:
        return None
    got = trace.collectives(run.trace)
    return None if got is None else (
        100.0 * got["exposed_s"] / run.trace_window_s)
