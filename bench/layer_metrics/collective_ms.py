"""``collective_ms`` (``distribution`` layer, ms): device time per epoch in
which a collective ran or was in flight — the operations whose name or
HLO category is a collective (all-gather, all-reduce, reduce-scatter,
collective-permute, all-to-all) and the ``-start``..``-done`` spans of
the async line — on the chip that spends most in them.  Source: the
device trace of the traced stretch."""

from harness import trace


def read(run):
    if run.trace is None or not run.trace_epochs:
        return None
    got = trace.collectives(run.trace)
    return None if got is None else (
        got["collective_s"] * 1e3 / run.trace_epochs)
