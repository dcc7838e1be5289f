"""``agg_slot_roundtrip_share`` (``aggregation`` layer, %): of the slots
the sum chunk scans gather a pass, the share whose gathered rows still
make a round trip through HBM — written there by the gather and read
back by a separate sum — rather than being summed by the kernel that
holds the table in VMEM (``roc_tpu/ops/aggregate.py gather_sum_form``):
100 x the sum of ``slots`` over the tables whose form is ``two_pass``
over the sum of all ``slots`` of the ``plan`` line's
``agg_gather_sum``, one ``[form, slots]`` a table the scans walk.  100
on a program that scans chunks and has no such key (a parent commit),
so both sides of a pair read the same thing.  A count made by the
program on the host, so a rehearsal reads it too.  A program that
scans no chunk (attention alone, the bucketed ``ell`` layout, the
edge-list reference) gives nothing to read."""


def read(run):
    resolved = run.scratch.get("resolved") or {}
    forms = resolved.get("agg_gather_sum")
    if forms:
        slots = sum(n for _, n in forms)
        return (100.0 * sum(n for f, n in forms if f == "two_pass")
                / slots if slots else None)
    scans = resolved.get("agg_chunk_rows") or any(
        layer.get("slots_fwd")
        for layer in resolved.get("rel_layers") or ())
    return 100.0 if scans else None
