"""``agg_carry_update_share`` (``aggregation`` layer, %): of the rows
the sum chunk scans would add into their carries one by one — every
sub-row of every chunk a serial scatter update — the share they still
add after the program's segmented sum (``roc_tpu/ops/aggregate.py
scan_seg_sum``) has summed a destination row's sub-rows on the MXU:
100 x the sum of ``after`` over the sum of ``before`` of the ``plan``
line's ``agg_carry_updates``, one ``[before, after]`` a section of the
tables the scans walk.  100 where the rule engaged on no section; 100
too on a program that scans chunks and has no such key (a parent
commit), so the metric reads the same thing on both sides.  A count
made by the program on the host, so a rehearsal reads it too.  A
program that scans no chunk (attention alone, the bucketed ``ell``
layout, the edge-list reference) gives nothing to read."""


def read(run):
    resolved = run.scratch.get("resolved") or {}
    updates = resolved.get("agg_carry_updates")
    if updates:
        before = sum(b for b, _ in updates)
        return 100.0 * sum(a for _, a in updates) / before if before else None
    scans = resolved.get("agg_chunk_rows") or any(
        layer.get("slots_fwd")
        for layer in resolved.get("rel_layers") or ())
    return 100.0 if scans else None
