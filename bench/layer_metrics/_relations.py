"""Shared by the typed-graph cell's readers (``rel_agg_roofline``,
``opt_embed_ms``, ``opt_embed_roofline``): the bytes a relation
aggregation and an embedding optimizer step need, from shapes alone,
and device self time under one *named* program scope.

``_step_scopes.py`` reduces the device trace by scope class, op index
and direction.  ``roc.opt.embed`` is a name nested inside the class
``opt`` (``roc_tpu/obs/scopes.py``), so its time is a second walk over
the same join — that module's ``module_intervals`` and instruction ->
``op_name`` map, filtered on the name.  A program without
``instruction_scopes``, or without the name anywhere in its text (a
parent commit, a model with no embedding table), gives nothing to
read."""

HBM_ROW_INDEX_BYTES = 4
WEIGHT_BYTES = 4
# Adam over one fp32 parameter under --dtype mixed: read w, g, m, v,
# write w, m, v, and write the bfloat16 compute copy
ADAM_MIXED_BYTES = 4 * 4 + 3 * 4 + 2


def relation_aggregation_bytes(num_edges, width, itemsize, out_rows):
    """``out[row] = sum over relation edges of weight * x[src]`` with NO
    reuse of a gathered row: every stored relation edge reads one source
    row at the gathered width, its 4-byte index and its 4-byte weight;
    every row the pass sums into is written once."""
    return (num_edges * (width * itemsize + HBM_ROW_INDEX_BYTES
                         + WEIGHT_BYTES)
            + out_rows * width * itemsize)


def embedding_adam_bytes(embedding_rows, width):
    return embedding_rows * width * ADAM_MIXED_BYTES


def named_scope_ms(run, needle):
    """Per-epoch, per-chip device self time inside the train step of
    the operations whose ``op_name`` contains ``needle``; None when
    there is nothing to read."""
    key = ("named_scope_ms", needle)
    if key in run.scratch:
        return run.scratch[key]
    run.scratch[key] = out = _named_scope_ms(run, needle)
    return out


def _named_scope_ms(run, needle):
    ask = getattr(getattr(run.trainer, "_train_step", None),
                  "instruction_scopes", None)
    if (ask is None or run.trace is None or not run.trace_epochs
            or not run.scratch.get("xplane")):
        return None
    got = ask()
    if got is None or not any(needle in v for v in got["scopes"].values()):
        return None
    from harness import trace
    ss = run.cell.module("layer_metrics", "_step_scopes")
    inside = ss.module_intervals(run.scratch["xplane"], got["module"])
    total = 0
    for chip, ops in run.trace.chips.items():
        spans = inside.get(chip, [])
        starts = [lo for lo, _ in spans]
        for op in ops:
            if not ss._inside(spans, starts, op):
                continue
            m = trace.HLO_TEXT.match(op.name)
            name = got["scopes"].get(m.group(1) if m else op.name) or ""
            if needle in name:
                total += op.self_ns
    return (total * 1e-6 / max(len(run.trace.chips), 1)
            / max(run.trace_epochs, 1))


def relation_layers(run):
    """The ``rel_layers`` of the program's ``plan`` line (the
    ``resolved`` block of its manifest) and ``resolved`` itself; (None,
    None) for a program that resolves no relation."""
    resolved = run.scratch.get("resolved") or {}
    return resolved.get("rel_layers") or None, resolved
