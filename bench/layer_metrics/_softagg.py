"""Shared by the DeeperGCN cell's readers (``bn_ms``, ``bn_roofline``,
``softagg_ms``, ``softagg_roofline``): the bytes a batch normalization
and a softmax-weighted aggregation need, from shapes alone, and what
the program's ``plan`` line says it resolved them to.

The shapes come from the program (the ``resolved`` block of its
manifest: ``batch_norm`` — count, width, rows counted — and
``soft_aggregate`` — ops, width, the lanes a forward pass gathers, how
many such passes, the dtype of the gathered table), because whether
numerator and denominator ride one gather or two, and in which dtype,
is the program's resolution, not the reader's.  A program whose plan
has neither key (a parent commit, any other model) gives nothing to
read."""

HBM_ROW_INDEX_BYTES = 4
DEN_BYTES = 4                  # the denominator the backward keeps


def batch_norm_bytes(rows, width, itemsize, count):
    """A train step's ``count`` batch normalizations over ``[rows,
    width]`` with NO reuse: the forward reads ``x`` twice (the moments,
    then the normalization) and writes ``y`` once, the backward reads
    ``x`` and the cotangent twice each (its two sums, then ``dx``) and
    writes ``dx`` once — eight passes over the array at the compute
    dtype; the ``[width]`` vectors weigh nothing."""
    return 8 * rows * width * itemsize * count


def soft_aggregation_bytes(num_edges, num_nodes, width, lanes, passes,
                           itemsize, table_itemsize):
    """The forward of ONE softmax-weighted aggregation with NO reuse of
    a gathered row.  The sum passes (``passes`` of them at ``lanes``
    lanes each, together the ``[V, 2 * width]`` table ``[e * m, e]``):
    per stored edge a row at the gathered lanes and a 4-byte index, per
    vertex the summed row read and written once
    (``roofline.aggregation_bytes``' model).  Around them, what
    ``roc.sagg.weights`` reads and writes a vertex: ``z`` for the
    per-channel shift, ``z`` again for the table, the table written;
    numerator and denominator read back, ``z`` read, the output written
    and the float32 denominator kept."""
    table = passes * lanes * table_itemsize            # 2 * width wide
    scan = (passes * num_edges * (lanes * table_itemsize
                                  + HBM_ROW_INDEX_BYTES)
            + 2 * num_nodes * table)
    around = num_nodes * (3 * width * itemsize + 2 * table
                          + width * itemsize + width * DEN_BYTES)
    return scan + around


def plan(run, key):
    """The ``batch_norm`` / ``soft_aggregate`` entry of the program's
    ``plan`` line, or None."""
    return (run.scratch.get("resolved") or {}).get(key) or None


def soft_rows(run, ways):
    """``[(op, way, ms), ...]``: the ``agg`` rows of the step-scope
    reduction that belong to the softmax aggregations, in the
    directions ``ways``; None when there is nothing to read."""
    soft = plan(run, "soft_aggregate")
    got = run.cell.module("layer_metrics", "_step_scopes").measure(run)
    if soft is None or got is None:
        return None
    ops = set(soft["ops"])
    return [(i, way, ms) for cls, i, way, ms, _ in got["rows"]
            if cls == "agg" and i in ops and way in ways]
