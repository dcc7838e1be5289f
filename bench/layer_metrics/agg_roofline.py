"""``agg_roofline`` (``aggregation`` layer, %): the least time the chip
could take for one forward aggregation by the no-reuse byte model
(``roofline.aggregation_bytes`` at the peak HBM bandwidth of
``peaks.json``), over the forward's measured time.  HBM bounds it.  A
layout that reuses gathered rows from fast memory moves fewer bytes
than the model, and may exceed 100%."""


def read(run):
    got = run.cell.module("layer_metrics", "_aggregation").measure(run)
    if got is None or run.peaks is None:
        return None
    import roofline
    nbytes = roofline.aggregation_bytes(
        got["num_edges"], got["num_nodes"], got["width"], got["itemsize"])
    flops = 2.0 * got["num_edges"] * got["width"]
    least_ms = roofline.least_seconds(nbytes, flops, run.peaks) * 1e3
    return 100.0 * least_ms / got["forward_ms"]
