"""Operations and bytes the algorithm needs, from shapes alone; divided
by the chip's published peak (``peaks.json``) they give the least time a
call can take, and over the measured time, its share of the roofline.
"""

from __future__ import annotations


def aggregation_bytes(num_edges: int, num_nodes: int, width: int,
                      itemsize: int) -> int:
    """Neighbour aggregation ``out[v] = sum_{(u,v)} w_uv x[u]`` with NO
    reuse of a gathered row: every stored edge reads one source row
    (``width * itemsize`` bytes) and one 4-byte index, and every output
    row is read and written once (``2 * V * width * itemsize``).  The
    arithmetic (2 * E * width FLOP) is three orders of magnitude below
    the chip's FLOP peak at these sizes, so HBM bounds the op.  A layout
    that reuses gathered rows from fast memory moves fewer bytes than
    this, and may show a share above 100%."""
    return (num_edges * (width * itemsize + 4)
            + 2 * num_nodes * width * itemsize)


def least_seconds(num_bytes: float, flops: float, peaks: dict) -> float:
    """The larger of bytes over peak bandwidth and operations over peak
    FLOP/s."""
    return max(num_bytes / peaks["hbm_bytes_per_s"],
               flops / peaks["bf16_flops_per_s"])
