"""Ring halo exchange: blocked all-gather overlapped with aggregation.

The reference materializes the WHOLE node-feature region on every GPU
for each aggregation (``scattergather.cc:70-72``; explicitly
``ncclAllGather`` in the vestigial ``gnn_kernel.cu:65-78``), which caps
graph size at one device's memory.  SURVEY §7 flags the TPU fix: a ring
schedule that never holds more than one shard's features at a time.

Mechanism (the ring-attention communication shape, with CSR aggregation
as the local op): each device keeps a rotating buffer of one shard's
features.  At ring step k, device p holds shard ``(p - k) mod P``; it
aggregates the local edges whose *sources* live in that shard into its
running output, while ``lax.ppermute`` rotates the buffer one hop
around the ICI ring.  After P steps every edge has been applied exactly
once and peak memory is O(V/P * F) instead of O(V * F).

Per-(partition, source-shard) edge groups are stored as FLAT dst-sorted
edge lists padded to the max pair edge count — SPMD needs identical
shapes on every device, and for edge-balanced partitions of power-law
graphs this pads ~1.5-1.7x (the build reports the padding ratio as
``ring_padding_ratio``; a uniform per-pair ELL layout was measured at
~8x on the same graphs and replaced by this one).  The tables are built
part by part from these helpers by ``models/tables.py build_tables``
(layout ``'ring'``).  The per-step local op is a
chunked gather + sorted scatter-add — padding edges gather the zero row
into the last output row, so they are numeric no-ops.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core.partition import PartitionedGraph
from ..obs.scopes import HALO_SCOPE
from . import PARTS_AXIS


def ring_hop_perm(num_shards: int):
    """THE named hop schedule: one step of the ring rotation as a
    ``lax.ppermute`` permutation — ``[(i, (i+1) % S)]``, a single
    cycle covering the full axis.  :func:`ring_aggregate` issues
    exactly this permutation every hop, and the SPMD collective
    verifier (``analysis/collective_lint.py``) recovers and checks the
    traced ``ppermute`` eqns against it: any other shape (a two-cycle,
    a partial cover) deadlocks or drops shards at P>=2 on real
    hardware, where no trace-time error exists to catch it."""
    return [(i, (i + 1) % num_shards) for i in range(num_shards)]


def build_ring_pairs(pg: PartitionedGraph, p: int,
                     col: Optional[np.ndarray] = None) -> dict:
    """Partition ``p``'s per-source-shard edge lists, built from ``p``'s
    OWN column data only: ``{s: (src_local_to_shard_s, dst_local)}``
    with dst sorted ascending within each pair.  ``col`` overrides the
    column array (multi-host partition-local loading passes the slice
    it read; global ids, NOT padded-remapped); default reads
    ``pg.part_col_idx``."""
    P = pg.num_parts
    offsets = np.asarray([l for l, _ in pg.bounds] + [pg.num_nodes],
                         dtype=np.int64)
    starts = np.minimum(offsets[:P], pg.num_nodes)
    n = int(pg.real_nodes[p])
    ptr = pg.part_row_ptr[p, :n + 1].astype(np.int64)
    if col is None:
        col = pg.part_col_idx[p]
    col = np.asarray(col[:int(ptr[n])], dtype=np.int64)
    dst = np.repeat(np.arange(n, dtype=np.int64), np.diff(ptr))
    shard = np.searchsorted(offsets[1:P + 1], col, side="right")
    pairs = {}
    for s in range(P):
        sel = shard == s
        # dst is globally sorted, so the stable mask keeps it sorted
        pairs[s] = ((col[sel] - starts[s]).astype(np.int32),
                    dst[sel].astype(np.int32))
    return pairs


def pack_ring_part(pairs: dict, num_shards: int, pair_edges: int,
                   part_nodes: int):
    """One partition's ``[S, pair_edges]`` (src, dst) tables from its
    pair lists: padding sources point at the dummy zero row
    (``part_nodes``), padding destinations at the last row (keeps the
    dst sort; the gathered zero adds nothing)."""
    src = np.full((num_shards, pair_edges), part_nodes, dtype=np.int32)
    dst = np.full((num_shards, pair_edges), part_nodes - 1,
                  dtype=np.int32)
    for s, (c, d) in pairs.items():
        src[s, :c.shape[0]] = c
        dst[s, :d.shape[0]] = d
    return src, dst


def round_pair_edges(max_pair: int) -> int:
    """Pad the pair width to an 8-multiple so chunking divides evenly."""
    return -(-max(max_pair, 1) // 8) * 8


def ring_part_weights(src: np.ndarray, dst: np.ndarray, d: np.ndarray,
                      p: int) -> np.ndarray:
    """Partition ``p``'s baked fused-normalization weights for its ring
    tables ``src`` / ``dst`` ``[S, pair_edges]`` (:func:`ring_aggregate`
    ``weights``): fp32, ``w = d[row summed into] * d[source row]`` — the
    per-edge entries of ``D^-1/2 A D^-1/2`` in ring layout, so the
    fused aggregation runs the rotation with ZERO runtime
    normalization.  ``d`` ``[P, part_nodes]``: the inv-sqrt in-degrees
    of every partition's padded rows.  Padding slots (dummy source id
    ``part_nodes``) weigh 0."""
    pn = d.shape[1]
    real = src < pn
    # padding dst slots use part_nodes - 1 and the dummy source is
    # part_nodes: clip for the lookup, the mask zeroes them
    w = (d[p][np.minimum(dst, pn - 1)]
         * d[np.arange(src.shape[0])[:, None], np.minimum(src, pn - 1)])
    return np.where(real, w, 0.0).astype(np.float32)


def ring_aggregate(x: jax.Array, ring_src: jax.Array,
                   ring_dst: jax.Array, axis_name: str = PARTS_AXIS,
                   edge_chunk: int = 1 << 17,
                   weights: Optional[jax.Array] = None,
                   overlap: bool = True) -> jax.Array:
    """SPMD ring aggregation (call inside shard_map).

    x: [part_nodes, F] this device's shard.
    ring_src/ring_dst: int32 [S, pair_edges] (this device's slice).
    Returns [part_nodes, F] = sum aggregation over ALL global edges
    whose destination is local.  The per-step local op chunks the pair's
    edges (bounding the [C, F] gather transient) and scatter-adds with
    ``indices_are_sorted`` (dst-sorted within every pair by
    construction).

    ``weights`` (optional): [S, pair_edges] per-edge weights
    (:func:`ring_part_weights` — the baked fused-norm scales),
    applied to the gathered rows in-register before the scatter-add.

    ``overlap`` (default True): double-buffered hop schedule — the
    ``ppermute`` of the incoming buffer is ISSUED before the
    scatter-accumulate of the current one.  The two are
    data-independent once double-buffered, so XLA's latency-hiding
    scheduler can run the collective under the compute (the
    reference's interconnect/compute overlap, ICI edition).
    ``overlap=False`` keeps the strictly sequential
    compute-then-permute form: the parity/measurement reference —
    both orders produce identical values (the rotation never reads
    the accumulator), so this is a schedule knob, not a numerics one.
    """
    S, pair_edges = ring_src.shape
    n, F = x.shape
    me = lax.axis_index(axis_name)
    perm = ring_hop_perm(S)
    C = min(edge_chunk, pair_edges)
    while pair_edges % C:
        C //= 2
    n_chunks = pair_edges // C

    def local_pair(out, buf_ext, src_e, dst_e, w_e):
        xs = (src_e.reshape(n_chunks, C), dst_e.reshape(n_chunks, C))
        if w_e is not None:
            xs += (w_e.reshape(n_chunks, C),)

        def chunk_body(out, args):
            s_c, d_c = args[0], args[1]
            g = buf_ext[s_c]
            if len(args) > 2:
                g = g * args[2][:, None].astype(g.dtype)
            return out.at[d_c].add(g, indices_are_sorted=True,
                                   unique_indices=False), None
        out, _ = lax.scan(chunk_body, out, xs)
        return out

    def hop(buf):
        # the halo exchange of this layout: one rotation of the ring
        with jax.named_scope(HALO_SCOPE):
            return lax.ppermute(buf, axis_name, perm)

    def step(k, carry):
        buf, out = carry
        # double-buffered hop: the rotation that fills the NEXT step's
        # buffer is issued FIRST, before this step's scatter-accumulate
        # touches ``buf`` — the collective and the local aggregation
        # share no data (the permute never reads ``out``), so the
        # program order puts the ICI transfer under the gather/scatter
        # compute instead of after it.  (Skipped rotation work on the
        # last step is harmless; keeping it unconditional keeps the
        # loop body uniform.)
        nxt = hop(buf) if overlap else None
        src_shard = jnp.mod(me - k, S)
        src_e = lax.dynamic_index_in_dim(ring_src, src_shard, axis=0,
                                         keepdims=False)
        dst_e = lax.dynamic_index_in_dim(ring_dst, src_shard, axis=0,
                                         keepdims=False)
        w_e = (lax.dynamic_index_in_dim(weights, src_shard, axis=0,
                                        keepdims=False)
               if weights is not None else None)
        buf_ext = jnp.concatenate(
            [buf, jnp.zeros((1, F), dtype=buf.dtype)], axis=0)
        out = local_pair(out, buf_ext, src_e, dst_e, w_e)
        if not overlap:
            # sequential reference: rotate only after the accumulate
            nxt = hop(buf)
        return nxt, out

    out0 = jnp.zeros((n, F), dtype=x.dtype)
    _, out = lax.fori_loop(0, S, step, (x, out0))
    return out
