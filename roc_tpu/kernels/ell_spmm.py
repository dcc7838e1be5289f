"""One-launch Pallas TPU kernel for ELL neighbor-sum aggregation.

The reference's defining cost is one cooperative CSR kernel per
partition covering ALL its edges (``scattergather_kernel.cu:79-158``).
This module is the TPU equivalent built exactly to that shape: per
degree bucket, ONE ``pallas_call`` whose grid tiles the whole bucket —
no ``lax.scan`` over edge chunks, no XLA gather on the critical path.

Per grid step ``(i, j)`` covering rows ``[i*BR, (i+1)*BR)`` and widths
``[j*WC, (j+1)*WC)``:

1. the index block ``idx[BR, WC]`` is staged into SMEM by the Pallas
   pipeline (BlockSpec with ``memory_space=SMEM``), so source ids are
   scalar-readable for DMA address computation;
2. each edge's feature row is fetched with an async copy HBM->VMEM into
   an ``NBUF``-deep rotating buffer (DMA ``e+NBUF`` issues while edge
   ``e`` is reduced — the double-buffer pattern, generalized);
3. rows accumulate in fp32 in VMEM and add into the output block,
   which revisits across the ``j`` axis (zeroed at ``j == 0``).

The feature matrix itself never leaves HBM except row-by-row into VMEM,
and the gathered rows are reduced in registers — HBM traffic is the
irreducible ``E*F`` gather plus the output, with no ``[E, F]`` or
``[R, W, F]`` intermediate materialized (the XLA ``ell`` path's
``feats[idx]`` may materialize one depending on fusion).

Whether per-row DMA issue throughput beats XLA's native dynamic-gather
unit is an empirical question — ``benchmarks/micro_agg.py`` measures
both on the real chip and the framework default follows the numbers.

**Measured (TPU v5 lite, 2026-07-29, V=50k E=10M F=256 fp32, median of
10, ~66 ms constant fetch-barrier overhead included in both):**

====================  =========  ========
impl                  wall ms    GB/s
====================  =========  ========
ell (XLA gather)        119.1      86.0
pallas (this kernel)   1006.2      10.2
scan:4096               260.0      39.4
blocked:1024            294.6      34.8
====================  =========  ========

**bf16** (TPU v5 lite, libtpu 0.0.34, PR 21): the kernel used to be
refused by Mosaic with bfloat16 features ("cannot statically prove
that index in dimension 0 is a multiple of 8" on the single-row load
of the bf16 output block).  It compiles in both dtypes since the
output block is fp32 and the DMA group follows the feature dtype's
sublane tiling (:func:`_group_rows`); ``chip_smoke.py`` compiles it on
the chip in fp32 and bf16.  Compile only: it has not been timed in
bf16.

The XLA gather path wins by ~18x net of sync overhead and **is the
framework default**.  Two structural reasons, both discovered only by
compiling on real hardware (interpreter mode enforces neither):
(1) HBM memrefs are (8, 128)-tiled, so Mosaic rejects single-row DMAs
outright — every copy must stage an aligned 8-row group, an 8x gather
amplification; (2) DMA issue is serialized through the scalar core,
while XLA's dynamic-gather unit pipelines row fetches in hardware.
This kernel is kept as compiling, tested, honest evidence for that
design decision (``benchmarks/measured_baselines.json`` records the
race), not as a production path.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Edges (SMEM index-block elements) per grid step, and the DMA pipeline
# depth.  2048 edges keeps the SMEM block at 8 KiB; 8 outstanding row
# copies hides single-copy latency without exhausting DMA semaphores.
_EDGES_PER_STEP = 2048
_NBUF = 8


def _group_rows(dtype) -> int:
    """Rows of one aligned DMA group: the sublane tiling of ``dtype``
    in HBM — (8, 128) for 4-byte elements, (16, 128) for 2-byte ones
    (two rows pack into one sublane)."""
    return 8 * (4 // jnp.dtype(dtype).itemsize)


def _bucket_kernel(idx_ref, feats_ref, out_ref, buf, sem, *, nbuf: int):
    """One (row-block, width-chunk) tile of a single ELL bucket.

    idx_ref: int32 [BR, WC] in SMEM (source row ids; dummy -> zero row).
    feats_ref: [R_gathered + 1, F] in HBM/ANY (never block-copied).
    out_ref: fp32 [BR, F] VMEM output block, revisited over the width
    axis (fp32 whatever the feature dtype: rows accumulate across the
    width chunks, and a 2-byte output block cannot take the single-row
    dynamic store).
    buf: VMEM [nbuf, G, F] rotating group buffer; sem: DMA sems [nbuf].

    HBM memrefs are (G, 128)-tiled on TPU (:func:`_group_rows`), so a
    single feature row can NOT be DMA'd (Mosaic: "slice shape along
    dimension 0 must be aligned to tiling"); each copy therefore
    stages the aligned G-row group containing the source row and the
    reduction mask-selects the one row — a Gx gather amplification
    that is this design's intrinsic cost (see module docstring for the
    measured consequence).
    """
    BR, WC = idx_ref.shape
    F = out_ref.shape[1]
    G = buf.shape[1]
    j = pl.program_id(1)
    total = BR * WC

    def group_base(e):
        # aligned G-row group start; the wrapper pads feats to a
        # multiple of G rows, so this is always in-bounds AND Mosaic
        # can prove tiling divisibility (a min-clamp defeats the prover)
        gid = idx_ref[e // WC, e % WC]
        return (gid // G) * G

    def dma(e, slot):
        return pltpu.make_async_copy(
            feats_ref.at[pl.ds(group_base(e), G), :],
            buf.at[slot],
            sem.at[slot])

    @pl.when(j == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    # warm the pipeline
    for k in range(min(nbuf, WC)):  # static unroll; nbuf, WC static
        dma(k, k % nbuf).start()

    lane = lax.broadcasted_iota(jnp.int32, (G, 1), 0)

    def row_body(r, _):
        def w_body(w, acc):
            e = r * WC + w
            slot = lax.rem(e, nbuf)
            dma(e, slot).wait()
            gid = idx_ref[e // WC, e % WC]
            sub = gid - group_base(e)
            rows = buf[slot].astype(jnp.float32)
            acc = acc + jnp.sum(
                jnp.where(lane == sub, rows, 0.0), axis=0, keepdims=True)
            nxt = e + nbuf

            @pl.when(nxt < total)
            def _():
                dma(nxt, slot).start()

            return acc

        acc = lax.fori_loop(0, WC, w_body, jnp.zeros((1, F), jnp.float32),
                            unroll=False)
        out_ref[pl.ds(r, 1), :] = out_ref[pl.ds(r, 1), :] + acc
        return 0

    lax.fori_loop(0, BR, row_body, 0, unroll=False)


def _tile_shape(rows: int, width: int) -> Tuple[int, int]:
    """(BR, WC): rows x width-chunk per grid step.  Mosaic requires the
    last two block dims to be divisible by (8, 128) or equal to the
    whole (padded) array dims — interpreter mode does not enforce this,
    the real compiler does (measured on v5e) — so BR is rounded up to a
    multiple of 8 and WC is either the full width or 128-aligned."""
    wc = min(width, _EDGES_PER_STEP)
    if wc < width:
        wc = max(128, (wc // 128) * 128)
    br = max(1, min(256, _EDGES_PER_STEP // wc))
    br = -(-br // 8) * 8
    return br, wc


@functools.partial(jax.jit,
                   static_argnames=("num_rows", "interpret"))
def ell_aggregate_pallas(feats: jax.Array, ell_idx, ell_row_pos: jax.Array,
                         num_rows: int,
                         interpret: bool = False) -> jax.Array:
    """Drop-in for :func:`roc_tpu.ops.aggregate.aggregate_ell` backed by
    the one-launch-per-bucket Pallas kernel.

    feats: [R_gathered + 1, F] with trailing zero row (dummy target).
    ell_idx: tuple of int32 [rows_b, width_b] bucket index tables.
    ell_row_pos: int32 [num_rows] inverse permutation (core/ell.py).
    """
    F = feats.shape[1]
    dummy = feats.shape[0] - 1
    # pad rows to a multiple of the group so every aligned DMA group
    # is in-bounds (HBM tiling; see _bucket_kernel docstring)
    G = _group_rows(feats.dtype)
    Rg = feats.shape[0]
    Rgp = -(-Rg // G) * G
    if Rgp != Rg:
        feats = jnp.pad(feats, ((0, Rgp - Rg), (0, 0)))
    outs = []
    for idx in ell_idx:
        R, W = idx.shape
        BR, WC = _tile_shape(R, W)
        Rp = -(-R // BR) * BR
        Wp = -(-W // WC) * WC
        if Rp != R or Wp != W:
            idx = jnp.pad(idx, ((0, Rp - R), (0, Wp - W)),
                          constant_values=dummy)
        grid = (Rp // BR, Wp // WC)
        out = pl.pallas_call(
            functools.partial(_bucket_kernel, nbuf=_NBUF),
            grid=grid,
            in_specs=[
                pl.BlockSpec((BR, WC), lambda i, j: (i, j),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((BR, F), lambda i, j: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((Rp, F), jnp.float32),
            scratch_shapes=[
                pltpu.VMEM((_NBUF, G, F), feats.dtype),
                pltpu.SemaphoreType.DMA((_NBUF,)),
            ],
            interpret=interpret,
        )(idx, feats)
        outs.append(out[:R].astype(feats.dtype))
    zero = jnp.zeros((1, F), dtype=feats.dtype)
    cat = jnp.concatenate(outs + [zero], axis=0)
    return cat[ell_row_pos]
