"""Pallas TPU kernel for the CSR neighbor-sum hot loop.

The reference's defining cost is ``aggre_coop_kernel``
(``scattergather_kernel.cu:20-76``): a cub-BlockScan cooperative CSR
sum-aggregation over destination-sorted edges.  This module is the
TPU-native equivalent: a fused segmented reduction over edge chunks,
one chunk per VMEM-resident kernel invocation, driven by the same
write-once window + carry-record scheme as
:func:`roc_tpu.ops.aggregate.aggregate_scan`.

Per chunk of ``C`` sorted edges the kernel fuses, in one VMEM pass:

1. local destination ids from the chunk's first row,
2. the segmented sum as a *one-hot MXU contraction*
   ``onehot(local)^T @ g`` — Mosaic has no VMEM vector-gather, so the
   selection matmul is the TPU's native scatter-free reduction,
3. masking of the chunk's last row into a carry record (emitted for a
   post-scan scatter-add, so output windows are written exactly once).

The feature gather itself stays in XLA (``feats[src]`` — the TPU's
dynamic-gather path, the irreducible cost: ~5.3 ns/row measured on
v5e at V=50k E=10M F=256, benchmarks/measured_baselines.json);
everything after it lands in this kernel.
VMEM working set is O(C * (C + F)), independent of E.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _seg_reduce_kernel(dst_ref, g_ref, out_ref, carry_ref):
    """One edge chunk: segmented sum of gathered rows ``g`` by sorted
    local destination, emitting the window block + last-row carry."""
    C = dst_ref.shape[1]
    dst = dst_ref[0, :]                               # [C] int32
    r0 = dst_ref[0, 0]
    local = dst - r0                                  # [C] in [0, C)
    pos = dst_ref[0, C - 1] - r0                      # last local row

    # Scatter-free segmented reduction: sel[e, j] = (local[e] == j);
    # sel^T @ g on the MXU with fp32 accumulation.
    jj = lax.broadcasted_iota(jnp.int32, (C, C), 1)
    sel = (local[:, None] == jj).astype(jnp.float32)  # [C(e), C(j)]
    g = g_ref[:].astype(jnp.float32)                  # [C, F]
    L = lax.dot_general(sel, g, (((0,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)  # [C, F]

    # row ``pos`` leaves as the carry and is zeroed in the window; a
    # masked reduction selects it (Mosaic lowers no dynamic_slice on
    # a value)
    last = lax.broadcasted_iota(jnp.int32, (C, 1), 0) == pos
    carry_ref[:] = jnp.sum(jnp.where(last, L, 0.0), axis=0,
                           keepdims=True).astype(carry_ref.dtype)
    out_ref[:] = jnp.where(last, 0.0, L).astype(out_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("num_rows", "chunk", "interpret"))
def csr_spmm_pallas(feats: jax.Array, edge_src: jax.Array,
                    edge_dst: jax.Array, num_rows: int,
                    chunk: int = 512,
                    interpret: bool = False) -> jax.Array:
    """``out[dst] = sum feats[src]`` over dst-sorted padded edges.

    Same contract as :func:`roc_tpu.ops.aggregate.aggregate_blocked`:
    ``feats`` is ``[R+1, F]`` with a trailing zero dummy row, edges are
    padded to a ``chunk`` multiple, every destination has degree >= 1
    over the full edge list (so a chunk of C edges spans <= C rows).
    """
    E = edge_src.shape[0]
    F = feats.shape[1]
    assert E % chunk == 0, "pad edges to a chunk multiple"
    C = chunk
    n_chunks = E // C
    src_c = edge_src.reshape(n_chunks, C)
    dst_c = edge_dst.reshape(n_chunks, 1, C)

    kernel = pl.pallas_call(
        _seg_reduce_kernel,
        out_shape=(
            jax.ShapeDtypeStruct((C, F), feats.dtype),
            jax.ShapeDtypeStruct((1, F), feats.dtype),
        ),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=(
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ),
        interpret=interpret,
    )

    out0 = jnp.zeros((num_rows + C, F), dtype=feats.dtype)

    def body(out, inputs):
        src, dst = inputs
        g = feats[src]                                # XLA gather
        window, carry = kernel(dst, g)
        out = lax.dynamic_update_slice(out, window, (dst[0, 0], 0))
        return out, (dst[0, C - 1], carry[0])

    out, (rows, vecs) = lax.scan(body, out0, (src_c, dst_c))
    out = out.at[rows].add(vecs)
    return out[:num_rows]
