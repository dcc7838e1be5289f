"""The plain reference the system's outputs are held to, and the
comparison that decides ``correct``.

Plain means: ``jax.numpy`` in float32 with
``jax.default_matmul_precision("highest")``, neighbour aggregation as a
chunked ``segment_sum`` over the stored edge list — no tables, no
kernels, no layout, nothing imported from ``roc_tpu``.  The system hands
over only its parameter pytree and its logits.  A configuration's
forward pass is a file of its own under ``references/`` built from the
pieces here.
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np

# transient [chunk, F] float32 gathered per scan step (the chunk is
# that many edges at the configuration's widest layer)
_CHUNK_BYTES = 128 << 20


class Graph:
    """The stored edge list as the reference sees it, destination-sorted:
    ``src``/``dst`` int32 [n_chunks, chunk] for the whole chunks,
    ``tail_src``/``tail_dst`` int32 [E mod chunk] for the rest, and the
    in-degree float32 [V]."""

    def __init__(self, src, dst, tail_src, tail_dst, degree,
                 num_nodes: int):
        self.src, self.dst = src, dst
        self.tail_src, self.tail_dst = tail_src, tail_dst
        self.degree = degree
        self.num_nodes = num_nodes

    def arrays(self):
        return (self.src, self.dst, self.tail_src, self.tail_dst,
                self.degree)

    @classmethod
    def from_csr(cls, row_ptr: np.ndarray, col_idx: np.ndarray,
                 widest: int) -> "Graph":
        V = int(row_ptr.shape[0] - 1)
        deg = np.diff(row_ptr)
        chunk = int(max(1024, _CHUNK_BYTES // (4 * max(widest, 1))))
        src = col_idx.astype(np.int32)
        dst = np.repeat(np.arange(V, dtype=np.int32), deg)
        whole = src.shape[0] - src.shape[0] % chunk
        return cls(src[:whole].reshape(-1, chunk),
                   dst[:whole].reshape(-1, chunk), src[whole:],
                   dst[whole:], deg.astype(np.float32), V)


def aggregate_sum(x, graph: Graph):
    """``out[v] = sum over stored edges (u -> v) of x[u]``: a scan of
    ``segment_sum`` over chunks of the edge list."""
    import jax
    import jax.numpy as jnp

    def step(acc, sd):
        s, d = sd
        return acc.at[d].add(x[s], indices_are_sorted=True), None

    acc = jnp.zeros((graph.num_nodes, x.shape[1]), x.dtype)
    if graph.src.shape[0]:
        acc, _ = jax.lax.scan(step, acc, (graph.src, graph.dst))
    if graph.tail_src.shape[0]:
        acc, _ = step(acc, (graph.tail_src, graph.tail_dst))
    return acc


def dense(x, w):
    import jax
    import jax.numpy as jnp
    return jnp.dot(x, w.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)


def loss_sum(logits, labels, mask, train_value: int = 1):
    """The loss the program prints: the sum over train vertices of
    ``1 - p_true`` (the reference's ``calc_loss``)."""
    import jax
    import jax.numpy as jnp
    p = jax.nn.softmax(logits, axis=-1)
    p_true = jnp.take_along_axis(p, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(jnp.where(mask == train_value, 1.0 - p_true, 0.0))


def run(forward, params: Dict[str, Any], features: np.ndarray,
        labels: np.ndarray, mask: np.ndarray, row_ptr: np.ndarray,
        col_idx: np.ndarray, model: Dict[str, Any],
        on: Any = None) -> Dict[str, Any]:
    """``forward(params, x, graph, model) -> logits`` jitted on device
    ``on`` (default: JAX's default device); returns float32 logits
    [V, C] on the host and the loss on them."""
    import jax
    import jax.numpy as jnp
    layers = [int(d) for d in model["layers"]]
    g = Graph.from_csr(row_ptr, col_idx, widest=max(layers))

    def fwd(p, x, *arrays):
        return forward(p, x, Graph(*arrays, g.num_nodes), model)

    with jax.default_device(on), \
            jax.default_matmul_precision("highest"):
        p32 = {k: jnp.asarray(np.asarray(v), dtype=jnp.float32)
               for k, v in params.items()}
        logits = jax.jit(fwd)(
            p32, jnp.asarray(features, dtype=jnp.float32),
            *(jnp.asarray(a) for a in g.arrays()))
        loss = loss_sum(logits, jnp.asarray(labels, dtype=jnp.int32),
                        jnp.asarray(mask, dtype=jnp.int32))
        return {"logits": np.asarray(logits, dtype=np.float32),
                "loss": float(loss)}


def compare(system: np.ndarray, ref: np.ndarray) -> Dict[str, float]:
    """Per-row relative L2 of ``system`` against ``ref`` over all rows:
    ``|s - r| / max(|r|, floor)``, the floor being a thousandth of the
    median row norm (a row of near-zero logits is not a division by
    nothing)."""
    s = np.asarray(system, dtype=np.float64)
    r = np.asarray(ref, dtype=np.float64)
    if s.shape != r.shape:
        raise ValueError(f"logits {s.shape} against reference {r.shape}")
    norm = np.linalg.norm(r, axis=1)
    floor = 1e-3 * float(np.median(norm))
    rel = np.linalg.norm(s - r, axis=1) / np.maximum(norm, floor)
    finite = bool(np.isfinite(s).all())
    return {"rows": int(r.shape[0]), "finite": finite,
            "row_rel_l2_median": float(np.median(rel)),
            "row_rel_l2_p99": float(np.percentile(rel, 99)),
            "row_rel_l2_max": float(rel.max()),
            "argmax_agree": float(np.mean(s.argmax(1) == r.argmax(1)))}
