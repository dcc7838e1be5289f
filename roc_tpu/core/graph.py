"""Graph data layer: CSR graph container, .lux binary reader, feature /
label / mask loaders, and synthetic fixtures.

TPU-native re-design of the reference data layer:

- Reference ``Graph`` (``gnn.h:120-130``) holds Legion regions for row
  pointers (inclusive-end offsets, one per vertex) and column indices.  We
  hold plain numpy arrays host-side with the standard exclusive-start
  ``row_ptr`` of length ``V+1`` (``row_ptr[0] == 0``), converting on load.
- Reference `.lux` format (``gnn.cc:756-801``, ``load_task.cu:229-243``):
  ``u32 numNodes``, ``u64 numEdges``, then ``numNodes`` u64 *inclusive end*
  row offsets, then ``numEdges`` u32 source-vertex ids, rows sorted by
  destination.  Self-edges are pre-added in the file (the driver appends
  ``.add_self_edge.lux`` to the path, ``gnn.cc:756``); we expose
  :func:`add_self_edges` to perform the same conversion in-framework.
- Feature CSV loader with ``.feats.bin`` binary caching mirrors
  ``load_task.cu:41-73``; labels are class indices (one integer per line,
  ``load_task.cu:118-123`` one-hots them — we keep int labels and one-hot
  lazily on device); masks are the strings Train/Val/Test/None
  (``load_task.cu:169-183``).

Row-major node-feature layout ``[num_nodes, dim]`` (the reference uses
``[dim, num_nodes]`` column-major Legion rects — row-major is the
TPU-friendly choice: feature dim lands on the 128-wide lane axis).
"""

from __future__ import annotations

import os
import struct
from dataclasses import dataclass
from typing import Any, Optional

import numpy as np

# Mask values mirror the reference enum MaskType (gnn.h:98-103).
MASK_NONE = 0
MASK_TRAIN = 1
MASK_VAL = 2
MASK_TEST = 3

_MASK_NAMES = {"Train": MASK_TRAIN, "Val": MASK_VAL, "Test": MASK_TEST,
               "None": MASK_NONE}


@dataclass
class Graph:
    """An in-memory CSR graph, destination-major.

    ``row_ptr`` has length ``num_nodes + 1`` with ``row_ptr[0] == 0``;
    edges for destination vertex ``v`` occupy ``col_idx[row_ptr[v]:row_ptr[v+1]]``
    and store *source* vertex ids.  Aggregation computes
    ``out[v] = sum(in[col_idx[row_ptr[v]:row_ptr[v+1]]])`` exactly like the
    reference hot loop (``scattergather_kernel.cu:20-76``).
    """

    row_ptr: np.ndarray  # int64 [V+1]
    col_idx: np.ndarray  # int32 [E]

    def __post_init__(self):
        self.row_ptr = np.asarray(self.row_ptr, dtype=np.int64)
        self.col_idx = np.asarray(self.col_idx, dtype=np.int32)
        assert self.row_ptr.ndim == 1 and self.col_idx.ndim == 1
        assert self.row_ptr[0] == 0
        assert self.row_ptr[-1] == self.col_idx.shape[0]

    @property
    def num_nodes(self) -> int:
        return int(self.row_ptr.shape[0] - 1)

    @property
    def num_edges(self) -> int:
        return int(self.col_idx.shape[0])

    @property
    def in_degree(self) -> np.ndarray:
        """Per-destination edge counts (int32), the reference's indegree
        (``graphnorm_kernel.cu:45-55`` computes it from CSR row pointers)."""
        return np.diff(self.row_ptr).astype(np.int32)

    def edge_dst(self) -> np.ndarray:
        """Expand row_ptr to a per-edge destination id array (int32 [E])."""
        return np.repeat(
            np.arange(self.num_nodes, dtype=np.int32), self.in_degree
        )

    def has_all_self_edges(self) -> bool:
        deg = self.in_degree
        if (deg == 0).any():
            return False
        dst = self.edge_dst()
        # binary check: does each row contain its own id?
        out = np.zeros(self.num_nodes, dtype=bool)
        out[dst[self.col_idx == dst]] = True
        return bool(out.all())

    def is_symmetric(self) -> bool:
        """True iff the adjacency matrix equals its transpose.  The
        reference backward pass reuses the forward CSR
        (``scattergather_kernel.cu:160-170``) which is only correct for
        symmetric graphs; callers can verify with this."""
        return check_symmetric(self)

    def transpose(self) -> "Graph":
        """CSC <-> CSR flip: returns the graph with edge directions
        reversed (sorted by the old source)."""
        dst = self.edge_dst()
        src = self.col_idx
        order = np.argsort(src, kind="stable")
        new_dst = src[order]
        new_col = dst[order]
        counts = np.bincount(new_dst, minlength=self.num_nodes)
        row_ptr = np.zeros(self.num_nodes + 1, dtype=np.int64)
        np.cumsum(counts, out=row_ptr[1:])
        return Graph(row_ptr=row_ptr, col_idx=new_col.astype(np.int32))


def check_symmetric(graph: Graph) -> bool:
    """Exact symmetry check via sorted edge-list comparison."""
    dst = graph.edge_dst().astype(np.int64)
    src = graph.col_idx.astype(np.int64)
    fwd = dst * graph.num_nodes + src
    bwd = src * graph.num_nodes + dst
    return bool(np.array_equal(np.sort(fwd), np.sort(bwd)))


# ---------------------------------------------------------------------------
# .lux binary format
# ---------------------------------------------------------------------------

def _read_slice(f, offset: int, count: int, dtype: str) -> np.ndarray:
    """Seek + read a typed slice.  All partition-local binary reads go
    through here so tests can spy on exactly which byte ranges a host
    touches (the reference's per-partition loader contract,
    ``load_task.cu:41-51,201-245``)."""
    f.seek(offset)
    out = np.fromfile(f, dtype=dtype, count=count)
    if out.size != count:
        raise IOError(f"truncated read at {offset} (+{count}): "
                      f"got {out.size} items")
    return out


def load_lux_header(path: str) -> tuple:
    """(num_nodes, num_edges) from a `.lux` header without reading the
    body."""
    with open(path, "rb") as f:
        return struct.unpack("<IQ", f.read(12))


def load_lux_rows(path: str, row_lo: int, row_hi: int) -> tuple:
    """Partition-local `.lux` read: only rows ``[row_lo, row_hi)``.

    Reads the (row_hi - row_lo + 1)-entry offset slice and exactly the
    partition's column-index bytes — the reference loader's skip-to-
    rowLeft behavior (``load_task.cu:41-51,201-245``) — instead of the
    whole file.  Returns ``(local_row_ptr, col_idx)`` with
    ``local_row_ptr`` int64 [n+1] rebased to 0.
    """
    num_nodes, num_edges = load_lux_header(path)
    if not 0 <= row_lo <= row_hi <= num_nodes:
        raise ValueError(f"bad row range [{row_lo}, {row_hi}) for "
                         f"{num_nodes} nodes")
    n = row_hi - row_lo
    header = 12
    with open(path, "rb") as f:
        # offsets are u64 *inclusive ends*; row v's edges end at off[v]
        # and start at off[v-1] (0 for v == 0)
        lo_off = 0 if row_lo == 0 else int(_read_slice(
            f, header + (row_lo - 1) * 8, 1, "<u8")[0])
        if n == 0:
            return np.zeros(1, dtype=np.int64), np.zeros(0, dtype=np.int32)
        ends = _read_slice(f, header + row_lo * 8, n, "<u8").astype(
            np.int64)
        if not ((np.diff(ends) >= 0).all() and ends[0] >= lo_off):
            raise ValueError(f"{path}: non-monotone row offsets in "
                             f"rows [{row_lo}, {row_hi})")
        col_base = header + num_nodes * 8
        e0, e1 = lo_off, int(ends[-1])
        col = _read_slice(f, col_base + e0 * 4, e1 - e0, "<u4")
    local_ptr = np.zeros(n + 1, dtype=np.int64)
    local_ptr[1:] = ends - lo_off
    return local_ptr, col.astype(np.int32)


def load_lux(path: str) -> Graph:
    """Read a `.lux` binary graph (reference format, ``gnn.cc:756-801``):
    u32 num_nodes, u64 num_edges, num_nodes x u64 inclusive-end row
    offsets, num_edges x u32 source ids.

    Uses the native C++ reader (native/rocio.cc) when built; numpy
    fallback otherwise."""
    from .. import native
    if native.available():
        row_ptr, col_idx = native.load_lux(path)
        return Graph(row_ptr=row_ptr, col_idx=col_idx)
    with open(path, "rb") as f:
        header = f.read(12)
        num_nodes, num_edges = struct.unpack("<IQ", header)
        raw_rows = np.fromfile(f, dtype="<u8", count=num_nodes)
        col_idx = np.fromfile(f, dtype="<u4", count=num_edges)
    if raw_rows.shape[0] != num_nodes:
        raise IOError(f"{path}: truncated .lux row offsets")
    if col_idx.shape[0] != num_edges:
        raise IOError(f"{path}: truncated .lux col indices")
    # Monotonicity checks mirror gnn.cc:798-800 (ValueError, not assert:
    # data validation must survive python -O).
    if not (np.diff(raw_rows.astype(np.int64)) >= 0).all():
        raise ValueError(f"{path}: non-monotone row offsets")
    if raw_rows[-1] != num_edges:
        raise ValueError(f"{path}: row offsets end at {raw_rows[-1]}, "
                         f"expected {num_edges}")
    row_ptr = np.zeros(num_nodes + 1, dtype=np.int64)
    row_ptr[1:] = raw_rows.astype(np.int64)
    return Graph(row_ptr=row_ptr, col_idx=col_idx.astype(np.int32))


def save_lux(graph: Graph, path: str) -> None:
    """Write the reference `.lux` binary format (inverse of load_lux)."""
    with open(path, "wb") as f:
        f.write(struct.pack("<IQ", graph.num_nodes, graph.num_edges))
        graph.row_ptr[1:].astype("<u8").tofile(f)
        graph.col_idx.astype("<u4").tofile(f)


def add_self_edges(graph: Graph) -> Graph:
    """Ensure every vertex has a self edge (the `.add_self_edge.lux`
    preprocessing the reference assumes was done offline, ``gnn.cc:756``).
    Existing self edges are kept; missing ones are inserted."""
    from .. import native
    if native.available():
        row_ptr, col_idx = native.add_self_edges(graph.row_ptr,
                                                 graph.col_idx)
        return Graph(row_ptr=row_ptr, col_idx=col_idx)
    V = graph.num_nodes
    dst = graph.edge_dst()
    has_self = np.zeros(V, dtype=bool)
    self_rows = dst[graph.col_idx == dst]
    has_self[self_rows] = True
    missing = np.flatnonzero(~has_self).astype(np.int32)
    if missing.size == 0:
        return graph
    dst_all = np.concatenate([dst, missing])
    col_all = np.concatenate([graph.col_idx, missing])
    order = np.argsort(dst_all, kind="stable")
    dst_all = dst_all[order]
    col_all = col_all[order]
    counts = np.bincount(dst_all, minlength=V)
    row_ptr = np.zeros(V + 1, dtype=np.int64)
    np.cumsum(counts, out=row_ptr[1:])
    return Graph(row_ptr=row_ptr, col_idx=col_all.astype(np.int32))


def from_edge_list(src: np.ndarray, dst: np.ndarray, num_nodes: int,
                   symmetrize: bool = False) -> Graph:
    """Build a dst-major CSR graph from a COO edge list."""
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    if symmetrize:
        src, dst = np.concatenate([src, dst]), np.concatenate([dst, src])
        # dedupe
        key = dst * num_nodes + src
        key = np.unique(key)
        dst, src = key // num_nodes, key % num_nodes
    order = np.argsort(dst, kind="stable")
    src, dst = src[order], dst[order]
    counts = np.bincount(dst, minlength=num_nodes)
    row_ptr = np.zeros(num_nodes + 1, dtype=np.int64)
    np.cumsum(counts, out=row_ptr[1:])
    return Graph(row_ptr=row_ptr, col_idx=src.astype(np.int32))


# ---------------------------------------------------------------------------
# Feature / label / mask loaders (reference load_task.cu:25-199)
# ---------------------------------------------------------------------------

def load_features(prefix: str, num_nodes: int, in_dim: int,
                  rows: Optional[tuple] = None) -> np.ndarray:
    """Load ``<prefix>.feats.csv`` (one comma-separated row per vertex),
    caching a ``.feats.bin`` float32 binary alongside exactly like
    ``load_task.cu:41-73``.  Returns float32 ``[num_nodes, in_dim]``.

    ``rows=(lo, hi)`` reads only that half-open row range — from the
    ``.bin`` cache it is an exact byte-range read (the reference's
    per-partition skip-to-rowLeft, ``load_task.cu:41-51``); from the CSV
    the native parser line-skips to ``lo``, and the numpy fallback
    parses only the needed lines."""
    from .. import native
    bin_path = prefix + ".feats.bin"
    csv_path = prefix + ".feats.csv"
    if rows is not None:
        lo, hi = rows
        if not 0 <= lo <= hi <= num_nodes:
            raise ValueError(f"bad row range [{lo}, {hi}) for "
                             f"{num_nodes} nodes")
        if os.path.exists(bin_path):
            with open(bin_path, "rb") as f:
                data = _read_slice(f, lo * in_dim * 4, (hi - lo) * in_dim,
                                   np.float32)
            return data.reshape(hi - lo, in_dim)
        if native.available():
            return native.load_features_csv_rows(csv_path, lo, hi, in_dim)
        data = np.loadtxt(_iter_lines(csv_path, lo, hi), delimiter=",",
                          dtype=np.float32, ndmin=2)
        if data.shape != (hi - lo, in_dim):
            raise ValueError(f"{csv_path}: rows [{lo}, {hi}) parsed to "
                             f"{data.shape}, expected {(hi - lo, in_dim)}")
        return data
    if os.path.exists(bin_path):
        data = np.fromfile(bin_path, dtype=np.float32,
                           count=num_nodes * in_dim)
        if data.size != num_nodes * in_dim:
            raise IOError(f"{bin_path}: truncated .feats.bin "
                          f"({data.size} of {num_nodes * in_dim} floats)")
        return data.reshape(num_nodes, in_dim)
    if native.available():
        data = native.load_features_csv(csv_path, num_nodes, in_dim)
    else:
        data = np.loadtxt(csv_path, delimiter=",", dtype=np.float32)
        data = data.reshape(num_nodes, in_dim)
    data.tofile(bin_path)
    return data


def _iter_lines(path: str, lo: int, hi: int):
    """Yield lines [lo, hi) of a text file (the numpy-fallback line
    skip for partition-local CSV/label/mask reads)."""
    import itertools
    with open(path) as f:
        yield from itertools.islice(f, lo, hi)


def load_labels(prefix: str, num_nodes: int, num_classes: int,
                rows: Optional[tuple] = None) -> np.ndarray:
    """Load ``<prefix>.label`` (one class index per line,
    ``load_task.cu:118-123``).  Returns int32 ``[num_nodes]`` (or the
    ``rows=(lo, hi)`` slice); one-hot is formed on device by the loss."""
    if rows is not None:
        lo, hi = rows
        labels = np.loadtxt(_iter_lines(prefix + ".label", lo, hi),
                            dtype=np.int64, ndmin=1)
        n = hi - lo
    else:
        labels = np.loadtxt(prefix + ".label", dtype=np.int64,
                            ndmin=1)[:num_nodes]
        n = num_nodes
    if labels.shape[0] != n:
        raise ValueError(f"{prefix}.label: got {labels.shape[0]} rows, "
                         f"expected {n}")
    if not ((labels >= 0) & (labels < num_classes)).all():
        raise ValueError(f"{prefix}.label: class index outside "
                         f"[0, {num_classes})")
    return labels.astype(np.int32)


def load_mask(prefix: str, num_nodes: int,
              rows: Optional[tuple] = None) -> np.ndarray:
    """Load ``<prefix>.mask`` ("Train"/"Val"/"Test"/"None" per line,
    ``load_task.cu:169-183``).  Returns int32 ``[num_nodes]`` (or the
    ``rows=(lo, hi)`` slice) with MASK_* values."""
    from .. import native
    if rows is None and native.available():
        return native.load_mask(prefix + ".mask", num_nodes)
    lo, hi = rows if rows is not None else (0, num_nodes)
    out = np.empty(hi - lo, dtype=np.int32)
    if hi == lo:
        return out
    count = 0
    for i, line in enumerate(_iter_lines(prefix + ".mask", lo, hi)):
        line = line.strip()
        if line not in _MASK_NAMES:
            raise ValueError(f"Unrecognized mask: {line!r}")
        out[i] = _MASK_NAMES[line]
        count = i + 1
    if count != hi - lo:
        raise ValueError(
            f"truncated .mask: wanted rows [{lo}, {hi}), got {count}")
    return out


@dataclass
class Dataset:
    """A fully-loaded full-graph node-classification problem."""

    graph: Graph
    features: np.ndarray  # float32 [V, in_dim]
    labels: np.ndarray    # int32 [V]
    mask: np.ndarray      # int32 [V] of MASK_* values
    num_classes: int
    name: str = "dataset"
    # a typed graph's kinds and relations (core/relations.py
    # derive_typed over ``graph``), for a typed model; None otherwise
    typed: Any = None

    @property
    def in_dim(self) -> int:
        return int(self.features.shape[1])


def save_dataset(ds: "Dataset", prefix: str, csv: bool = True,
                 feats_bin: bool = True) -> None:
    """Write a dataset in the reference on-disk layout (the format
    ``load_task.cu:25-199`` consumes): ``<prefix>.add_self_edge.lux``,
    ``.feats.csv`` and/or ``.feats.bin``, ``.label``, ``.mask``.  The
    graph is written as-is — callers ensure self edges are present
    (``add_self_edges``) to honor the filename's contract."""
    save_lux(ds.graph, prefix + ".add_self_edge.lux")
    if csv:
        np.savetxt(prefix + ".feats.csv", ds.features, delimiter=",",
                   fmt="%.7g")
    if feats_bin:
        ds.features.astype(np.float32).tofile(prefix + ".feats.bin")
    np.savetxt(prefix + ".label", ds.labels, fmt="%d")
    names = {v: k for k, v in _MASK_NAMES.items()}
    with open(prefix + ".mask", "w") as f:
        for m in ds.mask:
            f.write(names[int(m)] + "\n")


def load_dataset(prefix: str, in_dim: int, num_classes: int,
                 name: Optional[str] = None) -> Dataset:
    """Load a reference-layout dataset directory: ``<prefix>.add_self_edge.lux``
    (falling back to ``<prefix>.lux`` + in-framework self-edge insertion),
    ``.feats.csv``/``.feats.bin``, ``.label``, ``.mask``."""
    from ..obs.events import span
    lux = prefix + ".add_self_edge.lux"
    with span("setup.load.graph") as s:
        if os.path.exists(lux):
            graph = load_lux(lux)
        else:
            lux = prefix + ".lux"
            graph = add_self_edges(load_lux(lux))
        s["file_bytes"] = os.path.getsize(lux)
    with span("setup.load.features") as s:
        # the .bin cache where a load has written it, else the CSV
        cached = prefix + ".feats.bin"
        s["file_bytes"] = os.path.getsize(
            cached if os.path.exists(cached) else prefix + ".feats.csv")
        feats = load_features(prefix, graph.num_nodes, in_dim)
    with span("setup.load.labels") as s:
        labels = load_labels(prefix, graph.num_nodes, num_classes)
        s["file_bytes"] = os.path.getsize(prefix + ".label")
    with span("setup.load.mask") as s:
        mask = load_mask(prefix, graph.num_nodes)
        s["file_bytes"] = os.path.getsize(prefix + ".mask")
    return Dataset(graph=graph, features=feats, labels=labels, mask=mask,
                   num_classes=num_classes,
                   name=name or os.path.basename(prefix))


# ---------------------------------------------------------------------------
# Synthetic fixtures (the reference ships none; needed for tests + bench)
# ---------------------------------------------------------------------------

def random_csr(num_nodes: int, num_edges: int, seed: int = 0,
               power_law: bool = True) -> Graph:
    """Fast benchmark-scale CSR generator: draws a degree sequence
    (lognormal when ``power_law``, else near-uniform) summing to
    ``num_edges`` with every degree >= 1 (self-edge convention), and
    uniform random sources.  Not symmetric — use for timing, not for
    gradient-parity tests."""
    assert num_edges >= num_nodes, "need >= 1 edge per node (self edges)"
    rng = np.random.RandomState(seed)
    if power_law:
        deg = _lognormal_degree_sequence(num_nodes, num_edges, rng)
    else:
        raw = np.ones(num_nodes) + rng.rand(num_nodes) * 0.1
        deg = _degree_sequence(raw, num_edges, rng)
    row_ptr = np.zeros(num_nodes + 1, dtype=np.int64)
    np.cumsum(deg, out=row_ptr[1:])
    col_idx = rng.randint(0, num_nodes, size=num_edges, dtype=np.int64)
    return Graph(row_ptr=row_ptr, col_idx=col_idx.astype(np.int32))


def _degree_sequence(raw: np.ndarray, num_edges: int,
                     rng) -> np.ndarray:
    """Degree sequence proportional to ``raw`` summing to
    ``num_edges`` with every degree >= 1 (self-edge convention);
    rounding remainder distributed over random vertices."""
    num_nodes = raw.shape[0]
    extra = num_edges - num_nodes
    deg = 1 + np.floor(raw / raw.sum() * extra).astype(np.int64)
    short = num_edges - int(deg.sum())
    if short > 0:
        np.add.at(deg, rng.randint(0, num_nodes, size=short), 1)
    return deg


def _lognormal_degree_sequence(num_nodes: int, num_edges: int,
                               rng) -> np.ndarray:
    """In-degree sequence lognormal-skewed like real social graphs —
    shared by the benchmark-scale generators."""
    raw = rng.lognormal(mean=0.0, sigma=1.25, size=num_nodes)
    return _degree_sequence(raw, num_edges, rng)


def zipf_csr(num_nodes: int, num_edges: int, a: float = 1.0,
             seed: int = 0, shuffle: bool = True) -> Graph:
    """Benchmark-scale CSR with **Zipf in-degrees**: the vertex ranked
    k gets degree ∝ k^-a — a heavier hub tail than the lognormal
    draw, the stress case for edge-balanced partitioning (a handful
    of hubs can hold a whole partition cap's worth of edges).
    ``shuffle=True`` scatters the ranks over random vertex ids so the
    hubs are not id-contiguous.  Uniform random sources; not
    symmetric — timing/partitioning use only."""
    assert num_edges >= num_nodes, "need >= 1 edge per node"
    rng = np.random.RandomState(seed)
    raw = np.arange(1, num_nodes + 1, dtype=np.float64) ** (-a)
    if shuffle:
        rng.shuffle(raw)
    deg = _degree_sequence(raw, num_edges, rng)
    row_ptr = np.zeros(num_nodes + 1, dtype=np.int64)
    np.cumsum(deg, out=row_ptr[1:])
    col_idx = rng.randint(0, num_nodes, size=num_edges, dtype=np.int64)
    return Graph(row_ptr=row_ptr, col_idx=col_idx.astype(np.int32))


def planted_community_csr(num_nodes: int, num_edges: int,
                          community_rows: int = 65_536,
                          intra_frac: float = 0.8, seed: int = 0,
                          shuffle: bool = True,
                          src_skew: float = 0.0) -> Graph:
    """Benchmark-scale dst-major CSR with PLANTED community structure:
    each edge's source lands in its destination's community block with
    probability ``intra_frac``, uniformly elsewhere otherwise.  With
    ``shuffle=True`` vertex ids are randomly relabeled afterwards —
    the worst case for locality, which a reordering pass
    (core/reorder.py bfs_order) should be able to recover.
    ``src_skew`` > 0 additionally skews WHICH community member is
    picked (u**(1+src_skew) mapping), modelling hub sources.  Same
    lognormal in-degree sequence as :func:`random_csr`.  Not
    symmetric — timing use only."""
    assert num_edges >= num_nodes
    rng = np.random.RandomState(seed)
    deg = _lognormal_degree_sequence(num_nodes, num_edges, rng)
    row_ptr = np.zeros(num_nodes + 1, dtype=np.int64)
    np.cumsum(deg, out=row_ptr[1:])
    dst_all = np.repeat(np.arange(num_nodes, dtype=np.int64), deg)
    com_of = dst_all // community_rows
    com_lo = com_of * community_rows
    com_hi = np.minimum(com_lo + community_rows, num_nodes)
    u = rng.rand(num_edges)
    if src_skew > 0.0:
        u = u ** (1.0 + src_skew)
    local = com_lo + np.floor(u * (com_hi - com_lo)).astype(np.int64)
    anywhere = rng.randint(0, num_nodes, size=num_edges)
    intra = rng.rand(num_edges) < intra_frac
    col = np.where(intra, local, anywhere)
    if shuffle:
        relabel = rng.permutation(num_nodes).astype(np.int64)
        col = relabel[col]
        # destinations relabel too: re-sort edges by new dst
        new_dst = relabel[dst_all]
        order = np.argsort(new_dst, kind="stable")
        col = col[order]
        new_deg = np.bincount(new_dst, minlength=num_nodes)
        row_ptr = np.zeros(num_nodes + 1, dtype=np.int64)
        np.cumsum(new_deg, out=row_ptr[1:])
    del anywhere, local, u, com_of, com_lo, com_hi, dst_all
    return Graph(row_ptr=row_ptr, col_idx=col.astype(np.int32))


def synthetic_graph(num_nodes: int, avg_degree: int, seed: int = 0,
                    power_law: bool = False) -> Graph:
    """Random symmetric graph with self edges.  ``power_law=True`` skews
    degrees like real social graphs (Reddit-ish) to stress edge-balanced
    partitioning."""
    rng = np.random.RandomState(seed)
    n_rand = num_nodes * max(avg_degree - 1, 0) // 2
    if power_law and n_rand > 0:
        # preferential-attachment-flavored endpoints
        p = 1.0 / (np.arange(num_nodes) + 10.0)
        p /= p.sum()
        src = rng.choice(num_nodes, size=n_rand, p=p).astype(np.int64)
        dst = rng.randint(0, num_nodes, size=n_rand).astype(np.int64)
    else:
        src = rng.randint(0, num_nodes, size=n_rand).astype(np.int64)
        dst = rng.randint(0, num_nodes, size=n_rand).astype(np.int64)
    g = from_edge_list(src, dst, num_nodes, symmetrize=True)
    return add_self_edges(g)


def synthetic_dataset(num_nodes: int = 128, avg_degree: int = 8,
                      in_dim: int = 16, num_classes: int = 4,
                      seed: int = 0, homophily: float = 0.8,
                      name: str = "synthetic") -> Dataset:
    """Deterministic learnable fixture: a homophilous graph (edges mostly
    intra-class, like Cora/Reddit) with class-informative features
    (cluster means + noise), so a GCN converges quickly — the stand-in
    for the reference's convergence-as-test strategy (SURVEY §4)."""
    rng = np.random.RandomState(seed + 1)
    labels = rng.randint(0, num_classes, size=num_nodes).astype(np.int32)
    # homophilous edges: src random; dst same-class with prob
    # `homophily`.  Fully vectorized — same-class picks index into the
    # label-sorted id list via per-class offsets — so the generator
    # reaches benchmark scale (57M draws for Reddit-shaped E; the old
    # per-edge Python loop capped it at toy sizes).
    n_rand = num_nodes * max(avg_degree - 1, 0) // 2
    src = rng.randint(0, num_nodes, size=n_rand).astype(np.int64)
    order = np.argsort(labels, kind="stable")
    class_start = np.zeros(num_classes + 1, dtype=np.int64)
    np.cumsum(np.bincount(labels, minlength=num_classes),
              out=class_start[1:])
    src_lab = labels[src]
    sizes = np.maximum(class_start[src_lab + 1] - class_start[src_lab],
                       1)
    pick = class_start[src_lab] + np.minimum(
        np.floor(rng.rand(n_rand) * sizes).astype(np.int64), sizes - 1)
    same = rng.rand(n_rand) < homophily
    dst = np.where(same, order[pick],
                   rng.randint(0, num_nodes, size=n_rand))
    graph = add_self_edges(from_edge_list(src, dst, num_nodes,
                                          symmetrize=True))
    means = rng.randn(num_classes, in_dim).astype(np.float32) * 2.0
    feats = means[labels] + rng.randn(num_nodes, in_dim).astype(np.float32)
    mask = np.full(num_nodes, MASK_NONE, dtype=np.int32)
    split = rng.rand(num_nodes)
    mask[split < 0.5] = MASK_TRAIN
    mask[(split >= 0.5) & (split < 0.75)] = MASK_VAL
    mask[split >= 0.75] = MASK_TEST
    return Dataset(graph=graph, features=feats.astype(np.float32),
                   labels=labels, mask=mask, num_classes=num_classes,
                   name=name)
