"""Substrate ``skewed_homophilous``: a symmetric, self-edged,
class-homophilous graph with lognormal degree skew, at a public
dataset's V, stored-edge count, feature width and class count.

A copy-and-extend of ``roc_tpu/core/graph.py synthetic_dataset`` (the
benchmark owns its generator: a PR it judges cannot change it).  What
is added is the skew: both endpoints of every random edge are drawn in
proportion to a per-vertex lognormal weight, sigma 1.25 — the value the
program's own "real social graph" generators use
(``_lognormal_degree_sequence``).  A uniform-degree graph flatters every
padded table layout.  Labels are uniform over vertex ids, so ids carry
no locality.

Two draws, two seeds:

* :func:`make_topology` — edges and labels, from the traffic file's
  ``graph_seed``.  Table shapes follow the topology and a new shape is a
  cold compile, so every run of a cell sees the same topology.
* :func:`make_features` — features and the train/val/test mask, from
  ``--seed``.

Everything is vectorised numpy: Reddit's 115 M stored edges take about a
minute, once per checkout (the harness caches the topology on disk).
"""

from __future__ import annotations

from typing import Dict

import numpy as np

MASK_NONE, MASK_TRAIN, MASK_VAL, MASK_TEST = 0, 1, 2, 3   # gnn.h:98-103

DEFAULTS = {"degree_sigma": 1.25, "homophily": 0.8,
            "feature_mean_scale": 2.0, "train_frac": 0.5, "val_frac": 0.25}

# the distinct-pair count is steered to the target by repeated draws
# and a surplus is thinned, so E lands within 0.1% of it
_MAX_ROUNDS = 12
_CLOSE_ENOUGH = 0.999
# pairs per draw: bounds the working set (fresh pages are the slow part
# of a large draw on a new machine)
_DRAW_CHUNK = 1 << 23


def _weighted_pairs(n: int, rng, cw: np.ndarray, order: np.ndarray,
                    class_lo: np.ndarray, class_hi: np.ndarray,
                    labels: np.ndarray, homophily: float) -> np.ndarray:
    """``n`` undirected pair keys ``lo << 32 | hi`` (self pairs
    dropped).  The first endpoint is drawn in proportion to its weight
    over all vertices; the second, with probability ``homophily``, over
    the first's class, else over all vertices — always by weight.
    ``cw`` is the cumulative weight in class-sorted vertex order.  The
    first endpoint's uniforms are sorted before the look-up (pairs are a
    set: their order carries nothing), which keeps both look-ups inside
    one class's stretch of ``cw`` at a time."""
    V = order.shape[0]
    total = cw[-1]
    u = rng.random(n)
    u.sort()
    u *= total
    a = order[np.minimum(np.searchsorted(cw, u, side="right"), V - 1)]
    lab = labels[a]
    same = rng.random(n) < homophily
    lo_w = np.where(same, class_lo[lab], 0.0)
    u = rng.random(n)
    u *= np.where(same, class_hi[lab], total) - lo_w
    u += lo_w
    b = order[np.minimum(np.searchsorted(cw, u, side="right"), V - 1)]
    keep = a != b
    a, b = a[keep].astype(np.int64), b[keep].astype(np.int64)
    return (np.minimum(a, b) << 32) | np.maximum(a, b)


def _sorted_unique(keys: np.ndarray) -> np.ndarray:
    """``np.unique`` of an int64 array, in place where it can be."""
    if keys.shape[0] == 0:
        return keys
    keys.sort()
    keep = np.empty(keys.shape[0], dtype=bool)
    keep[0] = True
    np.not_equal(keys[1:], keys[:-1], out=keep[1:])
    return keys[keep]


def make_topology(num_nodes: int, num_edges: int, num_classes: int,
                  graph_seed: int, **params) -> Dict[str, np.ndarray]:
    """``{"row_ptr": int64 [V+1], "col_idx": int32 [E], "labels": int32
    [V]}``: destination-major CSR of a symmetric graph holding every
    self edge, with ``E`` within 1% of ``num_edges`` (self edges and
    both directions counted, as the program stores them)."""
    p = {**DEFAULTS, **params}
    V = int(num_nodes)
    want_pairs = (int(num_edges) - V) // 2
    if want_pairs < 0:
        raise ValueError(f"num_edges {num_edges} < num_nodes {V}: every "
                         f"vertex stores its self edge")
    if want_pairs > V * (V - 1) // 2:
        raise ValueError(f"{want_pairs} distinct pairs do not exist "
                         f"among {V} vertices")
    rng = np.random.default_rng([int(graph_seed), 0])
    labels = rng.integers(0, num_classes, size=V).astype(np.int32)
    weight = rng.lognormal(mean=0.0, sigma=p["degree_sigma"], size=V)
    order = np.argsort(labels, kind="stable")
    cw = np.cumsum(weight[order])
    ends = np.cumsum(np.bincount(labels, minlength=num_classes))
    class_hi = cw[np.maximum(ends - 1, 0)]
    class_lo = np.concatenate([[0.0], class_hi[:-1]])

    keys = np.zeros(0, dtype=np.int64)
    yield_rate = 1.0               # distinct new pairs per draw, so far
    for _ in range(_MAX_ROUNDS):
        short = want_pairs - keys.shape[0]
        if short <= want_pairs * (1.0 - _CLOSE_ENOUGH):
            break
        n = int(short / max(yield_rate, 0.05)) + 16
        before = keys.shape[0]
        drawn = [keys]
        for start in range(0, n, _DRAW_CHUNK):
            drawn.append(_weighted_pairs(
                min(_DRAW_CHUNK, n - start), rng, cw, order, class_lo,
                class_hi, labels, p["homophily"]))
        keys = _sorted_unique(np.concatenate(drawn))
        del drawn
        yield_rate = max((keys.shape[0] - before) / n, 1e-3)
    if keys.shape[0] > want_pairs:
        # thin the surplus: an exact-count random choice of victims
        keep = np.ones(keys.shape[0], dtype=bool)
        keep[rng.choice(keys.shape[0], keys.shape[0] - want_pairs,
                        replace=False)] = False
        keys = keys[keep]
    lo, hi = keys >> 32, keys & 0xFFFFFFFF
    del keys
    diag = np.arange(V, dtype=np.int64)
    # dst-major: key = dst << 32 | src, both directions and the diagonal
    full = np.concatenate([(lo << 32) | hi, (hi << 32) | lo,
                           (diag << 32) | diag])
    del lo, hi
    full.sort()
    col_idx = (full & 0xFFFFFFFF).astype(np.int32)
    full >>= 32
    row_ptr = np.zeros(V + 1, dtype=np.int64)
    np.cumsum(np.bincount(full, minlength=V), out=row_ptr[1:])
    return {"row_ptr": row_ptr, "col_idx": col_idx, "labels": labels}


def make_features(labels: np.ndarray, in_dim: int, num_classes: int,
                  seed: int, **params) -> Dict[str, np.ndarray]:
    """``{"features": float32 [V, in_dim], "mask": int32 [V]}``: class
    means plus unit noise (so informative that the model is learnt in a
    step or two: a does-it-learn signal, not a convergence benchmark)
    and a 50/25/25 train/val/test split."""
    p = {**DEFAULTS, **params}
    V = labels.shape[0]
    rng = np.random.default_rng([int(seed), 1])
    means = (rng.standard_normal((num_classes, in_dim), dtype=np.float32)
             * np.float32(p["feature_mean_scale"]))
    feats = rng.standard_normal((V, in_dim), dtype=np.float32)
    feats += means[labels]
    split = rng.random(V)
    mask = np.full(V, MASK_NONE, dtype=np.int32)
    t, v = p["train_frac"], p["train_frac"] + p["val_frac"]
    mask[split < t] = MASK_TRAIN
    mask[(split >= t) & (split < v)] = MASK_VAL
    mask[split >= v] = MASK_TEST
    return {"features": feats, "mask": mask}
