"""ctypes bindings for the native host-side data layer (librocio.so).

The reference implements its entire data path in C++ host code inside
CUDA task bodies (``load_task.cu``, ``gnn.cc:751-872``); here the same
components live in ``native/rocio.cc`` behind a C ABI, loaded lazily
via ctypes.  Every entry point has a pure-numpy fallback in
``roc_tpu.core`` — the native library is a performance path, not a hard
dependency, so ``available()`` gates all call sites.  It does decide
what ``aggr_impl='auto'`` can choose (the bdense structure probe is
native-only), so a library that failed to build or load is never
silent: the reason is echoed once as a ``resolve`` event and recorded
in every run manifest (:func:`status`).

The library is built with ``make -C native`` (attempted automatically
on first use); :func:`rebuild` forces a fresh build and raises when
the result does not load.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
from typing import Optional, Tuple

import numpy as np

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "native")
_LIB_PATH = os.environ.get(
    "ROC_TPU_NATIVE", os.path.join(_NATIVE_DIR, "librocio.so"))

_ABI_VERSION = 5

_lib: Optional[ctypes.CDLL] = None
_tried = False
_why: Optional[str] = None      # why _lib is None after a load attempt


def _i64p(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))


def _i32p(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))


def _pinned() -> bool:
    """A library pinned via ROC_TPU_NATIVE is an explicit operator
    override: never rebuilt, trusted as-is."""
    return "ROC_TPU_NATIVE" in os.environ


def _stale() -> bool:
    """True when a previously built .so is older than its source —
    rebuilding then keeps native tests validating current code (the
    binary is a build artifact, never checked in)."""
    try:
        lib_mtime = os.path.getmtime(_LIB_PATH)
        return any(
            os.path.getmtime(os.path.join(_NATIVE_DIR, f)) > lib_mtime
            for f in ("rocio.cc", "Makefile"))
    except OSError:
        return False


def _make(force: bool = False) -> Optional[str]:
    """``make -C native`` (``-B`` when ``force``); None on success,
    else the reason it failed."""
    cmd = ["make", "-C", _NATIVE_DIR] + (["-B"] if force else [])
    try:
        r = subprocess.run(cmd, capture_output=True, text=True,
                           timeout=120, check=False)
    except (OSError, subprocess.TimeoutExpired) as e:
        return f"{' '.join(cmd)}: {e!r}"
    if r.returncode != 0:
        return (f"{' '.join(cmd)} exited {r.returncode}: "
                f"{r.stderr.strip()[-400:]}")
    return None


def _abi_of(lib: ctypes.CDLL) -> int:
    try:
        lib.roc_abi_version.restype = ctypes.c_int
        return int(lib.roc_abi_version())
    except AttributeError:
        return 1  # predates the version export


def _dlopen():
    """(lib, None) or (None, reason) for the library at ``_LIB_PATH``."""
    if not os.path.exists(_LIB_PATH):
        return None, f"{_LIB_PATH} does not exist"
    try:
        lib = ctypes.CDLL(_LIB_PATH)
    except OSError as e:
        return None, f"dlopen {_LIB_PATH}: {e}"
    # ABI gate: the argtypes in _load describe THIS source tree's C
    # signatures; a .so from before an ABI bump would read a pointer
    # slot as an int (SIGSEGV or silent garbage)
    got = _abi_of(lib)
    if got != _ABI_VERSION:
        return None, (f"{_LIB_PATH} has ABI v{got}, this tree expects "
                      f"v{_ABI_VERSION}")
    return lib, None


def _open():
    """(lib, None) or (None, reason).  Builds the in-tree library when
    it is missing or older than its source, and once more from scratch
    when what it then finds does not load — an ABI bump a copied
    tree's rewritten mtimes hid from :func:`_stale`."""
    if _pinned():
        return _dlopen()
    found = os.path.exists(_LIB_PATH)
    build_err = _make() if not found or _stale() else None
    lib, why = _dlopen()
    if lib is None and found and build_err is None:
        build_err = _make(force=True)
        if build_err is None:
            lib, why = _dlopen()
    if lib is None and build_err:
        why = f"{why}; build: {build_err}"
    return lib, why


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _tried, _why
    if _lib is not None or _tried:
        return _lib
    _tried = True
    lib, _why = _open()
    if lib is None:
        from .obs.events import emit
        emit("resolve", f"native librocio.so unavailable — {_why}; "
             f"numpy host paths in use and aggr_impl='auto' cannot "
             f"probe for bdense", native=False, reason=_why)
        return None
    # Full argtypes: int64_t params must not fall back to the 32-bit
    # c_int default (graphs with > 2^31 edges are in scope for the
    # streaming tier).
    c = ctypes
    i64, i32p, i64p, f32p = (c.c_int64, c.POINTER(c.c_int32),
                             c.POINTER(c.c_int64), c.POINTER(c.c_float))
    lib.roc_lux_header.restype = c.c_int
    lib.roc_lux_header.argtypes = [c.c_char_p, c.POINTER(c.c_uint32),
                                   c.POINTER(c.c_uint64)]
    lib.roc_lux_read.restype = c.c_int
    lib.roc_lux_read.argtypes = [c.c_char_p, i64, i64, i64p, i32p]
    lib.roc_lux_write.restype = c.c_int
    lib.roc_lux_write.argtypes = [c.c_char_p, i64, i64, i64p, i32p]
    lib.roc_load_features_csv.restype = c.c_int
    lib.roc_load_features_csv.argtypes = [c.c_char_p, f32p, i64, i64]
    lib.roc_load_features_csv_rows.restype = c.c_int
    lib.roc_load_features_csv_rows.argtypes = [c.c_char_p, f32p, i64,
                                               i64, i64]
    lib.roc_load_mask.restype = c.c_int
    lib.roc_load_mask.argtypes = [c.c_char_p, i32p, i64]
    lib.roc_edge_balanced_bounds.restype = c.c_int
    lib.roc_edge_balanced_bounds.argtypes = [i64p, i64, i64, i64p]
    lib.roc_add_self_edges.restype = c.c_int64
    lib.roc_add_self_edges.argtypes = [i64p, i32p, i64, i64p, i32p, i64]
    lib.roc_ell_widths.restype = c.c_int
    lib.roc_ell_widths.argtypes = [i64p, i64, c.c_int32, i32p]
    lib.roc_sectioned_counts.restype = c.c_int
    lib.roc_sectioned_counts.argtypes = [i64p, i32p, i64, i64, i64, i64,
                                         i64p]
    lib.roc_sectioned_fill.restype = c.c_int
    lib.roc_sectioned_fill.argtypes = [i64p, i32p, i64, i64, i64, i64,
                                       i64p, i64p, i32p, i32p]
    u8p = c.POINTER(c.c_uint8)
    lib.roc_block_counts.restype = c.c_int64
    lib.roc_block_counts.argtypes = [i64p, i32p, i64, i64, i64, i64p,
                                     i64p, i64]
    lib.roc_block_fill.restype = c.c_int64
    lib.roc_block_fill.argtypes = [i64p, i32p, i64, i64, i64, i64p,
                                   i64, u8p, i64p, i32p, i64]
    lib.roc_lpa_iterate.restype = c.c_int64
    lib.roc_lpa_iterate.argtypes = [i64p, i32p, i64, i32p, i32p]
    _lib = lib
    return _lib


def rebuild() -> None:
    """Build the in-tree library afresh (``make -B``) and load it;
    raises when the build fails or the result does not load.  For
    callers that must not run on a library they merely found
    (chip_smoke.py: a copied tree keeps ignored build products and
    rewrites the mtimes :func:`_stale` compares)."""
    global _lib, _tried, _why
    if _lib is not None:
        raise RuntimeError("native.rebuild() after the library was "
                           "loaded: call it before first use")
    err = _make(force=True)
    if err:
        raise RuntimeError(f"native build failed: {err}")
    _tried = False
    if _load() is None:
        raise RuntimeError(f"native library did not load: {_why}")


def status() -> dict:
    """{'loaded', 'path', 'reason'} for the run manifest: which host
    data path this process runs on, and why when it is not native."""
    loaded = _load() is not None
    return {"loaded": loaded, "path": _LIB_PATH,
            "reason": None if loaded else _why}


def available() -> bool:
    return _load() is not None


def load_lux(path: str) -> Tuple[np.ndarray, np.ndarray]:
    """(row_ptr int64 [V+1], col_idx int32 [E]) from a .lux file."""
    lib = _load()
    assert lib is not None
    nn = ctypes.c_uint32()
    ne = ctypes.c_uint64()
    rc = lib.roc_lux_header(path.encode(), ctypes.byref(nn),
                            ctypes.byref(ne))
    if rc != 0:
        raise IOError(f"roc_lux_header({path}) failed: {rc}")
    V, E = int(nn.value), int(ne.value)
    row_ptr = np.empty(V + 1, dtype=np.int64)
    col_idx = np.empty(E, dtype=np.int32)
    rc = lib.roc_lux_read(path.encode(), V, E, _i64p(row_ptr),
                          _i32p(col_idx))
    if rc != 0:
        raise IOError(f"roc_lux_read({path}) failed: {rc}")
    return row_ptr, col_idx


def save_lux(path: str, row_ptr: np.ndarray, col_idx: np.ndarray) -> None:
    lib = _load()
    assert lib is not None
    row_ptr = np.ascontiguousarray(row_ptr, dtype=np.int64)
    col_idx = np.ascontiguousarray(col_idx, dtype=np.int32)
    rc = lib.roc_lux_write(path.encode(), row_ptr.shape[0] - 1,
                           col_idx.shape[0], _i64p(row_ptr),
                           _i32p(col_idx))
    if rc != 0:
        raise IOError(f"roc_lux_write({path}) failed: {rc}")


def load_features_csv(path: str, rows: int, cols: int) -> np.ndarray:
    lib = _load()
    assert lib is not None
    out = np.empty((rows, cols), dtype=np.float32)
    rc = lib.roc_load_features_csv(
        path.encode(), out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        rows, cols)
    if rc != 0:
        raise IOError(f"roc_load_features_csv({path}) failed: {rc}")
    return out


def load_features_csv_rows(path: str, row_lo: int, row_hi: int,
                           cols: int) -> np.ndarray:
    """Partition-local CSV feature read: rows [row_lo, row_hi)."""
    lib = _load()
    assert lib is not None
    out = np.empty((row_hi - row_lo, cols), dtype=np.float32)
    rc = lib.roc_load_features_csv_rows(
        path.encode(), out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        row_lo, row_hi, cols)
    if rc != 0:
        raise IOError(f"roc_load_features_csv_rows({path}) failed: {rc}")
    return out


def load_mask(path: str, n: int) -> np.ndarray:
    lib = _load()
    assert lib is not None
    out = np.empty(n, dtype=np.int32)
    rc = lib.roc_load_mask(path.encode(), _i32p(out), n)
    if rc != 0:
        raise IOError(f"roc_load_mask({path}) failed: {rc}")
    return out


def edge_balanced_bounds(row_ptr: np.ndarray, num_parts: int) -> np.ndarray:
    """int64 [num_parts, 2] inclusive [left, right] ranges."""
    lib = _load()
    assert lib is not None
    row_ptr = np.ascontiguousarray(row_ptr, dtype=np.int64)
    bounds = np.empty((num_parts, 2), dtype=np.int64)
    rc = lib.roc_edge_balanced_bounds(
        _i64p(row_ptr), row_ptr.shape[0] - 1, num_parts, _i64p(bounds))
    if rc != 0:
        raise ValueError(f"roc_edge_balanced_bounds failed: {rc}")
    return bounds


def add_self_edges(row_ptr: np.ndarray, col_idx: np.ndarray
                   ) -> Tuple[np.ndarray, np.ndarray]:
    lib = _load()
    assert lib is not None
    row_ptr = np.ascontiguousarray(row_ptr, dtype=np.int64)
    col_idx = np.ascontiguousarray(col_idx, dtype=np.int32)
    V = row_ptr.shape[0] - 1
    cap = col_idx.shape[0] + V
    new_ptr = np.empty(V + 1, dtype=np.int64)
    new_col = np.empty(cap, dtype=np.int32)
    rc = lib.roc_add_self_edges(_i64p(row_ptr), _i32p(col_idx), V,
                                _i64p(new_ptr), _i32p(new_col), cap)
    if rc < 0:
        raise ValueError(f"roc_add_self_edges failed: {rc}")
    return new_ptr, new_col[: col_idx.shape[0] + int(rc)].copy()


def ell_widths(row_ptr: np.ndarray, min_width: int = 8) -> np.ndarray:
    """Per-row power-of-two ELL bucket width (0 for empty rows)."""
    lib = _load()
    assert lib is not None
    row_ptr = np.ascontiguousarray(row_ptr, dtype=np.int64)
    n = row_ptr.shape[0] - 1
    out = np.empty(n, dtype=np.int32)
    rc = lib.roc_ell_widths(_i64p(row_ptr), n, min_width, _i32p(out))
    if rc != 0:
        raise ValueError(f"roc_ell_widths failed: {rc}")
    return out


def sectioned_counts(row_ptr: np.ndarray, col_idx: np.ndarray,
                     num_rows: int, section_rows: int,
                     n_sec: int, sub_w: int = 8) -> np.ndarray:
    """Per-section width-``sub_w`` sub-row totals (core/ell.py
    sectioned prep, counts pass)."""
    lib = _load()
    assert lib is not None
    row_ptr = np.ascontiguousarray(row_ptr, dtype=np.int64)
    col_idx = np.ascontiguousarray(col_idx, dtype=np.int32)
    out = np.empty(n_sec, dtype=np.int64)
    rc = lib.roc_sectioned_counts(_i64p(row_ptr), _i32p(col_idx),
                                  num_rows, section_rows, n_sec,
                                  sub_w, _i64p(out))
    if rc != 0:
        raise ValueError(f"roc_sectioned_counts failed: {rc}")
    return out


def sectioned_fill(row_ptr: np.ndarray, col_idx: np.ndarray,
                   num_rows: int, section_rows: int,
                   sec_sizes: np.ndarray, slots: np.ndarray,
                   sub_w: int = 8) -> Tuple[np.ndarray, np.ndarray]:
    """Fill pass: (idx_flat [sum(slots), sub_w], sub_dst_flat
    [sum(slots)]) with per-section regions laid out consecutively in
    section order."""
    lib = _load()
    assert lib is not None
    row_ptr = np.ascontiguousarray(row_ptr, dtype=np.int64)
    col_idx = np.ascontiguousarray(col_idx, dtype=np.int32)
    sec_sizes = np.ascontiguousarray(sec_sizes, dtype=np.int64)
    slots = np.ascontiguousarray(slots, dtype=np.int64)
    total = int(slots.sum())
    idx_flat = np.empty((total, sub_w), dtype=np.int32)
    sub_dst = np.empty(total, dtype=np.int32)
    rc = lib.roc_sectioned_fill(
        _i64p(row_ptr), _i32p(col_idx), num_rows, section_rows,
        slots.shape[0], sub_w, _i64p(sec_sizes), _i64p(slots),
        _i32p(idx_flat), _i32p(sub_dst))
    if rc != 0:
        raise ValueError(f"roc_sectioned_fill failed: {rc}")
    return idx_flat, sub_dst


def block_counts(row_ptr: np.ndarray, col_idx: np.ndarray,
                 num_rows: int, block: int,
                 num_cols: int = None
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """(keys, counts) per occupied [block x block] adjacency tile,
    key-ascending (ops/blockdense.py plan_blocks, census pass).
    ``num_cols`` sets a rectangular tile space (distributed planner:
    local dst rows x gathered source coordinates); default square."""
    lib = _load()
    assert lib is not None
    if num_cols is None:
        num_cols = num_rows
    row_ptr = np.ascontiguousarray(row_ptr, dtype=np.int64)
    col_idx = np.ascontiguousarray(col_idx, dtype=np.int32)
    n_tiles = -(-num_rows // block)
    n_src_tiles = -(-num_cols // block)
    cap = int(min(n_tiles * n_src_tiles, col_idx.shape[0], 1 << 27))
    cap = max(cap, 1)
    while True:
        keys = np.empty(cap, dtype=np.int64)
        counts = np.empty(cap, dtype=np.int64)
        nnz = int(lib.roc_block_counts(
            _i64p(row_ptr), _i32p(col_idx), num_rows, num_cols, block,
            _i64p(keys), _i64p(counts), cap))
        if nnz < 0:
            raise ValueError(f"roc_block_counts failed: {nnz}")
        if nnz <= cap:
            return keys[:nnz].copy(), counts[:nnz].copy()
        cap = nnz


def block_fill(row_ptr: np.ndarray, col_idx: np.ndarray,
               num_rows: int, block: int, dense_keys: np.ndarray,
               num_cols: int = None
               ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(a_blocks uint8 [nblk, block, block], res_row_ptr, res_col):
    fill the selected tiles' multiplicity tables, spill the rest (and
    saturated duplicates) to a residual dst-major CSR.  ``num_cols``
    as in :func:`block_counts`."""
    lib = _load()
    assert lib is not None
    if num_cols is None:
        num_cols = num_rows
    row_ptr = np.ascontiguousarray(row_ptr, dtype=np.int64)
    col_idx = np.ascontiguousarray(col_idx, dtype=np.int32)
    dense_keys = np.ascontiguousarray(dense_keys, dtype=np.int64)
    nblk = dense_keys.shape[0]
    a = np.zeros((nblk, block, block), dtype=np.uint8)
    res_ptr = np.empty(num_rows + 1, dtype=np.int64)
    res_col = np.empty(col_idx.shape[0], dtype=np.int32)
    rc = int(lib.roc_block_fill(
        _i64p(row_ptr), _i32p(col_idx), num_rows, num_cols, block,
        _i64p(dense_keys), nblk,
        a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        _i64p(res_ptr), _i32p(res_col), res_col.shape[0]))
    if rc < 0:
        raise ValueError(f"roc_block_fill failed: {rc}")
    return a, res_ptr, res_col[:rc].copy()


def lpa_iterate(nbr_ptr: np.ndarray, nbr: np.ndarray,
                labels: np.ndarray) -> Tuple[np.ndarray, int]:
    """One ASYNCHRONOUS label-propagation sweep over an undirected
    CSR, in increasing vertex order (core/reorder.py lpa_labels):
    returns (new_labels, changed)."""
    lib = _load()
    assert lib is not None
    nbr_ptr = np.ascontiguousarray(nbr_ptr, dtype=np.int64)
    nbr = np.ascontiguousarray(nbr, dtype=np.int32)
    labels = np.ascontiguousarray(labels, dtype=np.int32)
    out = np.empty_like(labels)
    rc = int(lib.roc_lpa_iterate(
        _i64p(nbr_ptr), _i32p(nbr), labels.shape[0],
        _i32p(labels), _i32p(out)))
    if rc < 0:
        raise ValueError(f"roc_lpa_iterate failed: {rc}")
    return out, rc
