"""ctypes bindings for the C++ data-plane kernels (``kernels.cc``).

The reference's native layer is Ray core (C++) plus pandas/pyarrow; this
package is the standalone equivalent for the shuffle pipeline's host-side
hot ops: permutation gathers, fused concat+gather, stable group-by
partitioning, and narrowing casts (see ``kernels.cc`` for the
reference-file citations per op).

Loading strategy:

1. build the library once from the tracked source with ``g++ -O3 -shared
   -fPIC -pthread`` into a per-user cache dir, under a name that carries
   the source's digest (no pip/cmake involved; nothing prebuilt is ever
   loaded in its place);
2. else (no toolchain / build failure) every wrapper falls back to an
   equivalent numpy expression — correctness never depends on the native
   build, only throughput does. :func:`native_available` says which.

Set ``RSDL_DISABLE_NATIVE=1`` to force the numpy paths (used by tests to
compare both implementations).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import sys
import tempfile
import threading
from typing import List, Optional, Sequence

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "kernels.cc")
_LIB_BASENAME = "librsdl_native.so"

_lib = None
_lib_lock = threading.Lock()
_load_attempted = False

ENV_THREADS = "RSDL_NATIVE_THREADS"


def _threads_from_env() -> int:
    """Kernel thread count: ``RSDL_NATIVE_THREADS`` when set (clamped
    ≥ 1), else the old heuristic — gathers are memory-bound, so a
    handful of threads saturates DRAM and more just adds spawn cost."""
    env = os.environ.get(ENV_THREADS, "").strip()
    if env:
        try:
            return max(1, int(env))
        except ValueError:
            pass
    return max(1, min(8, (os.cpu_count() or 1)))


# Read once at import (the knob is a process-level setting, like the
# telemetry gates); tools that sweep thread counts pass n_threads= per
# call instead of mutating the env.
_NUM_THREADS = _threads_from_env()


def num_threads() -> int:
    """The resolved default kernel thread count (``RSDL_NATIVE_THREADS``)."""
    return _NUM_THREADS


def refresh_threads_from_env() -> None:
    """Re-read ``RSDL_NATIVE_THREADS`` (tests)."""
    global _NUM_THREADS
    _NUM_THREADS = _threads_from_env()


def set_num_threads(n: Optional[int]) -> None:
    """Set the process default kernel thread count. The planner's
    delivery path for its ``native_threads`` term: stage tasks apply
    the planned value on entry (env snapshots date from pool spawn, so
    the env-read default can't carry it). None is a no-op."""
    global _NUM_THREADS
    if n is not None:
        _NUM_THREADS = max(1, int(n))


def _resolve_threads(n_threads: Optional[int]) -> int:
    return _NUM_THREADS if n_threads is None else max(1, int(n_threads))


# Thread-slice floor shared with the C side's parallel_for cap: one
# thread per ~524k rows. Below ~1 ms of per-slice work the std::thread
# spawn cost dominates and threading is a measured LOSS (the r7 sweep at
# 372k rows ran 0.6-0.9x serial uncapped); the parallel group scatter
# engages only when at least two such slices exist.
_MIN_ROWS_PER_THREAD = 1 << 19


def _build_lib() -> Optional[str]:
    """Compile kernels.cc into a cached .so; returns its path or None."""
    try:
        with open(_SRC, "rb") as f:
            digest = hashlib.sha256(f.read()).hexdigest()[:16]
    except OSError:
        return None
    cache_dir = os.environ.get("RSDL_NATIVE_CACHE") or os.path.join(
        tempfile.gettempdir(), f"rsdl-native-{os.getuid()}"
    )
    out = os.path.join(cache_dir, f"{digest}-{_LIB_BASENAME}")
    if os.path.exists(out):
        return out
    os.makedirs(cache_dir, exist_ok=True)
    tmp = out + f".build-{os.getpid()}"
    cmd = [
        "g++",
        "-O3",
        "-std=c++17",
        "-shared",
        "-fPIC",
        "-pthread",
        _SRC,
        "-o",
        tmp,
    ]
    try:
        subprocess.run(
            cmd, check=True, capture_output=True, timeout=120
        )
        os.rename(tmp, out)  # atomic publish for concurrent builders
        return out
    except (subprocess.SubprocessError, OSError) as exc:
        print(
            f"[rsdl.native] build failed, using numpy fallbacks: {exc}",
            file=sys.stderr,
        )
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return None


def _declare(lib: ctypes.CDLL) -> ctypes.CDLL:
    c_i64 = ctypes.c_int64
    c_int = ctypes.c_int
    p = ctypes.c_void_p
    lib.rsdl_take.argtypes = [p, p, p, c_i64, c_i64, c_i64, c_int]
    lib.rsdl_take.restype = c_int
    lib.rsdl_take_multi.argtypes = [p, p, c_i64, p, p, c_i64, c_i64, c_int]
    lib.rsdl_take_multi.restype = c_int
    lib.rsdl_cast_i64_i32.argtypes = [p, p, c_i64, c_int]
    lib.rsdl_cast_i64_i32_checked.argtypes = [p, p, c_i64, c_int]
    lib.rsdl_cast_i64_i32_checked.restype = c_int
    lib.rsdl_cast_f64_f32.argtypes = [p, p, c_i64, c_int]
    lib.rsdl_group_rows.argtypes = [p, p, p, c_i64, c_i64, p]
    lib.rsdl_scatter.argtypes = [p, p, p, c_i64, c_i64, c_i64, c_int]
    lib.rsdl_scatter.restype = c_int
    lib.rsdl_group_plan.argtypes = [p, c_i64, c_i64, c_int, p, p]
    lib.rsdl_group_rows_multi_mt.argtypes = [
        p, p, p, c_i64, p, c_i64, p, c_int, c_i64
    ]
    return lib


def _get_lib() -> Optional[ctypes.CDLL]:
    global _lib, _load_attempted
    if _lib is not None or _load_attempted:
        return _lib
    with _lib_lock:
        if _lib is not None or _load_attempted:
            return _lib
        _load_attempted = True
        if os.environ.get("RSDL_DISABLE_NATIVE"):
            return None
        built = _build_lib()
        if built is not None:
            _lib = _declare(ctypes.CDLL(built))
        return _lib


def native_available() -> bool:
    return _get_lib() is not None


def _ptr(arr: np.ndarray):
    return arr.ctypes.data_as(ctypes.c_void_p)


def _rows_contig(arr: np.ndarray) -> Optional[int]:
    """Bytes per row if arr is C-contiguous (row = one index-0 slice)."""
    if not arr.flags.c_contiguous:
        return None
    return int(arr.dtype.itemsize * int(np.prod(arr.shape[1:], dtype=np.int64)))


def _check_bounds(idx: np.ndarray, n: int) -> bool:
    """True if idx is safe for the unchecked C gathers; raises on
    out-of-range exactly like numpy. Non-integer index arrays (bool masks,
    floats) and negative indices route to the numpy fallback, which
    implements their semantics."""
    if len(idx) == 0 or not np.issubdtype(idx.dtype, np.integer):
        return False
    lo, hi = int(idx.min()), int(idx.max())
    if hi >= n or lo < -n:
        raise IndexError(
            f"index out of bounds for axis 0 with size {n}: [{lo}, {hi}]"
        )
    return lo >= 0


def _out_ok(out: Optional[np.ndarray], shape, dtype) -> bool:
    """Strict ``out=`` contract: providing a destination that cannot hold
    the result is a caller bug and raises — silently falling back to a
    fresh array would publish an untouched (zero) segment in the
    direct-to-store write paths."""
    if out is None:
        return False
    if (
        out.shape != tuple(shape)
        or out.dtype != dtype
        or not out.flags.c_contiguous
        or not out.flags.writeable
    ):
        raise ValueError(
            f"out= mismatch: need {tuple(shape)} {dtype} C-contiguous "
            f"writable, got {out.shape} {out.dtype} "
            f"(contig={out.flags.c_contiguous}, "
            f"writable={out.flags.writeable})"
        )
    return True


def take(
    arr: np.ndarray,
    idx: np.ndarray,
    out: Optional[np.ndarray] = None,
    n_threads: Optional[int] = None,
) -> np.ndarray:
    """``arr[idx]`` along axis 0 (multi-threaded when native is loaded).

    ``out``: pre-allocated destination (e.g. a writable store-segment view
    from ``ObjectStore.create_columns``) — the gather lands directly in
    shared memory, skipping the copy-out a fresh array would need.
    ``n_threads`` overrides the ``RSDL_NATIVE_THREADS`` default.

    Bounds are checked INSIDE the kernel (free per row): the old Python
    ``idx.min()/idx.max()`` pre-scan cost two full single-threaded
    passes per call, a fixed term that measurably capped multi-core
    scaling. The rare failure (out-of-range raises, negative indices
    fall back) re-derives exact numpy semantics off the hot path."""
    lib = _get_lib()
    row_bytes = _rows_contig(arr)
    idx_arr = np.asarray(idx)
    shape = (len(idx_arr), *arr.shape[1:])
    if (
        lib is not None
        and row_bytes is not None
        and arr.size != 0
        and len(idx_arr) != 0
        and np.issubdtype(idx_arr.dtype, np.integer)
    ):
        idx_c = np.ascontiguousarray(idx_arr, dtype=np.int64)
        if not _out_ok(out, shape, arr.dtype):
            out = np.empty(shape, dtype=arr.dtype)
        rc = lib.rsdl_take(
            _ptr(arr), _ptr(out), _ptr(idx_c), len(idx_c), row_bytes,
            len(arr), _resolve_threads(n_threads),
        )
        if rc == 0:
            return out
        try:
            _check_bounds(idx_arr, len(arr))  # IndexError if truly OOB
        except IndexError:
            # The kernel may have partially written ``out`` before the
            # bad index was hit; restore the fresh-segment invariant
            # (direct-to-store destinations start zeroed) before
            # surfacing the error — error-path only, never a hot cost.
            out[...] = 0
            raise
        np.take(arr, idx_arr, axis=0, out=out)  # negative-index semantics
        return out
    if _out_ok(out, shape, arr.dtype):
        np.take(arr, idx_arr, axis=0, out=out)
        return out
    return arr[idx]


def scatter(
    src: np.ndarray,
    idx: np.ndarray,
    out: np.ndarray,
    n_threads: Optional[int] = None,
) -> np.ndarray:
    """``out[idx] = src`` along axis 0 — the write-side inverse of
    :func:`take`, multi-threaded when native is loaded.

    The overlapped reduce's hot op: each arriving partition window lands
    at its permuted output rows (``idx`` = a slice of the inverted epoch
    permutation) while later windows are still in flight over DCN — the
    C call releases the GIL, so the scatter uses every core concurrently
    with the prefetch threads' socket reads.

    ``idx`` values must be UNIQUE (permutation-derived): numpy resolves
    duplicate destinations last-write-wins, but across kernel threads
    the winner would be racy — callers with possibly-duplicated indices
    must use the numpy assignment directly. Non-integer / negative /
    out-of-range indices fall back to (or raise like) numpy; on the
    out-of-range raise, already-scattered rows of ``out`` keep their
    new values (``out`` accumulates across calls in the overlapped
    reduce, so "restore" has no meaning here — the failing task aborts
    its pending segment instead)."""
    src = np.asarray(src)
    idx_arr = np.asarray(idx)
    if len(src) != len(idx_arr):
        raise ValueError(
            f"scatter length mismatch: {len(src)} rows vs {len(idx_arr)} "
            "indices"
        )
    lib = _get_lib()
    row_bytes = _rows_contig(src)
    if (
        lib is None
        or row_bytes is None
        or row_bytes != _rows_contig(out)
        or src.dtype != out.dtype
        or src.shape[1:] != out.shape[1:]
        or not out.flags.writeable
        or src.size == 0
        or not np.issubdtype(idx_arr.dtype, np.integer)
    ):
        out[idx_arr] = src
        return out
    idx_c = np.ascontiguousarray(idx_arr, dtype=np.int64)
    rc = lib.rsdl_scatter(
        _ptr(src), _ptr(out), _ptr(idx_c), len(idx_c), row_bytes,
        len(out), _resolve_threads(n_threads),
    )
    if rc != 0:
        # Out-of-range raises (like numpy); negative indices fall back
        # to numpy's wraparound semantics — both off the hot path.
        _check_bounds(idx_arr, len(out))
        out[idx_arr] = src
    return out


def _take_multi_sparse(
    parts: Sequence[np.ndarray],
    idx: np.ndarray,
    out: Optional[np.ndarray],
) -> np.ndarray:
    """Numpy sparse multi-part gather: partition ``idx`` by source part
    (one searchsorted over the part offsets) and scatter each part's rows
    into place — never materializes the concatenated source. Used when the
    fused C++ kernel is unavailable yet the gather is sparse enough that a
    full concat would dominate the cost."""
    offsets = np.zeros(len(parts) + 1, dtype=np.int64)
    np.cumsum([len(p) for p in parts], out=offsets[1:])
    idx = idx.astype(np.int64, copy=False)
    shape = (len(idx), *parts[0].shape[1:])
    if not _out_ok(out, shape, parts[0].dtype):
        out = np.empty(shape, dtype=parts[0].dtype)
    part_id = np.searchsorted(offsets, idx, side="right") - 1
    local = idx - offsets[part_id]
    for p in range(len(parts)):
        sel = np.nonzero(part_id == p)[0]
        if len(sel):
            out[sel] = parts[p][local[sel]]
    return out


def take_multi(
    parts: Sequence[np.ndarray],
    idx: np.ndarray,
    out: Optional[np.ndarray] = None,
    n_threads: Optional[int] = None,
) -> np.ndarray:
    """``np.concatenate(parts)[idx]`` without materializing the concat.

    The reduce-stage hot path: `parts` are one column's partitions from all
    mappers, `idx` the epoch permutation over their concatenated rows.
    ``out`` lands the gather directly in a pre-allocated destination.

    Bounds are checked INSIDE the fused kernel (free per row, like
    take/scatter): the old Python ``idx.min()/idx.max()`` pre-scan cost
    two full single-threaded passes per call on this — the hottest —
    kernel (ROADMAP 2b residual). The numpy fallback paths still
    pre-validate (they need the answer to pick sparse vs concat anyway).
    """
    if not parts:
        raise ValueError("need at least one part to concatenate")
    template = parts[0]
    parts = [p for p in parts if len(p)]
    if not parts:
        return template[idx]  # empty concat: numpy raises/returns likewise
    lib = _get_lib()
    row_bytes = _rows_contig(parts[0])
    same = all(
        _rows_contig(p) == row_bytes
        and p.dtype == parts[0].dtype
        and p.shape[1:] == parts[0].shape[1:]
        for p in parts
    )
    total = sum(len(p) for p in parts)
    idx_arr = np.asarray(idx)
    is_int_idx = (
        len(idx_arr) != 0 and np.issubdtype(idx_arr.dtype, np.integer)
    )
    # Strategy: the fused kernel skips materializing the concat but pays a
    # per-row part lookup; a DENSE gather (idx covers ~all rows, the
    # reduce path) only wins fused when threads amortize that — on few
    # cores a sequential concat (pure memcpy) + one gather is fastest.
    # A SPARSE gather (idx << total rows, the steady-state index-schedule
    # path) must never materialize the concat: the copy would dwarf the
    # gather itself. Sparse paths assume parts[0]'s dtype/shape for every
    # part, so mixed-dtype parts must keep going through the concat
    # (numpy promotes there; the sparse scatter would silently truncate).
    compat = all(
        p.dtype == parts[0].dtype and p.shape[1:] == parts[0].shape[1:]
        for p in parts
    )
    maybe_sparse = compat and len(parts) > 1 and 2 * len(idx_arr) < total
    threads = _resolve_threads(n_threads)
    if (
        lib is not None
        and row_bytes is not None
        and same
        and len(parts) > 1
        and (threads >= 4 or maybe_sparse)
        and is_int_idx
    ):
        idx_c = np.ascontiguousarray(idx_arr, dtype=np.int64)
        offsets = np.zeros(len(parts) + 1, dtype=np.int64)
        np.cumsum([len(p) for p in parts], out=offsets[1:])
        ptrs = (ctypes.c_void_p * len(parts))(*[p.ctypes.data for p in parts])
        shape = (len(idx_c), *parts[0].shape[1:])
        if not _out_ok(out, shape, parts[0].dtype):
            out = np.empty(shape, dtype=parts[0].dtype)
        # rsdl_take_multi dispatches typed inner loops for widths 1/2/4/8
        # internally; rc != 0 means an index fell outside [0, total) and
        # the slow path below re-derives exact numpy semantics.
        rc = lib.rsdl_take_multi(
            ptrs, _ptr(offsets), len(parts), _ptr(out), _ptr(idx_c),
            len(idx_c), row_bytes, threads,
        )
        if rc == 0:
            return out
        try:
            _check_bounds(idx_arr, total)  # IndexError if truly OOB
        except IndexError:
            # Restore the fresh-segment invariant of direct-to-store
            # destinations before surfacing the error (error-path only).
            out[...] = 0
            raise
        # Negative indices: numpy wraparound semantics via the concat.
        np.take(np.concatenate(parts), idx_arr, axis=0, out=out)
        return out
    in_bounds = _check_bounds(idx_arr, total)  # raises when truly OOB
    if maybe_sparse and in_bounds:
        return _take_multi_sparse(parts, idx_arr, out)
    base = parts[0] if len(parts) == 1 else np.concatenate(parts)
    return take(base, idx, out=out, n_threads=n_threads)


def narrow_i64_checked(
    arr: np.ndarray, n_threads: Optional[int] = None
) -> Optional[np.ndarray]:
    """Range-checked ``int64 -> int32`` in ONE fused pass (the numpy route
    costs three: max scan, min scan, astype). Returns the int32 array, or
    None when any value falls outside int32 range — the caller decides how
    to fail. Falls back to the three-pass numpy check without the .so."""
    if arr.dtype != np.int64:
        # Not an assert: stripped under PYTHONOPTIMIZE, and a wrong dtype
        # reaching the C kernel reads past the buffer.
        raise TypeError(f"narrow_i64_checked expects int64, got {arr.dtype}")
    lib = _get_lib()
    if lib is not None and arr.flags.c_contiguous and arr.size:
        out = np.empty(arr.shape, dtype=np.int32)
        ok = lib.rsdl_cast_i64_i32_checked(
            _ptr(arr), _ptr(out), arr.size, _resolve_threads(n_threads)
        )
        return out if ok else None
    if arr.size and (
        arr.max() > np.iinfo(np.int32).max or arr.min() < np.iinfo(np.int32).min
    ):
        return None
    return arr.astype(np.int32)


def narrow(
    arr: np.ndarray, dtype, n_threads: Optional[int] = None
) -> np.ndarray:
    """``arr.astype(dtype)`` with fast paths for the staging casts
    (int64→int32, float64→float32)."""
    dtype = np.dtype(dtype)
    if arr.dtype == dtype:
        return arr
    lib = _get_lib()
    threads = _resolve_threads(n_threads)
    if lib is not None and arr.flags.c_contiguous and arr.size:
        out = np.empty(arr.shape, dtype=dtype)
        if arr.dtype == np.int64 and dtype == np.int32:
            lib.rsdl_cast_i64_i32(_ptr(arr), _ptr(out), arr.size, threads)
            return out
        if arr.dtype == np.float64 and dtype == np.float32:
            lib.rsdl_cast_f64_f32(_ptr(arr), _ptr(out), arr.size, threads)
            return out
    return arr.astype(dtype)


def group_rows(
    arr: np.ndarray,
    assignment: np.ndarray,
    num_groups: int,
    n_threads: Optional[int] = None,
):
    """Stable partition of rows by ``assignment`` (the map-stage op).

    Returns ``(grouped, offsets)`` where ``grouped`` has ``arr``'s rows
    reordered so group ``g`` occupies ``grouped[offsets[g]:offsets[g+1]]``,
    preserving input order within a group. Single-pass counting scatter vs
    the argsort+gather equivalent.
    """
    grouped, offsets = group_rows_multi(
        {"": arr}, assignment, num_groups, n_threads=n_threads
    )
    return grouped[""], offsets


def group_rows_multi(
    columns: dict,
    assignment: np.ndarray,
    num_groups: int,
    out: Optional[dict] = None,
    n_threads: Optional[int] = None,
):
    """:func:`group_rows` over several equal-length columns sharing one
    assignment. The numpy fallback argsorts the assignment ONCE and gathers
    each column, matching the native path's per-column O(n) cost.

    With ``n_threads > 1`` (the ``RSDL_NATIVE_THREADS`` default) and
    enough rows, the scatter runs the two-pass parallel kernel: one
    (thread, group) histogram + prefix-sum plan per batch, then an
    independent typed scatter per contiguous input range — bit-identical
    to the serial kernel because thread ranges are contiguous and the
    plan orders their output spans by thread id (stability preserved).

    ``out``: dict of pre-allocated destinations per column (e.g. writable
    store-segment views) — the partition scatter writes shared memory
    directly; the map stage's only full data pass."""
    lib = _get_lib()
    arrs = list(columns.values())
    assignment = np.asarray(assignment)
    if len(assignment) and (
        int(assignment.min()) < 0 or int(assignment.max()) >= num_groups
    ):
        raise ValueError(
            f"assignment values must be in [0, {num_groups}); got "
            f"[{assignment.min()}, {assignment.max()}]"
        )
    native_ok = (
        lib is not None
        and arrs
        and arrs[0].size > 0
        and all(_rows_contig(a) is not None for a in arrs)
    )
    # One histogram pass for the whole batch, shared by every column.
    counts = np.bincount(assignment, minlength=num_groups)
    offsets = np.zeros(num_groups + 1, dtype=np.int64)
    np.cumsum(counts, out=offsets[1:])
    def _dst(name, arr):
        if out is None:
            return None
        if name not in out:
            raise KeyError(f"out= missing destination for column {name!r}")
        return out[name]

    if not native_ok:
        order = np.argsort(assignment, kind="stable")
        result = {}
        for k, v in columns.items():
            dst = _dst(k, v)
            if _out_ok(dst, v.shape, v.dtype):
                np.take(v, order, axis=0, out=dst)
                result[k] = dst
            else:
                result[k] = v[order]
        return result, offsets
    assignment = np.ascontiguousarray(assignment, dtype=np.int32)
    n = len(assignment)
    # Cap threads so every contiguous slice is worth its spawn (shared
    # policy with the C parallel_for — see _MIN_ROWS_PER_THREAD).
    threads = min(
        _resolve_threads(n_threads), max(1, n // _MIN_ROWS_PER_THREAD)
    )
    dsts = {}
    for name, arr in columns.items():
        dst = _dst(name, arr)
        if not _out_ok(dst, arr.shape, arr.dtype):
            dst = np.empty_like(arr)
        dsts[name] = dst
    if threads > 1:
        # Two-pass parallel stable scatter: ONE (thread, group) cursor
        # plan for the batch, then one multi-column kernel call — threads
        # spawn once and sweep every column over their input range.
        plan = np.empty(threads * num_groups, dtype=np.int64)
        group_starts = np.ascontiguousarray(offsets[:num_groups])
        lib.rsdl_group_plan(
            _ptr(assignment), n, num_groups, threads,
            _ptr(group_starts), _ptr(plan),
        )
        arrs_list = list(columns.values())
        dst_list = [dsts[name] for name in columns]
        src_ptrs = (ctypes.c_void_p * len(arrs_list))(
            *[a.ctypes.data for a in arrs_list]
        )
        dst_ptrs = (ctypes.c_void_p * len(dst_list))(
            *[d.ctypes.data for d in dst_list]
        )
        itemsizes = np.array(
            [_rows_contig(a) for a in arrs_list], dtype=np.int64
        )
        lib.rsdl_group_rows_multi_mt(
            src_ptrs, dst_ptrs, _ptr(itemsizes), len(arrs_list),
            _ptr(assignment), n, _ptr(plan), threads, num_groups,
        )
    else:
        for name, arr in columns.items():
            cursors = offsets[:num_groups].copy()  # C kernel advances these
            lib.rsdl_group_rows(
                _ptr(arr), _ptr(dsts[name]), _ptr(assignment), len(arr),
                _rows_contig(arr), _ptr(cursors),
            )
    return dsts, offsets
