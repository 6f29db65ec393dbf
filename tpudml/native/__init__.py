"""Native (C++) host data-plane, bound via ctypes.

Lazy-builds ``dataplane.cpp`` with g++ into a cached shared library on
first use and exposes thin numpy wrappers. The library's file name carries
a hash of the source it was built from, so only a library built from THIS
``dataplane.cpp`` is ever loaded — a stale git-ignored build that rode
along with a copied tree is simply not found. Every entry point has a pure
numpy fallback, so the framework runs unchanged where no toolchain exists
(``TPUDML_NO_NATIVE=1`` forces the fallback; ``available()`` reports which
path is active); a build that was attempted and failed says so once on
stderr instead of falling back in silence.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import sys
import threading
from pathlib import Path

import numpy as np

_HERE = Path(__file__).resolve().parent
_SRC = _HERE / "dataplane.cpp"
_BUILD_DIR = _HERE / "_build"

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_tried = False

_i64p = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
_f32p = np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS")
_u8p = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")
_i32p = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")


def _build() -> ctypes.CDLL | None:
    if os.environ.get("TPUDML_NO_NATIVE"):
        return None
    digest = hashlib.sha256(_SRC.read_bytes()).hexdigest()[:16]
    lib_path = _BUILD_DIR / f"libtpudml_dataplane-{digest}.so"
    try:
        if not lib_path.exists():
            _BUILD_DIR.mkdir(exist_ok=True)
            tmp = lib_path.with_suffix(f".tmp{os.getpid()}.so")
            subprocess.run(
                ["g++", "-O3", "-shared", "-fPIC", "-o", str(tmp), str(_SRC)],
                check=True,
                capture_output=True,
            )
            os.replace(tmp, lib_path)  # atomic: concurrent builders race safely
        lib = ctypes.CDLL(str(lib_path))
        lib.tpudml_gather_rows_f32.argtypes = [
            _f32p, _i64p, ctypes.c_int64, ctypes.c_int64, _f32p,
        ]
        lib.tpudml_gather_rows_u8.argtypes = [
            _u8p, _i64p, ctypes.c_int64, ctypes.c_int64, _u8p,
        ]
        lib.tpudml_gather_normalize_u8.argtypes = [
            _u8p, _i64p, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_float, ctypes.c_float, _f32p,
        ]
        lib.tpudml_gather_i32.argtypes = [_i32p, _i64p, ctypes.c_int64, _i32p]
        lib.tpudml_byteswap.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int32,
        ]
        lib.tpudml_byteswap.restype = ctypes.c_int
        return lib
    except (OSError, subprocess.CalledProcessError) as e:
        detail = getattr(e, "stderr", b"") or b""
        print(
            f"[tpudml.native] C++ data plane unavailable, using the numpy "
            f"path: {e!r} {detail.decode(errors='replace').strip()}",
            file=sys.stderr,
        )
        return None


def _get() -> ctypes.CDLL | None:
    global _lib, _tried
    if not _tried:
        with _lock:
            if not _tried:
                _lib = _build()
                _tried = True
    return _lib


def available() -> bool:
    """True when the C++ data-plane is built and loaded."""
    return _get() is not None


def _prep_idx(idx: np.ndarray, n: int) -> np.ndarray:
    """Validate + canonicalize gather indices. The C++ kernels do raw
    pointer arithmetic, so out-of-range indices must be caught HERE (the
    numpy fallback would raise; the native path would read out of bounds).
    Negative indices follow numpy semantics (count from the end)."""
    idx = np.ascontiguousarray(idx, np.int64)
    if idx.size:
        lo, hi = int(idx.min()), int(idx.max())
        if lo < -n or hi >= n:
            raise IndexError(
                f"gather index out of range: [{lo}, {hi}] vs {n} rows"
            )
        if lo < 0:
            idx = np.ascontiguousarray(np.where(idx < 0, idx + n, idx))
    return idx


def gather_rows(src: np.ndarray, idx: np.ndarray) -> np.ndarray:
    """out[i] = src[idx[i]] for row-major [N, ...] float32/uint8 arrays."""
    idx = _prep_idx(idx, len(src))
    lib = _get()
    if lib is None or not src.flags.c_contiguous or src.dtype not in (
        np.float32,
        np.uint8,
    ):
        return src[idx]
    row = int(np.prod(src.shape[1:], dtype=np.int64))
    out = np.empty((len(idx), *src.shape[1:]), src.dtype)
    flat_src = src.reshape(len(src), row) if src.ndim != 2 else src
    flat_out = out.reshape(len(idx), row)
    if src.dtype == np.float32:
        lib.tpudml_gather_rows_f32(flat_src, idx, len(idx), row, flat_out)
    else:
        lib.tpudml_gather_rows_u8(flat_src, idx, len(idx), row, flat_out)
    return out


def gather_normalize(
    src: np.ndarray, idx: np.ndarray, scale: float, bias: float = 0.0
) -> np.ndarray:
    """out[i] = src[idx[i]] * scale + bias for uint8 [N, ...] → float32."""
    idx = _prep_idx(idx, len(src))
    lib = _get()
    if lib is None or not src.flags.c_contiguous or src.dtype != np.uint8:
        return src[idx].astype(np.float32) * scale + bias
    row = int(np.prod(src.shape[1:], dtype=np.int64))
    out = np.empty((len(idx), *src.shape[1:]), np.float32)
    lib.tpudml_gather_normalize_u8(
        src.reshape(len(src), row), idx, len(idx), row, scale, bias,
        out.reshape(len(idx), row),
    )
    return out


def gather_labels(src: np.ndarray, idx: np.ndarray) -> np.ndarray:
    idx = _prep_idx(idx, len(src))
    lib = _get()
    if lib is None or not src.flags.c_contiguous or src.dtype != np.int32:
        return src[idx]
    out = np.empty(len(idx), np.int32)
    lib.tpudml_gather_i32(src, idx, len(idx), out)
    return out


def byteswap_inplace(arr: np.ndarray) -> np.ndarray:
    """In-place endian swap (IDX big-endian payloads); returns ``arr``."""
    width = arr.dtype.itemsize
    lib = _get()
    if width == 1:
        return arr
    if not arr.flags.writeable:
        # The C++ path writes through the raw pointer; mirror numpy's
        # in-place semantics instead of corrupting a read-only buffer.
        raise ValueError("byteswap_inplace requires a writeable array")
    if lib is None or not arr.flags.c_contiguous:
        arr[...] = arr.byteswap()
        return arr
    rc = lib.tpudml_byteswap(
        arr.ctypes.data_as(ctypes.c_void_p), arr.size, width
    )
    if rc != 0:  # unsupported width — numpy handles it
        arr[...] = arr.byteswap()
    return arr
