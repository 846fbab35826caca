"""ctypes bindings for the native (C++) runtime components.

The shared library is built on demand from the checked-in source with the
toolchain g++ (no pip/pybind dependency — plain `extern "C"` + ctypes, so
the binding layer has zero install requirements). The build is cached
next to the source and rebuilt only when the source is newer. Hosts
without a compiler report `available() == False` and every caller falls
back to the pure-numpy path — the native library is a fast path, never a
hard dependency — but the build error is logged ONCE at warning level,
so a host that silently lost the fast path says so.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading
from typing import Optional

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "roundloader.cc")
_SO = os.path.join(_DIR, "libkubeml_native.so")
_ABI_VERSION = 1

logger = logging.getLogger("kubeml_tpu.native")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_failed = False


def _build() -> None:
    tmp = _SO + f".tmp.{os.getpid()}"
    cmd = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread",
           _SRC, "-o", tmp]
    subprocess.run(cmd, check=True, capture_output=True)
    os.replace(tmp, _SO)  # atomic: concurrent builders race harmlessly


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _failed
    with _lock:
        if _lib is not None or _failed:
            return _lib
        try:
            if (not os.path.exists(_SO)
                    or os.path.getmtime(_SO) < os.path.getmtime(_SRC)):
                _build()
            lib = ctypes.CDLL(_SO)
            if lib.kml_native_abi_version() != _ABI_VERSION:
                _build()
                lib = ctypes.CDLL(_SO)
            i64 = ctypes.c_int64
            p_u8 = ctypes.POINTER(ctypes.c_uint8)
            p_i64 = ctypes.POINTER(i64)
            p_f32 = ctypes.POINTER(ctypes.c_float)
            lib.kml_assemble_round.argtypes = [
                p_u8, p_u8, i64, i64,
                p_i64, p_i64, p_i64, p_i64,
                i64, i64, i64,
                p_u8, p_u8, p_f32, p_f32, p_f32, i64]
            lib.kml_assemble_round.restype = None
            _lib = lib
        except Exception as e:
            _failed = True  # one attempt, one warning per process
            detail = (getattr(e, "stderr", None) or b"").decode(
                errors="replace").strip()
            logger.warning(
                "native round loader unavailable (%s: %s)%s — round "
                "assembly falls back to the numpy path",
                type(e).__name__, e,
                f"; compiler said: {detail[-500:]}" if detail else "")
        return _lib


def available() -> bool:
    return _load() is not None


def _as_u8_ptr(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))


def assemble_round(x_src: np.ndarray, y_src: np.ndarray,
                   chunk_worker: np.ndarray, chunk_lo: np.ndarray,
                   chunk_hi: np.ndarray, chunk_steps: np.ndarray,
                   W: int, S: int, B: int,
                   n_threads: Optional[int] = None):
    """Assemble one round's dense tensors natively.

    x_src/y_src: C-contiguous (possibly mmapped) per-sample arrays of the
    whole split. chunk_*: int64 arrays describing the ACTIVE chunks
    (sample ranges, one per worker). Returns (x, y, sample_mask,
    step_mask, worker_mask) with x/y [W, S, B, *trailing].
    """
    lib = _load()
    assert lib is not None, "native library unavailable"
    if n_threads is None:
        n_threads = min(8, os.cpu_count() or 1)

    x_item = int(np.prod(x_src.shape[1:], dtype=np.int64) * x_src.itemsize)
    y_item = int(np.prod(y_src.shape[1:], dtype=np.int64) * y_src.itemsize)
    x_out = np.zeros((W, S, B) + x_src.shape[1:], x_src.dtype)
    y_out = np.zeros((W, S, B) + y_src.shape[1:], y_src.dtype)
    sample_mask = np.zeros((W, S, B), np.float32)
    step_mask = np.zeros((W, S), np.float32)
    worker_mask = np.zeros(W, np.float32)

    def i64arr(a):
        return np.ascontiguousarray(a, dtype=np.int64)

    cw, clo, chi, cst = map(i64arr, (chunk_worker, chunk_lo, chunk_hi,
                                     chunk_steps))
    p_i64 = ctypes.POINTER(ctypes.c_int64)
    lib.kml_assemble_round(
        _as_u8_ptr(x_src), _as_u8_ptr(y_src),
        ctypes.c_int64(x_item), ctypes.c_int64(y_item),
        cw.ctypes.data_as(p_i64), clo.ctypes.data_as(p_i64),
        chi.ctypes.data_as(p_i64), cst.ctypes.data_as(p_i64),
        ctypes.c_int64(len(cw)), ctypes.c_int64(S), ctypes.c_int64(B),
        _as_u8_ptr(x_out), _as_u8_ptr(y_out),
        sample_mask.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        step_mask.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        worker_mask.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        ctypes.c_int64(n_threads))
    return x_out, y_out, sample_mask, step_mask, worker_mask
