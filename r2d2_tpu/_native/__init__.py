"""ctypes loader for the native replay core (replay_core.cpp).

Builds the shared library with g++ on first import (pybind11 is not in
this image; plain C ABI + ctypes needs no build-time Python dependency at
all). The library is keyed on the SOURCE'S CONTENT — its file name
carries a hash of replay_core.cpp — so what loads was built from exactly
the source beside it: a stale or foreign .so that a tree copy brought
along (git-ignored files travel with a directory copy; their mtimes may
not) has another name and is never trusted. Thread/process safe via an
atomic rename. `load_native()` returns a NativeReplayCore or None — every
caller must tolerate None and fall back to the numpy path, so a missing
toolchain degrades performance, never correctness; the fallback is said
once on stderr with the compiler's message, and the trainer's start-up
banner reports which core loaded.
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import subprocess
import sys
import tempfile
import threading
from typing import Optional

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "replay_core.cpp")

_lock = threading.Lock()
_core: Optional["NativeReplayCore"] = None
_load_failed = False

_i64p = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
_f64p = np.ctypeslib.ndpointer(np.float64, flags="C_CONTIGUOUS")
_f32p = np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS")
_u8p = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")


def lib_path(src: str = _SRC) -> str:
    """Where the library built from `src`'s current content lives."""
    with open(src, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(os.path.dirname(src), f"libreplay_core.{digest}.so")


def _build() -> Optional[str]:
    """Compile the library for the current source unless that exact build
    exists. Returns its path, or None (reason on stderr) when it cannot
    be built."""
    try:
        lib = lib_path()
        if os.path.exists(lib):
            return lib
        fd, tmp = tempfile.mkstemp(suffix=".so.tmp", dir=_DIR)
        os.close(fd)
        cmd = [
            "g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-fopenmp",
            _SRC, "-o", tmp,
        ]
        r = subprocess.run(cmd, capture_output=True, text=True, timeout=120)
        if r.returncode != 0:
            # retry without OpenMP (toolchains without libgomp)
            cmd = [c for c in cmd if c != "-fopenmp"]
            r = subprocess.run(cmd, capture_output=True, text=True, timeout=120)
            if r.returncode != 0:
                os.unlink(tmp)
                print(
                    "[native] replay core build failed, using numpy: "
                    + (r.stderr.strip().splitlines() or ["g++ failed"])[-1],
                    file=sys.stderr,
                )
                return None
        os.replace(tmp, lib)  # atomic: concurrent builders race benignly
        for old in sorted(glob.glob(os.path.join(_DIR, "libreplay_core*.so"))):
            if old != lib:  # builds of other source contents
                try:
                    os.unlink(old)
                except OSError:
                    pass
        return lib
    except (OSError, subprocess.SubprocessError) as e:
        print(f"[native] replay core build failed, using numpy: {e!r}",
              file=sys.stderr)
        return None


class NativeReplayCore:
    """The interface replay/sum_tree.py's `native` hook expects, plus the
    window gatherer used by replay/replay_buffer.py batch assembly."""

    def __init__(self, lib: ctypes.CDLL):
        self._lib = lib
        lib.tree_update.argtypes = [_f64p, ctypes.c_int64, _i64p, _f64p,
                                    ctypes.c_int64, ctypes.c_double]
        lib.tree_update.restype = None
        lib.tree_sample.argtypes = [_f64p, ctypes.c_int64, _f64p,
                                    ctypes.c_int64, _i64p]
        lib.tree_sample.restype = None
        lib.gather_windows.argtypes = [_u8p, ctypes.c_int64, ctypes.c_int64,
                                       _i64p, _i64p, ctypes.c_int64,
                                       ctypes.c_int64, _u8p]
        lib.gather_windows.restype = None
        lib.gather_windows_multi.argtypes = [
            ctypes.POINTER(ctypes.c_void_p), _i64p, ctypes.c_int64,
            ctypes.c_int64, _i64p, _i64p, ctypes.c_int64, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_void_p),
        ]
        lib.gather_windows_multi.restype = None
        lib.is_weights.argtypes = [_f64p, ctypes.c_int64, _i64p,
                                   ctypes.c_int64, ctypes.c_double, _f32p]
        lib.is_weights.restype = ctypes.c_int64

    # --- sum tree ---------------------------------------------------------

    def tree_update(self, tree: np.ndarray, num_layers: int,
                    idxes: np.ndarray, td_errors: np.ndarray,
                    alpha: float) -> None:
        idxes = np.ascontiguousarray(idxes, np.int64)
        td = np.ascontiguousarray(td_errors, np.float64)
        self._lib.tree_update(tree, num_layers, idxes, td, len(idxes), alpha)

    def tree_sample(self, tree: np.ndarray, num_layers: int,
                    prefixsums: np.ndarray) -> np.ndarray:
        prefixsums = np.ascontiguousarray(prefixsums, np.float64)
        out = np.empty(len(prefixsums), np.int64)
        self._lib.tree_sample(tree, num_layers, prefixsums, len(prefixsums), out)
        return out

    def is_weights(self, tree: np.ndarray, num_layers: int,
                   nodes: np.ndarray, beta: float) -> np.ndarray:
        nodes = np.ascontiguousarray(nodes, np.int64)
        out = np.empty(len(nodes), np.float32)
        self._lib.is_weights(tree, num_layers, nodes, len(nodes), beta, out)
        return out

    # --- batch assembly ---------------------------------------------------

    def gather_windows(self, store: np.ndarray, b: np.ndarray,
                       win_start: np.ndarray, T: int) -> np.ndarray:
        """store: (num_blocks, slot, *row_shape) C-contiguous; returns
        (B, T, *row_shape) with row indices clamped to [0, slot-1]."""
        assert store.flags["C_CONTIGUOUS"]
        slot = store.shape[1]
        row_shape = store.shape[2:]
        row_bytes = int(np.prod(row_shape, dtype=np.int64)) * store.itemsize
        b = np.ascontiguousarray(b, np.int64)
        win_start = np.ascontiguousarray(win_start, np.int64)
        B = len(b)
        out = np.empty((B, T, *row_shape), store.dtype)
        self._lib.gather_windows(
            store.view(np.uint8).reshape(-1),
            slot, row_bytes, b, win_start, B, T,
            out.view(np.uint8).reshape(-1),
        )
        return out

    def gather_windows_multi(self, stores, b: np.ndarray,
                             win_start: np.ndarray, T: int) -> list:
        """Gather the SAME (b, win_start) windows from several stores that
        share the slot axis, in ONE native call (one ctypes crossing + one
        OMP region for the whole field group). Returns one (B, T,
        *row_shape) array per store; clamp semantics identical to
        gather_windows (bit-identical outputs, pinned by test)."""
        b = np.ascontiguousarray(b, np.int64)
        win_start = np.ascontiguousarray(win_start, np.int64)
        B = len(b)
        slot = stores[0].shape[1]
        outs, row_bytes = [], np.empty(len(stores), np.int64)
        store_ptrs = (ctypes.c_void_p * len(stores))()
        out_ptrs = (ctypes.c_void_p * len(stores))()
        for f, store in enumerate(stores):
            assert store.flags["C_CONTIGUOUS"] and store.shape[1] == slot
            row_shape = store.shape[2:]
            row_bytes[f] = int(np.prod(row_shape, dtype=np.int64)) * store.itemsize
            out = np.empty((B, T, *row_shape), store.dtype)
            outs.append(out)
            store_ptrs[f] = store.ctypes.data
            out_ptrs[f] = out.ctypes.data
        self._lib.gather_windows_multi(
            store_ptrs, row_bytes, len(stores), slot, b, win_start, B, T,
            out_ptrs,
        )
        return outs


def load_native() -> Optional[NativeReplayCore]:
    """Build (if needed) and load the core; None if the toolchain or load
    fails. Result is cached process-wide."""
    global _core, _load_failed
    if _core is not None:
        return _core
    if _load_failed:
        return None
    with _lock:
        if _core is not None or _load_failed:
            return _core
        lib_file = _build()
        if lib_file is None:
            _load_failed = True
            return None
        try:
            _core = NativeReplayCore(ctypes.CDLL(lib_file))
        except OSError as e:
            print(f"[native] replay core load failed, using numpy: {e!r}",
                  file=sys.stderr)
            _load_failed = True
            return None
        return _core
