"""Build + load the creward shared library (ctypes; no pybind11 needed).

Compiles ``creward.cpp`` with g++ on first use into the package directory and
memoizes the handle. Every failure path (no compiler, compile error, load
error) returns None so callers fall back to the pure-Python scorer, and
leaves the reason in :func:`load_error` so the fallback can be named rather
than taken silently (the trainer logs it; ``chip_smoke.py`` fails on it).

The binary name embeds a hash of the source (``libcreward-<sha>.so``), so a
stale prebuilt library can never shadow newer source — git clones don't
preserve mtimes, making mtime staleness checks unreliable. Binaries are never
committed (.gitignore'd); the library is always built from source on the
machine that uses it.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "creward.cpp")

_lock = threading.Lock()
_cached: "ctypes.CDLL | None | bool" = False  # False = not attempted yet
_error = ""  # why the last load_creward() returned None


def _lib_path() -> str:
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_DIR, f"libcreward-{digest}.so")


def _compile(lib_path: str) -> str:
    """Build the library; "" on success, else the reason it failed."""
    tmp = f"{lib_path}.{os.getpid()}.tmp"  # per-process: builders can't collide
    cmd = [
        "g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-pthread",
        _SRC, "-o", tmp,
    ]
    try:
        proc = subprocess.run(cmd, capture_output=True, timeout=120)
        if proc.returncode != 0:
            tail = proc.stderr.decode(errors="replace").strip()[-400:]
            return f"g++ exited {proc.returncode}: {tail}"
        # sweep dead binaries from previous source revisions
        for old in os.listdir(_DIR):
            if old.startswith("libcreward-") and old.endswith(".so"):
                if os.path.join(_DIR, old) != lib_path:
                    try:
                        os.unlink(os.path.join(_DIR, old))
                    except OSError:
                        pass
        os.replace(tmp, lib_path)  # atomic publish
        return ""
    except (OSError, subprocess.TimeoutExpired) as e:
        return f"g++ build failed: {e!r}"


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    i32p = ctypes.POINTER(ctypes.c_int32)
    f64p = ctypes.POINTER(ctypes.c_double)
    f32p = ctypes.POINTER(ctypes.c_float)
    lib.crw_create.restype = ctypes.c_void_p
    lib.crw_create.argtypes = [ctypes.c_double, ctypes.c_double,
                               ctypes.c_int32, ctypes.c_int32, ctypes.c_int32]
    lib.crw_free.argtypes = [ctypes.c_void_p]
    lib.crw_set_df.argtypes = [ctypes.c_void_p, i32p, i32p, f64p, ctypes.c_int64]
    lib.crw_add_video.restype = ctypes.c_int32
    lib.crw_add_video.argtypes = [ctypes.c_void_p, i32p, i32p, ctypes.c_int32]
    lib.crw_score.argtypes = [
        ctypes.c_void_p, i32p, i32p, ctypes.c_int64, ctypes.c_int32,
        ctypes.c_double, ctypes.c_double, ctypes.c_int32, f32p,
    ]
    return lib


def load_creward() -> "ctypes.CDLL | None":
    """Load (building if needed) the reward kernel; None -> use Python path."""
    global _cached, _error
    with _lock:
        if _cached is not False:
            return _cached
        lib = None
        try:
            path = _lib_path()
            if not os.path.exists(path):
                _error = _compile(path)
            if not _error:
                lib = _bind(ctypes.CDLL(path))
        except OSError as e:
            _error = f"loading the built library failed: {e!r}"
        _cached = lib
        return lib


def load_error() -> str:
    """Why :func:`load_creward` returned None ("" when it loaded, or was
    never asked)."""
    return _error
