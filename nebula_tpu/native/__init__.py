"""ctypes loader for the C++ native kernels (native/nebula_native.cc).

Builds the shared library on first use if it's missing (g++ is in the
image; ~1s compile, cached next to the source).  Every entry point has a
NumPy/Python fallback so the framework runs without a toolchain — the
native path is the fast path, never the only path.
"""
from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading
from typing import Optional

_log = logging.getLogger(__name__)

_dir = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "native")
_so = os.path.join(_dir, "libnebula_native.so")
_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False


def build() -> bool:
    """Compile native/nebula_native.cc into the shared library (the
    library is git-ignored: a fresh checkout has only the source).
    A failed build is logged, never silent — callers that can live
    without the native path fall back to Python, chip_smoke.py does
    not."""
    src = os.path.join(_dir, "nebula_native.cc")
    if not os.path.exists(src):
        _log.warning("native build: %s is missing", src)
        return False
    tmp = f"{_so}.{os.getpid()}.tmp"
    try:
        subprocess.run(
            ["g++", "-O3", "-std=c++17", "-fPIC", "-shared", "-o", tmp, src],
            check=True, capture_output=True, timeout=120)
        # atomic: another process may be loading the library right now
        os.replace(tmp, _so)
        return True
    except subprocess.CalledProcessError as ex:
        _log.warning("native build failed: %s",
                     ex.stderr.decode(errors="replace")[-2000:])
    except (subprocess.SubprocessError, FileNotFoundError, OSError) as ex:
        _log.warning("native build failed: %r", ex)
    return False


def get_lib() -> Optional[ctypes.CDLL]:
    """The loaded library, or None (callers use their fallback)."""
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if not os.path.exists(_so) or (
                os.path.exists(os.path.join(_dir, "nebula_native.cc"))
                and os.path.getmtime(_so) <
                os.path.getmtime(os.path.join(_dir, "nebula_native.cc"))):
            if not build() and not os.path.exists(_so):
                return None
        try:
            lib = ctypes.CDLL(_so)
            lib.csv_ingest.restype = ctypes.c_longlong
            lib.csv_ingest.argtypes = [
                ctypes.c_char_p, ctypes.c_char, ctypes.c_int, ctypes.c_int,
                ctypes.POINTER(ctypes.c_int), ctypes.c_longlong,
                ctypes.POINTER(ctypes.c_void_p),
                ctypes.POINTER(ctypes.c_void_p)]
            lib.build_csr.restype = ctypes.c_longlong
            lib.build_csr.argtypes = [
                ctypes.c_longlong, ctypes.c_int, ctypes.c_longlong,
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_longlong]
            lib.row_encode.restype = ctypes.c_longlong
            lib.row_encode.argtypes = [
                ctypes.c_int, ctypes.c_int, ctypes.POINTER(ctypes.c_int),
                ctypes.POINTER(ctypes.c_int64),
                ctypes.POINTER(ctypes.c_double),
                ctypes.POINTER(ctypes.c_char_p),
                ctypes.POINTER(ctypes.c_int),
                ctypes.POINTER(ctypes.c_ubyte), ctypes.c_longlong]
            lib.row_decode.restype = ctypes.c_longlong
            lib.row_decode.argtypes = [
                ctypes.POINTER(ctypes.c_ubyte), ctypes.c_longlong,
                ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int),
                ctypes.POINTER(ctypes.c_int64),
                ctypes.POINTER(ctypes.c_double),
                ctypes.POINTER(ctypes.c_longlong),
                ctypes.POINTER(ctypes.c_int), ctypes.c_int]
            lib.join_halves.restype = ctypes.c_int
            lib.join_halves.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_longlong, ctypes.c_int]
        except (OSError, AttributeError):
            # unloadable OR stale .so missing a symbol — fall back to
            # the Python paths rather than crashing callers
            return None
        _lib = lib
        return _lib


def available() -> bool:
    return get_lib() is not None
