"""Native host kernels (C++, ctypes-bound) with pure-Python fallbacks.

The device compute path is JAX/XLA; this module accelerates the host-side
hot loops that feed it: per-distinct-value hashing, type classification and
utf-8 lengths over dictionary batches. The extension compiles on first use
(g++, from the tracked ``kernels.cpp``, cached next to it); if the build or
the load fails, the failure is reported ONCE on stderr with the compiler's
output and every entry point runs the bit-identical Python implementation
(``available()`` says which path a process is on).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import sys
import threading
from typing import List, Optional, Sequence

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "kernels.cpp")
_SO = os.path.join(_HERE, "_kernels.so")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_load_attempted = False


def _build() -> bool:
    cmd = [
        "g++", "-O3", "-shared", "-fPIC", "-std=c++17", _SRC, "-o", _SO,
    ]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        return True
    except (OSError, subprocess.SubprocessError) as e:
        output = getattr(e, "stderr", None) or b""
        print(
            f"deequ_tpu.native: building {_SO} failed ({e}); host string "
            "kernels run the Python path.\n"
            + output.decode("utf-8", "replace"),
            file=sys.stderr,
        )
        return False


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _load_attempted
    with _lock:
        if _load_attempted:
            return _lib
        _load_attempted = True
        from deequ_tpu.envcfg import env_value

        if env_value("DEEQU_TPU_DISABLE_NATIVE"):
            return None
        needs_build = (
            not os.path.exists(_SO)
            or os.path.getmtime(_SO) < os.path.getmtime(_SRC)
        )
        if needs_build and not _build():
            return None
        try:
            lib = ctypes.CDLL(_SO)
        except OSError as e:
            print(
                f"deequ_tpu.native: loading {_SO} failed ({e}); host "
                "string kernels run the Python path.",
                file=sys.stderr,
            )
            return None
        u8p = ctypes.POINTER(ctypes.c_uint8)
        i64p = ctypes.POINTER(ctypes.c_int64)
        u64p = ctypes.POINTER(ctypes.c_uint64)
        i32p = ctypes.POINTER(ctypes.c_int32)
        lib.xxhash64_batch.argtypes = [u8p, i64p, ctypes.c_int64,
                                       ctypes.c_uint64, u64p]
        lib.xxhash64_batch.restype = None
        lib.classify_batch.argtypes = [u8p, i64p, ctypes.c_int64, i32p]
        lib.classify_batch.restype = None
        lib.utf8_lengths.argtypes = [u8p, i64p, ctypes.c_int64, i64p]
        lib.utf8_lengths.restype = None
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


def _pack(values: Sequence[str]):
    """Pack strings into (contiguous utf-8 buffer, int64 offsets[n+1])."""
    encoded: List[bytes] = [str(v).encode("utf-8") for v in values]
    offsets = np.zeros(len(encoded) + 1, dtype=np.int64)
    np.cumsum([len(b) for b in encoded], out=offsets[1:])
    buffer = np.frombuffer(b"".join(encoded), dtype=np.uint8)
    if len(buffer) == 0:
        buffer = np.zeros(1, dtype=np.uint8)
    return buffer, offsets


def _ptr(arr: np.ndarray, ctype):
    return arr.ctypes.data_as(ctypes.POINTER(ctype))


def hash_strings(values: Sequence[str], seed: int) -> Optional[np.ndarray]:
    """Batch xxhash64; None when the native library is unavailable."""
    lib = _load()
    if lib is None:
        return None
    buffer, offsets = _pack(values)
    out = np.empty(len(values), dtype=np.uint64)
    lib.xxhash64_batch(
        _ptr(buffer, ctypes.c_uint8), _ptr(offsets, ctypes.c_int64),
        len(values), ctypes.c_uint64(seed), _ptr(out, ctypes.c_uint64),
    )
    return out


def classify_strings(values: Sequence[str]) -> Optional[np.ndarray]:
    """Batch DataType classification (1=fractional..4=string)."""
    lib = _load()
    if lib is None:
        return None
    buffer, offsets = _pack(values)
    out = np.empty(len(values), dtype=np.int32)
    lib.classify_batch(
        _ptr(buffer, ctypes.c_uint8), _ptr(offsets, ctypes.c_int64),
        len(values), _ptr(out, ctypes.c_int32),
    )
    return out


def utf8_lengths(values: Sequence[str]) -> Optional[np.ndarray]:
    """Batch string lengths in code points."""
    lib = _load()
    if lib is None:
        return None
    buffer, offsets = _pack(values)
    out = np.empty(len(values), dtype=np.int64)
    lib.utf8_lengths(
        _ptr(buffer, ctypes.c_uint8), _ptr(offsets, ctypes.c_int64),
        len(values), _ptr(out, ctypes.c_int64),
    )
    return out
