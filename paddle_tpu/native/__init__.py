"""Native host-staging library (C++) + ctypes bindings.

Reference analog: SURVEY.md §2.2 (pinned staging / allocator) and the
buffered_reader + DataLoader collation C++ (§2.4 reader ops, §2.6
pybind `core._convert_to_tensor_list`) — the parts of the reference's
native runtime that remain load-bearing on a TPU host, where XLA/PJRT
owns device memory and compute.

The library builds lazily with the system g++ into a source-hash-named
shared object under `native/_build/` (the build-at-first-use model of the
reference's JIT op-compilation, fluid custom-op SDK). Every consumer must handle
`available() == False` (no toolchain) and fall back to numpy.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

import numpy as np

__all__ = ["available", "stack_samples", "stack_u8_to_f32", "lib"]

_SRC = os.path.join(os.path.dirname(__file__), "staging.cpp")
#: fixed, git-ignored, inside the checkout
_BUILD_DIR = os.path.join(os.path.dirname(__file__), "_build")
_LOCK = threading.Lock()
_LIB = None
_TRIED = False
_DEFAULT_THREADS = min(8, os.cpu_count() or 1)


def _build() -> str:
    """Compile staging.cpp into `_BUILD_DIR`, named by a hash of the
    source: the artefact comes from the files of THIS checkout and
    nothing else (an mtime test against a shared temp directory could
    load another checkout's binary)."""
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    so = os.path.join(_BUILD_DIR, f"libptstaging_{digest}.so")
    if os.path.exists(so):
        return so
    os.makedirs(_BUILD_DIR, exist_ok=True)
    tmp = so + f".build{os.getpid()}"
    subprocess.run(
        ["g++", "-O3", "-march=native", "-shared", "-fPIC", "-std=c++17",
         "-pthread", _SRC, "-o", tmp],
        check=True, capture_output=True,
    )
    os.replace(tmp, so)  # atomic under concurrent builders
    return so


def lib():
    """The loaded library, or None when no toolchain is available."""
    global _LIB, _TRIED
    if _LIB is not None or _TRIED:
        return _LIB
    with _LOCK:
        if _LIB is not None or _TRIED:
            return _LIB
        _TRIED = True
        try:
            so = _build()
            L = ctypes.CDLL(so)
            L.pt_stack.argtypes = [
                ctypes.c_void_p, ctypes.POINTER(ctypes.c_void_p),
                ctypes.c_int64, ctypes.c_int64, ctypes.c_int,
            ]
            L.pt_stack_u8_to_f32.argtypes = [
                ctypes.c_void_p, ctypes.POINTER(ctypes.c_void_p),
                ctypes.c_int64, ctypes.c_int64, ctypes.c_float,
                ctypes.c_float, ctypes.c_int,
            ]
            L.pt_version.restype = ctypes.c_int
            assert L.pt_version() == 1
            _LIB = L
        except Exception:
            _LIB = None
    return _LIB


def available() -> bool:
    return lib() is not None


def _src_ptrs(samples):
    arr = (ctypes.c_void_p * len(samples))()
    for i, s in enumerate(samples):
        arr[i] = s.ctypes.data
    return arr


# below this, thread spawn/join overhead beats the memcpy win
_MIN_NATIVE_BYTES = 1 << 20


def stack_samples(samples) -> np.ndarray:
    """np.stack for a list of same-shape/dtype contiguous arrays, done by
    the native library (GIL released during the copies). Small batches
    (< ~1MB) go straight to np.stack — thread startup would dominate."""
    L = lib()
    first = samples[0]
    total = first.nbytes * len(samples)
    if L is None or total < _MIN_NATIVE_BYTES:
        return np.stack(samples)
    out = np.empty((len(samples),) + first.shape, first.dtype)
    threads = _DEFAULT_THREADS if total >= 8 * _MIN_NATIVE_BYTES else 2
    L.pt_stack(
        out.ctypes.data, _src_ptrs(samples), len(samples),
        first.nbytes, threads,
    )
    return out


def stack_u8_to_f32(samples, scale: float = 1.0 / 255.0,
                    shift: float = 0.0) -> np.ndarray:
    """Fused stack + uint8->float32 normalize (the vision-transform hot
    loop: ToTensor's /255)."""
    L = lib()
    first = samples[0]
    if L is None:
        return np.stack(samples).astype(np.float32) * scale + shift
    out = np.empty((len(samples),) + first.shape, np.float32)
    L.pt_stack_u8_to_f32(
        out.ctypes.data, _src_ptrs(samples), len(samples),
        first.size, scale, shift, _DEFAULT_THREADS,
    )
    return out
