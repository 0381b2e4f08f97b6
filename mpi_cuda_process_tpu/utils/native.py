"""ctypes bridge to the native host runtime (native/stencilhost.cpp).

Builds the library with g++ on first use, from the committed source only:
the binary is named by a hash of ``native/stencilhost.cpp``
(``native/build/`` is git-ignored), so a checkout never loads a library
built from other source.  Degrades gracefully: every entry point has a
pure NumPy fallback, so the framework works on machines without a
toolchain.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_SRC = os.path.join(_REPO, "native", "stencilhost.cpp")
_BUILD_DIR = os.path.join(_REPO, "native", "build")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False

_DESCR = {
    np.dtype(np.float32): "<f4",
    np.dtype(np.float64): "<f8",
    np.dtype(np.int32): "<i4",
    np.dtype(np.int64): "<i8",
    np.dtype(np.uint8): "|u1",
}


def _so_path() -> str:
    """The library built from the current source: named by its hash."""
    with open(_SRC, "rb") as fh:
        digest = hashlib.sha256(fh.read()).hexdigest()[:16]
    return os.path.join(_BUILD_DIR, f"libstencilhost-{digest}.so")


def _build(so: str) -> bool:
    os.makedirs(_BUILD_DIR, exist_ok=True)
    tmp = f"{so}.{os.getpid()}.tmp"  # concurrent builders never collide
    cmd = ["g++", "-O2", "-shared", "-fPIC", "-std=c++17", "-pthread",
           _SRC, "-o", tmp]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, so)
        return True
    except (OSError, subprocess.SubprocessError):
        return False


def load() -> Optional[ctypes.CDLL]:
    """Load (building if needed) the native library, or None if unavailable."""
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        try:
            so = _so_path()
        except OSError:
            return None
        if not os.path.exists(so) and not _build(so):
            return None
        try:
            lib = ctypes.CDLL(so)
        except OSError:
            return None
        lib.stencilhost_async_write_npy.restype = ctypes.c_int
        lib.stencilhost_write_npy.restype = ctypes.c_int
        lib.stencilhost_wait_all.restype = ctypes.c_int64
        lib.stencilhost_pending.restype = ctypes.c_int64
        _lib = lib
        return _lib


def available() -> bool:
    return load() is not None


def _shape_arr(a: np.ndarray):
    return (ctypes.c_int64 * a.ndim)(*a.shape)


def async_write_npy(path: str, arr: np.ndarray) -> None:
    """Queue a non-blocking .npy write (atomic tmp+rename); copies the data."""
    a = np.ascontiguousarray(arr)
    lib = load()
    if lib is None or a.dtype not in _DESCR:
        np.save(path if not path.endswith(".npy") else path[:-4], a)
        return
    rc = lib.stencilhost_async_write_npy(
        path.encode(), _DESCR[a.dtype].encode(),
        a.ctypes.data_as(ctypes.c_void_p), _shape_arr(a), a.ndim,
        a.dtype.itemsize)
    if rc != 0:
        raise IOError(f"async npy write submit failed for {path}")


def wait_all() -> None:
    """Block until queued writes finish; raise if any failed."""
    lib = load()
    if lib is None:
        return
    errs = lib.stencilhost_wait_all()
    if errs:
        raise IOError(f"{errs} async npy write(s) failed")


def life_step_native(grid: np.ndarray) -> np.ndarray:
    """Independent C++ Game-of-Life step (differential-test engine)."""
    lib = load()
    if lib is None:
        raise RuntimeError("native library unavailable")
    a = np.ascontiguousarray(grid, dtype=np.int32)
    out = np.empty_like(a)
    lib.stencilhost_life_step(
        a.ctypes.data_as(ctypes.c_void_p), out.ctypes.data_as(ctypes.c_void_p),
        ctypes.c_int64(a.shape[0]), ctypes.c_int64(a.shape[1]))
    return out


def heat3d_step_native(grid: np.ndarray, alpha: float) -> np.ndarray:
    """Independent C++ 7-point FTCS step (differential-test engine)."""
    lib = load()
    if lib is None:
        raise RuntimeError("native library unavailable")
    a = np.ascontiguousarray(grid, dtype=np.float32)
    out = np.empty_like(a)
    lib.stencilhost_heat3d_step(
        a.ctypes.data_as(ctypes.c_void_p), out.ctypes.data_as(ctypes.c_void_p),
        ctypes.c_int64(a.shape[0]), ctypes.c_int64(a.shape[1]),
        ctypes.c_int64(a.shape[2]), ctypes.c_float(alpha))
    return out


def _step_2d_native(fn_name: str, grid: np.ndarray, *scalars) -> np.ndarray:
    lib = load()
    if lib is None:
        raise RuntimeError("native library unavailable")
    a = np.ascontiguousarray(grid, dtype=np.float32)
    out = np.empty_like(a)
    getattr(lib, fn_name)(
        a.ctypes.data_as(ctypes.c_void_p), out.ctypes.data_as(ctypes.c_void_p),
        ctypes.c_int64(a.shape[0]), ctypes.c_int64(a.shape[1]),
        *(ctypes.c_float(s) for s in scalars))
    return out


def heat2d_step_native(grid: np.ndarray, alpha: float) -> np.ndarray:
    """Independent C++ 5-point FTCS step (the reference MDF workload)."""
    return _step_2d_native("stencilhost_heat2d_step", grid, alpha)


def advect2d_step_native(grid: np.ndarray, cy: float, cx: float) -> np.ndarray:
    """Independent C++ first-order upwind advection step."""
    return _step_2d_native("stencilhost_advect2d_step", grid, cy, cx)


def sor2d_step_native(grid: np.ndarray, omega: float) -> np.ndarray:
    """Independent C++ red-black SOR step (Gauss-Seidel semantics)."""
    return _step_2d_native("stencilhost_sor2d_step", grid, omega)


def wave2d_step_native(u: np.ndarray, uprev: np.ndarray,
                       c2dt2: float) -> np.ndarray:
    """Independent C++ leapfrog wave step; returns the new u (the caller
    carries the old u as the next u_prev, like the scan carry)."""
    lib = load()
    if lib is None:
        raise RuntimeError("native library unavailable")
    a = np.ascontiguousarray(u, dtype=np.float32)
    p = np.ascontiguousarray(uprev, dtype=np.float32)
    out = np.empty_like(a)
    lib.stencilhost_wave2d_step(
        a.ctypes.data_as(ctypes.c_void_p), p.ctypes.data_as(ctypes.c_void_p),
        out.ctypes.data_as(ctypes.c_void_p),
        ctypes.c_int64(a.shape[0]), ctypes.c_int64(a.shape[1]),
        ctypes.c_float(c2dt2))
    return out


def grayscott2d_step_native(u: np.ndarray, v: np.ndarray, du: float,
                            dv: float, f: float, kappa: float):
    """Independent C++ Gray-Scott step; returns (new_u, new_v)."""
    lib = load()
    if lib is None:
        raise RuntimeError("native library unavailable")
    a = np.ascontiguousarray(u, dtype=np.float32)
    b = np.ascontiguousarray(v, dtype=np.float32)
    out_u = np.empty_like(a)
    out_v = np.empty_like(b)
    lib.stencilhost_grayscott2d_step(
        a.ctypes.data_as(ctypes.c_void_p), b.ctypes.data_as(ctypes.c_void_p),
        out_u.ctypes.data_as(ctypes.c_void_p),
        out_v.ctypes.data_as(ctypes.c_void_p),
        ctypes.c_int64(a.shape[0]), ctypes.c_int64(a.shape[1]),
        ctypes.c_float(du), ctypes.c_float(dv), ctypes.c_float(f),
        ctypes.c_float(kappa))
    return out_u, out_v


def heat3d27_step_native(grid: np.ndarray, alpha: float) -> np.ndarray:
    """Independent C++ 27-point high-order diffusion step."""
    lib = load()
    if lib is None:
        raise RuntimeError("native library unavailable")
    a = np.ascontiguousarray(grid, dtype=np.float32)
    out = np.empty_like(a)
    lib.stencilhost_heat3d27_step(
        a.ctypes.data_as(ctypes.c_void_p), out.ctypes.data_as(ctypes.c_void_p),
        ctypes.c_int64(a.shape[0]), ctypes.c_int64(a.shape[1]),
        ctypes.c_int64(a.shape[2]), ctypes.c_float(alpha))
    return out
