"""ctypes bridge to the native C++ scene-compile runtime (native/).

The reference's build/runtime layer is C++ (bvh.cpp's builders run inside
the C++ process); ours mirrors that: hot host-side compile steps live in
native/*.cpp, compiled once into .native/libtpupbrt-<key>.so by the local
g++ and loaded here through ctypes (no pybind11 in this environment —
plain C ABI with caller-allocated numpy buffers).

The binary is keyed on the CONTENT of the source and the compiler flags,
never on file times: a tree copied as it stands on disk (or a checkout
over an old `.native/`) can hold a binary newer than a source it was not
built from.

Which builder runs is the operator's choice and nothing else:
TPU_PBRT_NATIVE=0 selects the pure-numpy implementations (tests cover
both paths and assert they agree); otherwise the native library is
REQUIRED, and a missing g++ or a failed build raises NativeBuildError
with the compiler's output instead of quietly building a different tree
with a slower builder."""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_SRC = os.path.join(_REPO, "native", "bvh_builder.cpp")
_OUT_DIR = os.path.join(_REPO, ".native")
_CXX = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC"]

_lock = threading.Lock()
_lib = None


class NativeBuildError(RuntimeError):
    """The native scene-compile library could not be built or loaded."""


def builder_name() -> str:
    """Which BVH builder this process uses: 'native' | 'numpy'."""
    from tpu_pbrt.config import cfg

    return "native" if cfg.native else "numpy"


def _compile() -> str:
    """Build (or find) the library for THIS source and these flags;
    returns its path."""
    try:
        with open(_SRC, "rb") as fh:
            src = fh.read()
    except OSError as e:
        raise NativeBuildError(f"native source unreadable: {e}") from e
    key = hashlib.sha256(src + " ".join(_CXX).encode()).hexdigest()[:16]
    lib = os.path.join(_OUT_DIR, f"libtpupbrt-{key}.so")
    if os.path.exists(lib):
        return lib
    os.makedirs(_OUT_DIR, exist_ok=True)
    # build beside the final name, then rename: a concurrent process
    # (fleet replicas start together) never loads a half-written file
    tmp = f"{lib}.{os.getpid()}.tmp"
    hint = "set TPU_PBRT_NATIVE=0 to use the numpy builders"
    try:
        r = subprocess.run(
            _CXX + ["-o", tmp, _SRC], capture_output=True, timeout=120
        )
    except (OSError, subprocess.TimeoutExpired) as e:
        raise NativeBuildError(f"native build did not run ({e}); {hint}") from e
    if r.returncode != 0:
        raise NativeBuildError(
            f"native build failed (rc={r.returncode}): "
            f"{r.stderr.decode(errors='replace')[-2000:]}\n{hint}"
        )
    os.replace(tmp, lib)
    return lib


def get_lib():
    """The loaded native library, or None under TPU_PBRT_NATIVE=0 (the
    numpy builders). Raises NativeBuildError when the library is wanted
    and cannot be had."""
    global _lib
    from tpu_pbrt.config import cfg

    if not cfg.native:
        return None
    with _lock:
        if _lib is not None:
            return _lib
        path = _compile()
        try:
            lib = ctypes.CDLL(path)
        except OSError as e:
            raise NativeBuildError(f"cannot load {path}: {e}") from e
        lib.build_sah_bvh.restype = ctypes.c_int64
        lib.build_sah_bvh.argtypes = [
            ctypes.POINTER(ctypes.c_double),  # bmin
            ctypes.POINTER(ctypes.c_double),  # bmax
            ctypes.c_int64,  # n
            ctypes.c_int32,  # max_leaf
            ctypes.POINTER(ctypes.c_float),  # out_min
            ctypes.POINTER(ctypes.c_float),  # out_max
            ctypes.POINTER(ctypes.c_int32),  # out_prim_off
            ctypes.POINTER(ctypes.c_int32),  # out_nprims
            ctypes.POINTER(ctypes.c_int32),  # out_second
            ctypes.POINTER(ctypes.c_int32),  # out_axis
            ctypes.POINTER(ctypes.c_int64),  # out_order
        ]
        _lib = lib
        return _lib


def _ptr(a, ctype):
    return a.ctypes.data_as(ctypes.POINTER(ctype))


def native_build_sah(bmin: np.ndarray, bmax: np.ndarray, max_leaf: int):
    """Run the native SAH build; returns BVHArrays, or None under
    TPU_PBRT_NATIVE=0 (the caller then runs the numpy builder)."""
    lib = get_lib()
    if lib is None:
        return None
    from tpu_pbrt.accel.build import BVHArrays

    n = len(bmin)
    bmin = np.ascontiguousarray(bmin, np.float64)
    bmax = np.ascontiguousarray(bmax, np.float64)
    cap = 2 * n + 1
    out_min = np.empty((cap, 3), np.float32)
    out_max = np.empty((cap, 3), np.float32)
    out_prim_off = np.zeros(cap, np.int32)
    out_nprims = np.zeros(cap, np.int32)
    out_second = np.zeros(cap, np.int32)
    out_axis = np.zeros(cap, np.int32)
    out_order = np.empty(n, np.int64)
    m = lib.build_sah_bvh(
        _ptr(bmin, ctypes.c_double),
        _ptr(bmax, ctypes.c_double),
        ctypes.c_int64(n),
        ctypes.c_int32(max_leaf),
        _ptr(out_min, ctypes.c_float),
        _ptr(out_max, ctypes.c_float),
        _ptr(out_prim_off, ctypes.c_int32),
        _ptr(out_nprims, ctypes.c_int32),
        _ptr(out_second, ctypes.c_int32),
        _ptr(out_axis, ctypes.c_int32),
        _ptr(out_order, ctypes.c_int64),
    )
    if m <= 0:
        raise RuntimeError(f"native SAH build failed on {n} primitives (rc={m})")
    return BVHArrays(
        bounds_min=out_min[:m].copy(),
        bounds_max=out_max[:m].copy(),
        prim_offset=out_prim_off[:m].copy(),
        n_prims=out_nprims[:m].copy(),
        second_child=out_second[:m].copy(),
        axis=out_axis[:m].copy(),
        prim_order=out_order,
    )
