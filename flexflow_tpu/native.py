"""ctypes bindings for the native PCG core (native/src/pcg_core.cc).

The reference keeps its graph/search core in C++ (SURVEY §2.1); this module
loads our C++ equivalent, building it with make on first use (pybind11 is
not installed, hence ctypes). Every entry point has a pure-Python fallback
so the framework works without a toolchain — the failed build is logged
once, so a run says which core served it.

The library's file name carries a digest of the source it was built from,
so a binary left on disk by another version of pcg_core.cc (native/build/
is not under git, and a copied tree keeps it) is never loaded: a source
without its binary is simply built.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
from typing import Optional

import numpy as np

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                           "native")
_SOURCE = os.path.join(_NATIVE_DIR, "src", "pcg_core.cc")

_lib = None
_lib_tried = False


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _lib_tried
    if _lib is not None or _lib_tried:
        return _lib
    _lib_tried = True
    from .telemetry import log as fflog

    try:
        with open(_SOURCE, "rb") as f:
            digest = hashlib.sha256(f.read()).hexdigest()[:12]
        lib_name = f"libpcg_core.{digest}.so"
        lib_path = os.path.join(_NATIVE_DIR, "build", lib_name)
        if not os.path.exists(lib_path):
            subprocess.run(
                ["make", "-C", _NATIVE_DIR, f"LIB={lib_name}"],
                check=True, capture_output=True, text=True)
        lib = ctypes.CDLL(lib_path)
    except (OSError, subprocess.CalledProcessError) as e:
        detail = (getattr(e, "stderr", "") or "").strip()
        fflog.warning(
            "native PCG core unavailable (%s%s) — the Python fallback "
            "serves", e, f": {detail}" if detail else "")
        return None
    i32p = ctypes.POINTER(ctypes.c_int32)
    lib.ff_topo_order.restype = ctypes.c_int
    lib.ff_topo_order.argtypes = [ctypes.c_int32, ctypes.c_int32,
                                  i32p, i32p, i32p]
    lib.ff_bottlenecks.restype = ctypes.c_int
    lib.ff_bottlenecks.argtypes = lib.ff_topo_order.argtypes
    lib.ff_transitive_reduction.restype = ctypes.c_int
    lib.ff_transitive_reduction.argtypes = lib.ff_topo_order.argtypes
    lib.ff_idominators.restype = ctypes.c_int
    lib.ff_idominators.argtypes = lib.ff_topo_order.argtypes
    lib.ff_eval_makespan.restype = ctypes.c_double
    lib.ff_eval_makespan.argtypes = [
        ctypes.c_int32, ctypes.POINTER(ctypes.c_double),
        ctypes.POINTER(ctypes.c_double),
        ctypes.c_int32, i32p, i32p]
    lib.ff_eval_makespan_axes.restype = ctypes.c_double
    lib.ff_eval_makespan_axes.argtypes = [
        ctypes.c_int32, ctypes.POINTER(ctypes.c_double),
        ctypes.POINTER(ctypes.c_double), i32p,
        ctypes.c_int32, i32p, i32p]
    _lib = lib
    return _lib


def available() -> bool:
    return _load() is not None


def _as_i32(a):
    return np.ascontiguousarray(a, dtype=np.int32)


def _ptr(a):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))


def topo_order(n: int, src, dst) -> Optional[np.ndarray]:
    lib = _load()
    if lib is None:
        return None
    src, dst = _as_i32(src), _as_i32(dst)
    out = np.zeros(n, np.int32)
    rc = lib.ff_topo_order(n, len(src), _ptr(src), _ptr(dst), _ptr(out))
    return out if rc == 0 else None


def bottlenecks(n: int, src, dst) -> Optional[np.ndarray]:
    lib = _load()
    if lib is None:
        return None
    src, dst = _as_i32(src), _as_i32(dst)
    mask = np.zeros(n, np.int32)
    rc = lib.ff_bottlenecks(n, len(src), _ptr(src), _ptr(dst), _ptr(mask))
    return mask.astype(bool) if rc >= 0 else None


def transitive_reduction(n: int, src, dst) -> Optional[np.ndarray]:
    lib = _load()
    if lib is None:
        return None
    src, dst = _as_i32(src), _as_i32(dst)
    keep = np.zeros(len(src), np.int32)
    rc = lib.ff_transitive_reduction(n, len(src), _ptr(src), _ptr(dst),
                                     _ptr(keep))
    return keep.astype(bool) if rc == 0 else None


def idominators(n: int, src, dst) -> Optional[np.ndarray]:
    lib = _load()
    if lib is None:
        return None
    src, dst = _as_i32(src), _as_i32(dst)
    out = np.zeros(n, np.int32)
    rc = lib.ff_idominators(n, len(src), _ptr(src), _ptr(dst), _ptr(out))
    return out if rc == 0 else None


def eval_makespan(compute, comm, src, dst) -> Optional[float]:
    """Critical-path makespan with serialized compute (ff_eval_makespan):
    max(sum(compute), longest path of compute+comm). None if the native lib
    is unavailable; raises ValueError on a cyclic graph (the two cases must
    stay distinguishable so a cyclic candidate is rejected rather than
    silently re-costed by the Python fallback)."""
    lib = _load()
    if lib is None:
        return None
    co = np.ascontiguousarray(compute, np.float64)
    cm = np.ascontiguousarray(comm, np.float64)
    src, dst = _as_i32(src), _as_i32(dst)
    out = lib.ff_eval_makespan(
        len(co), co.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        cm.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        len(src), _ptr(src), _ptr(dst))
    if out < 0:
        raise ValueError("eval_makespan: graph has a cycle")
    return float(out)


def eval_makespan_axes(compute, comm, axis, src, dst) -> Optional[float]:
    """Resource-aware makespan (ff_eval_makespan_axes): adds per-ICI-axis
    link-occupancy lower bounds — comm tasks on the same mesh axis
    serialize, disjoint axes overlap (the TPU recast of the reference's
    horizontal machine-resource splits). axis[i] is an int id, -1 = none.
    None if the native lib is unavailable; ValueError on a cycle."""
    lib = _load()
    if lib is None:
        return None
    co = np.ascontiguousarray(compute, np.float64)
    cm = np.ascontiguousarray(comm, np.float64)
    ax = _as_i32(axis)
    src, dst = _as_i32(src), _as_i32(dst)
    out = lib.ff_eval_makespan_axes(
        len(co), co.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        cm.ctypes.data_as(ctypes.POINTER(ctypes.c_double)), _ptr(ax),
        len(src), _ptr(src), _ptr(dst))
    if out < 0:
        raise ValueError("eval_makespan_axes: graph has a cycle")
    return float(out)
