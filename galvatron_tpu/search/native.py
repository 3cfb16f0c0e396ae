"""Loader for the native C++ DP core (csrc/dp_core.cpp).

The reference builds its DP kernel with pybind11 via setup.py (reference:
csrc/dp_core.cpp:92-94, setup.py:39-44, Makefile:1-20). pybind11 is not in
this environment, so the kernel is a plain C-ABI shared object compiled with
g++ on first use and bound with ctypes; dynamic_programming.py falls back to
NumPy when no compiler is available (mirroring the reference's NumPy fallback,
galvatron/core/dynamic_programming.py:98-128).
"""

from __future__ import annotations

import ctypes
from typing import Optional

import numpy as np

from galvatron_tpu.utils.native_build import load_native

_lib: Optional[ctypes.CDLL] = None
_load_failed = False


def get_dp_core() -> Optional[ctypes.CDLL]:
    """Returns the loaded library or None (→ NumPy fallback)."""
    global _lib, _load_failed
    if _lib is None and not _load_failed:
        lib = load_native("dp_core")
        if lib is None:
            _load_failed = True
            return None
        lib.galvatron_dp_core.restype = ctypes.c_double
        lib.galvatron_dp_core.argtypes = [
            ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
            np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
            np.ctypeslib.ndpointer(np.float64, flags="C_CONTIGUOUS"),
            np.ctypeslib.ndpointer(np.float64, flags="C_CONTIGUOUS"),
            np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
            ctypes.POINTER(ctypes.c_int32),
        ]
        _lib = lib
    return _lib


def dp_core_native(mem: np.ndarray, intra: np.ndarray, inter: np.ndarray, budget: int):
    """Run the native DP. mem: (L,S) int32 units; intra: (L,S); inter: (S,S).
    Returns (min_cost, res[L], mem_used) or None if the library is missing."""
    lib = get_dp_core()
    if lib is None:
        return None
    L, S = mem.shape
    res = np.full((L,), -1, np.int32)
    mem_used = ctypes.c_int32(0)
    cost = lib.galvatron_dp_core(
        np.int32(L), np.int32(budget), np.int32(S),
        np.ascontiguousarray(mem, np.int32),
        np.ascontiguousarray(intra, np.float64),
        np.ascontiguousarray(inter, np.float64),
        res, ctypes.byref(mem_used),
    )
    return float(cost), res, int(mem_used.value)
