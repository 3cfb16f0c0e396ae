"""Loader for the native data helpers (csrc/data_helpers.cpp).

Same build/bind pattern as the DP core (galvatron_tpu.search.native): g++ on
first use, C ABI via ctypes, and a NumPy fallback computing the *identical*
permutation (keyed-hash argsort with splitmix64), so epoch shuffles are
bit-equal with or without the native library. Reference analogue:
megatron/data/helpers.cpp sample/shuffle index builders.
"""

from __future__ import annotations

import ctypes
from typing import Optional

import numpy as np

from galvatron_tpu.utils.native_build import load_native

_lib: Optional[ctypes.CDLL] = None
_load_failed = False


def get_data_helpers() -> Optional[ctypes.CDLL]:
    global _lib, _load_failed
    if _lib is None and not _load_failed:
        lib = load_native("data_helpers")
        if lib is None:
            _load_failed = True
            return None
        lib.galvatron_shuffle_index.restype = None
        lib.galvatron_shuffle_index.argtypes = [
            ctypes.c_int64,
            ctypes.c_uint64,
            np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
        ]
        _lib = lib
    return _lib


def _splitmix64_np(x: np.ndarray) -> np.ndarray:
    z = (x + np.uint64(0x9E3779B97F4A7C15)).astype(np.uint64)
    z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return z ^ (z >> np.uint64(31))


def mix_seed(*vals: int) -> int:
    """Collision-resistant combine of integer seed components via a splitmix64
    chain. The additive ``seed + epoch`` scheme the loaders used aliases
    adjacent streams — ``(seed=s, epoch=1)`` replayed ``(seed=s+1, epoch=0)``
    exactly — so every (seed, epoch) / (seed, source, epoch) derivation goes
    through this instead."""
    h = np.uint64(0x9E3779B97F4A7C15)
    with np.errstate(over="ignore"):
        for v in vals:
            h = _splitmix64_np(h ^ np.uint64(int(v) % (1 << 64)))
    return int(h)


def shuffle_index(n: int, seed: int) -> np.ndarray:
    """Deterministic permutation of [0, n): stable argsort of
    splitmix64(seed ^ i). Native when available, numpy otherwise — identical
    output either way."""
    lib = get_data_helpers()
    if lib is not None:
        out = np.empty((n,), np.int64)
        lib.galvatron_shuffle_index(
            np.int64(n), np.uint64(np.uint64(seed) & np.uint64(2**64 - 1)), out
        )
        return out
    with np.errstate(over="ignore"):
        keys = _splitmix64_np(np.uint64(seed) ^ np.arange(n, dtype=np.uint64))
    return np.argsort(keys, kind="stable").astype(np.int64)
