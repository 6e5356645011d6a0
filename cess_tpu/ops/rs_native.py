"""ctypes binding for the native (C++) ErasureCodec backend.

Loads ``cess_tpu/native/libcessrs.so`` (auto-building it with the
in-tree Makefile on first use if a compiler is available) and exposes
``NativeCodec`` with the same surface as rs_ref.ReferenceCodec /
rs.TPUCodec. This is the framework's fast host path — the role the
reference delegates to native reed-solomon crates in its off-chain
components (SURVEY.md §2.3/§2.4) — and the honest CPU baseline for the
TPU-speedup benchmark (BASELINE.md, ≥40×).
"""
from __future__ import annotations

import ctypes

import numpy as np

from .. import native
from . import gf


def _load() -> ctypes.CDLL:
    so = native.ensure_built("libcessrs.so")
    try:
        lib = ctypes.CDLL(so)
    except OSError as e:
        # importers expect ImportError so the ErasureCodec gate (and
        # bench) can fall back cleanly
        raise ImportError(f"cannot load {so}: {e}") from e
    lib.cess_rs_apply.argtypes = [
        ctypes.POINTER(ctypes.c_uint8), ctypes.c_int, ctypes.c_int,
        ctypes.POINTER(ctypes.c_uint8), ctypes.c_int64, ctypes.c_int64,
        ctypes.POINTER(ctypes.c_uint8), ctypes.c_int,
    ]
    lib.cess_rs_apply.restype = None
    lib.cess_rs_simd.restype = ctypes.c_int
    return lib


_LIB = _load()


def simd_level() -> int:
    """0 = scalar build, 2 = AVX2 build."""
    return int(_LIB.cess_rs_simd())


def _as_u8_ptr(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))


def apply_matrix(mat: np.ndarray, shards: np.ndarray,
                 threads: int = 1) -> np.ndarray:
    """GF matrix [r, q] applied to shards [..., q, n] -> [..., r, n]."""
    mat = np.ascontiguousarray(mat, dtype=np.uint8)
    shards = np.ascontiguousarray(shards, dtype=np.uint8)
    r, q = mat.shape
    lead = shards.shape[:-2]
    if shards.shape[-2] != q:
        raise ValueError(f"expected {q} shard rows, got {shards.shape[-2]}")
    n = shards.shape[-1]
    batch = int(np.prod(lead, dtype=np.int64)) if lead else 1
    out = np.empty((*lead, r, n), dtype=np.uint8)
    _LIB.cess_rs_apply(_as_u8_ptr(mat), r, q, _as_u8_ptr(shards),
                       batch, n, _as_u8_ptr(out), int(threads))
    return out


class NativeCodec:
    """Systematic RS(k, m) on the native C++ path (ErasureCodec
    surface: encode / encode_parity / reconstruct / decode_data)."""

    def __init__(self, k: int, m: int, threads: int = 1):
        if k < 1 or m < 0 or k + m > gf.FIELD:
            raise ValueError(f"invalid RS geometry k={k}, m={m}")
        self.k = k
        self.m = m
        self.threads = threads
        self.parity = gf.cauchy_parity_matrix(k, m)

    def encode_parity(self, data: np.ndarray) -> np.ndarray:
        return apply_matrix(self.parity, data, self.threads)

    def encode(self, data: np.ndarray) -> np.ndarray:
        data = np.asarray(data, dtype=np.uint8)
        if data.shape[-2] != self.k:
            raise ValueError(
                f"expected {self.k} data shards, got {data.shape[-2]}")
        return np.concatenate([data, self.encode_parity(data)], axis=-2)

    def reconstruct(self, survivors: np.ndarray, present: tuple[int, ...],
                    missing: tuple[int, ...] | None = None) -> np.ndarray:
        present = tuple(present)
        if missing is None:
            missing = tuple(i for i in range(self.k + self.m)
                            if i not in present)
        mat = gf.repair_matrix(self.k, self.m, present, tuple(missing))
        return apply_matrix(mat, survivors, self.threads)

    def decode_data(self, survivors: np.ndarray,
                    present: tuple[int, ...]) -> np.ndarray:
        mat = gf.decode_matrix(self.k, self.m, tuple(present))
        return apply_matrix(mat, survivors, self.threads)
