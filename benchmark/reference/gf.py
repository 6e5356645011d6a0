"""GF(2^8) arithmetic core (NumPy, host-side).

This module owns the finite-field math the erasure codec is built on:

- exp/log tables for GF(2^8) with the AES-adjacent polynomial 0x11D
  (x^8 + x^4 + x^3 + x^2 + 1), the same field used by standard
  reed-solomon-erasure implementations the reference's off-chain
  components rely on (see SURVEY.md §2.3).
- Cauchy parity-matrix construction for a systematic RS(k, m) code.
- GF matrix inversion (Gauss-Jordan) for decode.
- Bit-matrix ("bitslice") expansion: every GF(2^8) constant multiply
  is an 8x8 matrix over GF(2), so an (r x k) GF byte-matrix apply
  becomes an (8r x 8k) 0/1 matrix applied to the bit-planes of the
  data with XOR accumulation — i.e. an integer matmul followed by
  ``& 1``. That is the lowering that puts RS encode/decode onto the
  TPU MXU (see cess_tpu/ops/rs.py).

All functions here are NumPy/host-side; they produce small constant
matrices consumed by the JAX/Pallas device paths.
"""
from __future__ import annotations

import functools

import numpy as np

POLY = 0x11D
FIELD = 256
ORDER = 255  # multiplicative group order


def _build_tables() -> tuple[np.ndarray, np.ndarray]:
    exp = np.zeros(2 * ORDER, dtype=np.uint8)
    log = np.zeros(FIELD, dtype=np.int32)
    x = 1
    for i in range(ORDER):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= POLY
    exp[ORDER:] = exp[:ORDER]
    return exp, log


EXP, LOG = _build_tables()


def gf_mul(a: int, b: int) -> int:
    if a == 0 or b == 0:
        return 0
    return int(EXP[LOG[a] + LOG[b]])


def gf_inv(a: int) -> int:
    if a == 0:
        raise ZeroDivisionError("GF(2^8) inverse of 0")
    return int(EXP[ORDER - LOG[a]])


def gf_pow(a: int, n: int) -> int:
    if n == 0:
        return 1
    if a == 0:
        return 0
    return int(EXP[(LOG[a] * n) % ORDER])


@functools.cache
def mul_table() -> np.ndarray:
    """Full 256x256 multiplication table; MUL[a, b] = a*b in GF(2^8)."""
    la = LOG.reshape(FIELD, 1)
    lb = LOG.reshape(1, FIELD)
    t = EXP[(la + lb) % ORDER].astype(np.uint8)
    t[0, :] = 0
    t[:, 0] = 0
    t.flags.writeable = False  # shared cached table; mutation would corrupt all math
    return t


def gf_matmul(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """GF(2^8) matrix product of uint8 matrices a @ b (XOR-accumulate).

    a: [r, k], b: [k, n] (n may be large — b rows are data). Vectorised
    with the 256-entry row tables of ``mul_table``; this is the CPU
    oracle the TPU path is golden-tested against.
    """
    a = np.asarray(a, dtype=np.uint8)
    b = np.asarray(b, dtype=np.uint8)
    assert a.ndim == 2 and b.ndim == 2 and a.shape[1] == b.shape[0]
    mt = mul_table()
    out = np.zeros((a.shape[0], b.shape[1]), dtype=np.uint8)
    for i in range(a.shape[0]):
        acc = out[i]
        for j in range(a.shape[1]):
            c = a[i, j]
            if c:
                acc ^= mt[c][b[j]]
        out[i] = acc
    return out


def gf_mat_inv(m: np.ndarray) -> np.ndarray:
    """Invert a square GF(2^8) matrix by Gauss-Jordan elimination."""
    m = np.array(m, dtype=np.uint8)
    n = m.shape[0]
    assert m.shape == (n, n)
    aug = np.concatenate([m, np.eye(n, dtype=np.uint8)], axis=1)
    mt = mul_table()
    for col in range(n):
        pivot = None
        for row in range(col, n):
            if aug[row, col]:
                pivot = row
                break
        if pivot is None:
            raise np.linalg.LinAlgError("singular GF(2^8) matrix")
        if pivot != col:
            aug[[col, pivot]] = aug[[pivot, col]]
        inv_p = gf_inv(int(aug[col, col]))
        aug[col] = mt[inv_p][aug[col]]
        for row in range(n):
            if row != col and aug[row, col]:
                aug[row] ^= mt[int(aug[row, col])][aug[col]]
    return aug[:, n:].copy()


def cauchy_parity_matrix(k: int, m: int) -> np.ndarray:
    """The m x k Cauchy parity matrix C[i, j] = 1 / (x_i ^ y_j).

    Points: y_j = j for data columns, x_i = k + i for parity rows; all
    distinct for k + m <= 256, so every square submatrix of the
    systematic generator [[I_k], [C]] is invertible (MDS property).
    """
    if k + m > FIELD:
        raise ValueError(f"k + m = {k + m} exceeds field size {FIELD}")
    c = np.zeros((m, k), dtype=np.uint8)
    for i in range(m):
        for j in range(k):
            c[i, j] = gf_inv((k + i) ^ j)
    return c


def systematic_generator(k: int, m: int) -> np.ndarray:
    """(k+m) x k generator: rows 0..k-1 identity, rows k..k+m-1 Cauchy."""
    return np.concatenate(
        [np.eye(k, dtype=np.uint8), cauchy_parity_matrix(k, m)], axis=0
    )


def _check_rows(k: int, m: int, rows: tuple[int, ...], what: str) -> None:
    if len(set(rows)) != len(rows):
        raise ValueError(f"duplicate {what} shard indices: {rows}")
    for r in rows:
        if not 0 <= int(r) < k + m:
            raise ValueError(f"{what} shard index {r} out of range for "
                             f"RS({k},{m}) with {k + m} rows")


def decode_matrix(k: int, m: int, present: tuple[int, ...]) -> np.ndarray:
    """Matrix R s.t. data = R @ shards[present] for any k present shard rows."""
    if len(present) != k:
        raise ValueError(f"need exactly k={k} present shard indices, got {len(present)}")
    _check_rows(k, m, present, "present")
    g = systematic_generator(k, m)
    sub = g[list(present)]
    return gf_mat_inv(sub)


def repair_matrix(k: int, m: int, present: tuple[int, ...],
                  missing: tuple[int, ...]) -> np.ndarray:
    """Matrix M s.t. shards[missing] = M @ shards[present]."""
    _check_rows(k, m, missing, "missing")
    g = systematic_generator(k, m)
    inv = decode_matrix(k, m, present)
    return gf_matmul(g[list(missing)], inv)


@functools.cache
def _single_bitmatrix(c: int) -> np.ndarray:
    """8x8 GF(2) matrix of multiply-by-c: M[a, b] = bit a of (c * 2^b)."""
    m = np.zeros((8, 8), dtype=np.uint8)
    for b in range(8):
        prod = gf_mul(c, 1 << b)
        for a in range(8):
            m[a, b] = (prod >> a) & 1
    m.flags.writeable = False  # shared cached matrix
    return m


def expand_bitmatrix(gf_mat: np.ndarray) -> np.ndarray:
    """Expand an (r x k) GF(2^8) byte matrix to its (8r x 8k) GF(2) form.

    Row index 8*i + a is output bit a of output byte i; column index
    8*j + b is input bit b of input byte j. Applying this matrix to the
    bit-planes of the data with XOR accumulation (integer matmul, then
    ``& 1``) computes the GF(2^8) matrix product — the MXU-friendly
    lowering used by the TPU codec.
    """
    gf_mat = np.asarray(gf_mat, dtype=np.uint8)
    r, k = gf_mat.shape
    out = np.zeros((8 * r, 8 * k), dtype=np.uint8)
    for i in range(r):
        for j in range(k):
            out[8 * i:8 * i + 8, 8 * j:8 * j + 8] = _single_bitmatrix(int(gf_mat[i, j]))
    return out
