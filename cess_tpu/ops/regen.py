"""Regenerating-code repair plane: computed repair symbols + fast
Cauchy-MDS decode.

Two papers, one plane:

- Fast Product-Matrix Regenerating Codes (arxiv 1412.3022): repair
  traffic should carry COMPUTED symbols, not raw fragments. Here each
  helper scales its survivor fragment by one product-matrix repair
  coefficient (``repair_coeffs``) and XOR-folds the result into a
  partial-sum accumulator passed down the helper chain
  (``fold_symbol_host``); only the final fragment-sized aggregate ever
  reaches the rebuilder. By GF(2^8) linearity the aggregate IS the
  reference reconstruction — ``XOR_j coeff_j * fragment_j`` equals the
  repair-matrix row applied to the survivors — so the rebuilder's
  ingress drops from k fragments to one, bit-identically.
- Cauchy MDS Array Codes With Efficient Decoding Method (arxiv
  1611.09968): the decode matrix for an erasure pattern is the inverse
  of a k x k submatrix of the systematic Cauchy generator. Instead of
  Gauss-Jordan elimination (gf.gf_mat_inv, O(t^3) with table lookups),
  the surviving-parity-by-missing-data subsystem is itself Cauchy, so
  its inverse has the closed product form (``cauchy_inverse``,
  O(t^2)); the full decode matrix assembles from it by one Schur
  complement step (``decode_matrix``). A field inverse is unique, so
  the fast construction is byte-identical to the reference path —
  pinned by tests, never assumed.

Device surfaces live behind the existing ``ErasureCodec`` gate
(ops/rs.py ``make_codec(..., backend="regen")``): ``RegenCodec``
subclasses TPUCodec, swaps every decode/repair matrix construction for
the closed form, and adds the batched symbol fold
(``fold_symbol`` — a [1, 2] GF matmul over (accumulator, fragment) row
pairs via the same gather/pallas lowerings) through the
per-shape programs of ops/rs.py (the coefficient an operand), warmed by
``engine.warm_repair`` base and per lane.
``RegenReference`` is the NumPy twin serving as the byte-exact oracle
and the engine's CPU-degraded fallback.

Determinism and sharing contracts (cesslint: this module is in the
sim-determinism and lock-discipline families): coefficient and matrix
construction feed the deterministic sim's repair storm and the
engine's warm caches, so nothing here may read a clock or draw
entropy; the warm/apply caches inherited from TPUCodec are shared by
the engine's batcher and pool-lane worker threads, so any state this
module adds must stay within the same single-writer warm-then-dispatch
discipline.
"""
from __future__ import annotations

import numpy as np

from . import gf
from .rs import TPUCodec
from .rs_ref import ReferenceCodec

__all__ = [
    "cauchy_inverse", "decode_matrix", "repair_matrix", "repair_coeffs",
    "fold_symbol_host", "fold_symbol_pairs", "RegenCodec",
    "RegenReference",
]


# ---------------------------------------------------------------------------
# Pattern validation (shared by every construction below)
# ---------------------------------------------------------------------------


def _check_pattern(k: int, m: int, present: tuple[int, ...],
                   what: str = "present") -> tuple[int, ...]:
    """Refuse malformed erasure patterns loudly: duplicates,
    out-of-range rows and (for ``present``) wrong survivor counts all
    produce garbage matrices downstream if let through."""
    present = tuple(int(r) for r in present)
    rows = k + m
    if len(set(present)) != len(present):
        raise ValueError(f"duplicate {what} shard indices: {present}")
    for r in present:
        if not 0 <= r < rows:
            raise ValueError(f"{what} shard index {r} out of range for "
                             f"RS({k},{m}) with {rows} rows")
    return present


# ---------------------------------------------------------------------------
# The efficient decoding method (arxiv 1611.09968)
# ---------------------------------------------------------------------------


def cauchy_inverse(xs, ys) -> np.ndarray:
    """Closed-form inverse of the Cauchy matrix A[i, j] = 1/(xs[i] ^ ys[j]).

    The classic product formula (subtraction is XOR in GF(2^8)):

        inv[j, i] = prod_l (xs[l]^ys[j]) * prod_l (xs[i]^ys[l])
                    / ((xs[i]^ys[j]) * prod_{l!=j} (ys[j]^ys[l])
                                     * prod_{l!=i} (xs[i]^xs[l]))

    O(t^2) multiplies after the O(t) prefix products, vs O(t^3) for
    Gauss-Jordan — and exactly equal to it, because a matrix inverse
    over a field is unique.
    """
    xs = tuple(int(x) for x in xs)
    ys = tuple(int(y) for y in ys)
    t = len(xs)
    if len(ys) != t:
        raise ValueError(f"need square Cauchy geometry, got {len(xs)} "
                         f"x-nodes and {len(ys)} y-nodes")
    if len(set(xs)) != t or len(set(ys)) != t or set(xs) & set(ys):
        raise ValueError("Cauchy nodes must be distinct and disjoint")
    # row/column products: full_x[i] = prod_l (xs[i] ^ ys[l]),
    # full_y[j] = prod_l (xs[l] ^ ys[j]); the diagonal-free node
    # products feed the denominator
    full_x = [1] * t
    full_y = [1] * t
    for i in range(t):
        for l in range(t):
            full_x[i] = gf.gf_mul(full_x[i], xs[i] ^ ys[l])
            full_y[i] = gf.gf_mul(full_y[i], xs[l] ^ ys[i])
    node_x = [1] * t
    node_y = [1] * t
    for i in range(t):
        for l in range(t):
            if l == i:
                continue
            node_x[i] = gf.gf_mul(node_x[i], xs[i] ^ xs[l])
            node_y[i] = gf.gf_mul(node_y[i], ys[i] ^ ys[l])
    inv = np.zeros((t, t), dtype=np.uint8)
    for j in range(t):
        for i in range(t):
            num = gf.gf_mul(full_y[j], full_x[i])
            den = gf.gf_mul(xs[i] ^ ys[j],
                            gf.gf_mul(node_y[j], node_x[i]))
            inv[j, i] = gf.gf_mul(num, gf.gf_inv(den))
    return inv


def decode_matrix(k: int, m: int, present: tuple[int, ...]) -> np.ndarray:
    """Decode matrix for ``present`` via one Schur-complement step over
    the closed-form Cauchy inverse — byte-identical to
    ``gf.decode_matrix`` (same unique inverse), without the
    Gauss-Jordan elimination.

    The survivor system splits: present data rows pin their own bytes
    directly, and each surviving parity row q reduces to an equation
    over just the MISSING data columns M —

        sum_{j in M} c[q, j] * data_j
            = shard_q  ^  sum_{d in D} c[q, d] * shard_d.

    The t x t submatrix c[q, j] = 1/((k+q) ^ j) is itself Cauchy
    (x-nodes k+q, y-nodes j), so its inverse is ``cauchy_inverse``.
    """
    present = _check_pattern(k, m, present)
    if len(present) != k:
        raise ValueError(f"need exactly k={k} present shard indices, "
                         f"got {len(present)}")
    pos = {r: p for p, r in enumerate(present)}
    data_rows = [r for r in present if r < k]
    parity_rows = [r - k for r in present if r >= k]
    missing_cols = [j for j in range(k) if j not in pos]
    inv = np.zeros((k, k), dtype=np.uint8)
    for d in data_rows:
        inv[d, pos[d]] = 1
    if not missing_cols:
        return inv
    w = cauchy_inverse([k + q for q in parity_rows], missing_cols)
    mt = gf.mul_table()
    for b, col in enumerate(missing_cols):
        for a, q in enumerate(parity_rows):
            coeff = int(w[b, a])
            inv[col, pos[k + q]] ^= coeff
            for d in data_rows:
                inv[col, pos[d]] ^= int(
                    mt[coeff, gf.gf_inv((k + q) ^ d)])
    return inv


def repair_matrix(k: int, m: int, present: tuple[int, ...],
                  missing: tuple[int, ...]) -> np.ndarray:
    """Repair matrix (generator rows of ``missing`` times the decode
    matrix) built on the fast path — byte-identical to
    ``gf.repair_matrix``."""
    missing = _check_pattern(k, m, missing, what="missing")
    g = gf.systematic_generator(k, m)
    return gf.gf_matmul(g[list(missing)], decode_matrix(k, m, present))


def repair_coeffs(k: int, m: int, present: tuple[int, ...],
                  missing: tuple[int, ...]) -> tuple[int, ...]:
    """The per-helper product-matrix coefficients for one lost row:
    helper at survivor position p contributes coeff[p] * fragment_p,
    and the XOR of all k contributions IS the lost fragment."""
    missing = tuple(int(r) for r in missing)
    if len(missing) != 1:
        raise ValueError("repair symbols regenerate ONE row per chain; "
                         f"got missing={missing}")
    row = repair_matrix(k, m, present, missing)
    return tuple(int(c) for c in row[0])


# ---------------------------------------------------------------------------
# The symbol fold: CPU reference twins
# ---------------------------------------------------------------------------


def fold_symbol_host(acc: np.ndarray, fragment: np.ndarray,
                     coeff: int) -> np.ndarray:
    """One helper's partial-sum hop on the host: acc ^ coeff*fragment.
    The byte-exact oracle for the device fold."""
    mt = gf.mul_table()
    acc = np.asarray(acc, dtype=np.uint8)
    fragment = np.asarray(fragment, dtype=np.uint8)
    return (acc ^ mt[int(coeff)][fragment]).astype(np.uint8)


def fold_symbol_pairs(pairs: np.ndarray, coeff: int) -> np.ndarray:
    """Batched host twin of ``RegenCodec.fold_symbol``: pairs
    [..., 2, n] of (accumulator, fragment) rows -> [..., 1, n]."""
    pairs = np.asarray(pairs, dtype=np.uint8)
    if pairs.shape[-2] != 2:
        raise ValueError(f"expected (accumulator, fragment) row pairs, "
                         f"got {pairs.shape[-2]} rows")
    mt = gf.mul_table()
    return (pairs[..., 0:1, :]
            ^ mt[int(coeff)][pairs[..., 1:2, :]]).astype(np.uint8)


def _symbol_matrix(coeff: int) -> np.ndarray:
    """The fold as a GF matrix: [1, coeff] applied to (acc, fragment)
    row pairs — one batched GF(2^8) matmul, same lowerings as every
    other codec apply."""
    coeff = int(coeff)
    if not 0 <= coeff < gf.FIELD:
        raise ValueError(f"repair coefficient {coeff} outside GF(2^8)")
    return np.array([[1, coeff]], dtype=np.uint8)


# ---------------------------------------------------------------------------
# Device codec behind the ErasureCodec gate
# ---------------------------------------------------------------------------


class RegenCodec(TPUCodec):
    """TPUCodec with the regenerating-repair surfaces: every decode /
    repair matrix comes from the closed-form Cauchy construction, and
    ``fold_symbol`` runs the helper partial-sum hop as a batched device
    matmul. The warm path (``warm_reconstruct``, a program per shape
    and placement) is inherited unchanged, so ``engine.warm_repair``
    serves regen patterns the same way it serves plain reconstructs.

    A fold's matrix is ``[1, coeff]``, one a coefficient, and a chain
    of k helpers asks for k of them a repair: RS(10,4) repaired from
    the ten lowest holders meets 140 over its 14 patterns. The LRU
    holds every one GF(2^8) has beside the decode matrices (an operand
    is 128 bytes), so a chain rebuilds none."""

    MATRICES = TPUCodec.MATRICES + gf.FIELD - 1

    def _build_matrix(self, kind: str, present: tuple[int, ...],
                      missing: tuple[int, ...]) -> np.ndarray:
        if kind == "decode":
            return decode_matrix(self.k, self.m, present)
        if kind == "symbol":
            return _symbol_matrix(present[0])
        return repair_matrix(self.k, self.m, present, missing)

    # -- the symbol fold ---------------------------------------------------
    def warm_fold(self, coeff: int, shape, device=None):
        """Pre-compile + pre-stage the symbol fold for one exact pair
        shape, per device, and stage this coefficient's operands — the
        regen leg of ``engine.warm_repair``. Same contract as
        ``warm_reconstruct``: the coefficient is an operand and one
        program folds them all."""
        self._warm_program(("symbol", (int(coeff),), ()), shape, device)

    def fold_symbol(self, pairs, coeff: int, *, sink: dict | None = None):
        """pairs [..., 2, n] uint8 (accumulator, fragment) rows, or
        the same as ``rs.LinearRows`` -> [..., 1, n]: acc ^
        coeff*fragment, batched on device.
        Compiles nothing when warmed for this shape and placement.
        ``sink`` as for ``reconstruct``."""
        return self._apply(("symbol", (int(coeff),), ()), pairs, sink)

    def repair_coeffs(self, present: tuple[int, ...],
                      missing: tuple[int, ...]) -> tuple[int, ...]:
        """Geometry-bound convenience over module-level
        ``repair_coeffs``."""
        return repair_coeffs(self.k, self.m, tuple(present),
                             tuple(missing))


class RegenReference(ReferenceCodec):
    """NumPy twin of RegenCodec: the same closed-form matrix
    constructions applied with the host GF matmul loop. The byte-exact
    oracle the device path is pinned against, and the symbol fold the
    engine's CPU-degraded path serves."""

    def reconstruct(self, survivors: np.ndarray, present: tuple[int, ...],
                    missing: tuple[int, ...] | None = None) -> np.ndarray:
        present = tuple(present)
        if missing is None:
            missing = tuple(i for i in range(self.k + self.m)
                            if i not in present)
        mat = repair_matrix(self.k, self.m, present, tuple(missing))
        return self._apply(mat, survivors)

    def decode_data(self, survivors: np.ndarray,
                    present: tuple[int, ...]) -> np.ndarray:
        mat = decode_matrix(self.k, self.m, tuple(present))
        return self._apply(mat, survivors)

    def fold_symbol(self, pairs: np.ndarray, coeff: int) -> np.ndarray:
        return fold_symbol_pairs(pairs, coeff)

    def repair_coeffs(self, present: tuple[int, ...],
                      missing: tuple[int, ...]) -> tuple[int, ...]:
        return repair_coeffs(self.k, self.m, tuple(present),
                             tuple(missing))
