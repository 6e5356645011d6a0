"""TPU-native GF(2^8) Reed-Solomon erasure codec (JAX/XLA).

The reference framework's segment->fragment erasure coding runs as a
sequential CPU loop in off-chain components (SURVEY.md §2.3, §6); here
it becomes a batched GF(2^8) matrix apply on TPU. Two lowerings, both
byte-exact against the NumPy oracle (cess_tpu/ops/rs_ref.py), and the
platform chooses between them (``default_strategy``):

- ``pallas``, the chip's: every GF(2^8) constant multiply is an 8x8
  GF(2) matrix, so the whole (r x q) GF apply becomes one (8r x 8q)
  0/1 matrix applied to bit-planes with XOR accumulation = a matmul on
  the MXU followed by ``& 1``, fused into one Pallas kernel that keeps
  the 8x bit expansion in VMEM (cess_tpu/ops/rs_pallas.py). Every
  line of PERF_LEDGER.jsonl ran it.
- ``gather``, the CPU's (the test mesh, the degraded fallback): the
  classic SIMD "split table" scheme (two 16-entry nibble tables per
  generator coefficient) vectorised over the byte axis, no bit
  expansion.

Geometry (k, m) is first-class (reference pins FRAGMENT_COUNT=3 i.e.
RS(2,1), /root/reference/runtime/src/lib.rs:1026-1027; BASELINE.json
targets RS(4,8)). Decode/repair matrices for a given erasure pattern
are built host-side (tiny Gauss-Jordan) and applied with the same
batched device kernels.

One program model: a lowering is a function of (matrix operands,
data), so a program compiled for one ``(q, r, n, batch)`` serves every
``(present, missing)`` of that shape. RS(10,4) has 4,004 single-loss
patterns once the repairer takes whichever ten helpers answer, and
none of them compiles anything after the shape is warm
(``TPUCodec.warm_reconstruct``). What a pattern costs is its matrix
(0.27 ms on the host at (10,4)) and the put of its operands (under
1 KiB); the codec keeps the newest ``TPUCodec.MATRICES`` of them,
operands placed, and rebuilds the rest.
"""
from __future__ import annotations

import collections
import contextlib
import functools
import math
import threading
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import trace
from ..resilience import faults
from . import gf

Strategy = str  # "gather" | "pallas"

# ---------------------------------------------------------------------------
# Table construction (host side, tiny)
# ---------------------------------------------------------------------------


def nibble_tables(mat: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Split tables for an (r x q) GF matrix.

    Returns (lo, hi), each [r, q, 16] uint8 with
    ``lo[i, j, x] = mat[i,j] * x`` and ``hi[i, j, x] = mat[i,j] * (x << 4)``
    so ``mat[i,j] * b == lo[i,j,b & 15] ^ hi[i,j,b >> 4]``.
    """
    mat = np.asarray(mat, dtype=np.uint8)
    r, q = mat.shape
    mt = gf.mul_table()
    lo = np.zeros((r, q, 16), dtype=np.uint8)
    hi = np.zeros((r, q, 16), dtype=np.uint8)
    nib = np.arange(16, dtype=np.uint8)
    for i in range(r):
        for j in range(q):
            lo[i, j] = mt[mat[i, j]][nib]
            hi[i, j] = mt[mat[i, j]][nib << 4]
    return lo, hi


# ---------------------------------------------------------------------------
# Device kernels (generic GF matrix apply, jitted per shape signature)
# ---------------------------------------------------------------------------


@jax.jit
def _apply_gather(lo: jax.Array, hi: jax.Array, data: jax.Array) -> jax.Array:
    """GF apply via nibble-table gathers.

    lo/hi: [r, q, 16] uint8 split tables; data: [..., q, n] uint8.
    Returns [..., r, n] uint8.
    """
    r, q, _ = lo.shape
    d_lo = (data & 0x0F).astype(jnp.int32)
    d_hi = (data >> 4).astype(jnp.int32)
    acc = None
    for j in range(q):
        # tables for input row j: [r, 16]; gather over the byte axis
        t_lo = jnp.take(lo[:, j], d_lo[..., j, :], axis=1)  # [r, ..., n]
        t_hi = jnp.take(hi[:, j], d_hi[..., j, :], axis=1)
        term = t_lo ^ t_hi
        acc = term if acc is None else acc ^ term
    return jnp.moveaxis(acc, 0, -2)  # [..., r, n]


@jax.jit
def _apply_pallas(bmat: jax.Array, data: jax.Array) -> jax.Array:
    """GF apply via the fused Pallas kernel (ops/rs_pallas.py): bmat is
    ``rs_pallas.operand_np`` for this batch's group."""
    from . import rs_pallas  # local import: pallas only needed on this path

    return rs_pallas.apply_operand(bmat, data)


@jax.jit
def _codeword_pallas(bmat: jax.Array, data: jax.Array) -> jax.Array:
    """``_apply_pallas`` with the data rows passed through the kernel:
    [..., q, n] -> [..., q + r, n], the rows as read and then the
    product's, written by the one call."""
    from . import rs_pallas

    return rs_pallas.apply_operand(bmat, data, passthrough=True)


# The lowerings: (matrix operands..., data) -> result, each one
# module-level jit. The matrix's VALUES are arguments, so the program
# jit compiles for one (matrix shape, data shape, placement) serves
# every matrix of that shape.
_DENSE = {"gather": _apply_gather, "pallas": _apply_pallas}


class LinearRows(NamedTuple):
    """Data ``u8[B, q, n]`` still as its ``B * q`` linear rows on the
    device: row j of request i is ``rows[i * q + j]``, a 1-D ``u8[n]``.
    A host array reaches the device fastest in this shape: the TPU
    packs four rows of the second-minor dimension into each 32-bit
    word, and a host -> device put of ``u8[1, 2, n]`` takes 8.7 ms
    where the same 16 MiB as two ``u8[n]`` take 2.5 (PERF.md section 5,
    the link probe). The codec's decode-side calls (``reconstruct`` /
    ``decode_data`` / ``fold_symbol``) take one in place of the array
    and stack it on the device, in the program that applies the matrix
    (``_apply_rows``); the fused ingest does not (``codeword_rows``)."""
    rows: tuple
    q: int

    @property
    def shape(self) -> tuple:
        return (len(self.rows) // self.q, self.q) + self.rows[0].shape


def _stack_rows(rows, q: int) -> jax.Array:
    """``rows`` (``LinearRows.rows``, traced) as ``u8[B, q, n]``.
    ``stack`` forms only: a ``reshape`` that moves bytes between
    dimensions compiles in time proportional to the array on this
    libtpu (models/pipeline.py split_rows); this compiles in under a
    second at q = 10, n = 8 MiB, and runs there in 3.2 ms (0.97 ms at
    q = 2), twice as fast as ``dynamic_update_slice`` into zeros
    (PERF.md, PR 32). Who still stacks (PR 51): the repair programs
    (``_apply_rows``), ``gather``, and a batch ``codeword_rows`` leaves."""
    return jnp.stack([jnp.stack(rows[i:i + q])
                      for i in range(0, len(rows), q)])


@functools.partial(jax.jit, static_argnames=("strategy", "q"))
def _apply_rows(operands, rows, *, strategy: Strategy, q: int):
    """The lowerings over linear rows: stack, then apply, one
    program per (strategy, matrix shape, row count, n, placement). The
    inner program is traced into this one, so the Pallas kernel keeps
    its name (``_apply_3d``) in the compiled text and in a trace."""
    return _DENSE[strategy](*operands, _stack_rows(rows, q))


# ---------------------------------------------------------------------------
# Codec front-end
# ---------------------------------------------------------------------------


class _MatrixApply:
    """A GF matrix as the operands of a chosen strategy's program."""

    def __init__(self, mat: np.ndarray, strategy: Strategy):
        self.mat = np.asarray(mat, dtype=np.uint8)
        self.strategy = strategy
        # the operands on the device, put once per (placement, kernel
        # group) and kept with the matrix
        self._placed: dict[tuple, tuple] = {}
        if strategy == "gather":
            self._host = nibble_tables(self.mat)
        elif strategy == "pallas":
            self._host = (gf.expand_bitmatrix(self.mat),)
        else:
            raise ValueError(f"unknown strategy {strategy!r}: "
                             "'gather' or 'pallas'")

    def operands(self, shape) -> tuple:
        """The matrix as the device operands of this strategy's program
        for data of ``shape``, on the device a dispatch issued now lands
        on: put there on first use (the one host -> device copy a new
        matrix costs, under 1 KiB) and kept."""
        group = 0
        if self.strategy == "pallas":
            from . import rs_pallas

            # the kernel's operand is block-diagonal over the segments
            # of one grid step, which follow from the batch
            group = rs_pallas.group_for(math.prod(shape[:-2]))
        key = (_placement_device(), group)
        placed = self._placed.get(key)
        if placed is None:
            host = self._host
            if self.strategy == "pallas":
                host = (rs_pallas.operand_np(host[0], group),)
            placed = tuple(jnp.asarray(t) for t in host)
            # under a jit trace the operands may be the trace's own:
            # never keep those
            if not any(isinstance(t, jax.core.Tracer) for t in placed):
                self._placed[key] = placed
        return placed

    def _check_rows(self, data) -> None:
        if data.shape[-2] != self.mat.shape[1]:
            raise ValueError(
                f"expected {self.mat.shape[1]} shard rows, got {data.shape[-2]}"
            )

    def __call__(self, data) -> jax.Array:
        """Apply to ``data``: ``u8[..., q, n]`` or ``LinearRows``
        (stacked inside the program)."""
        self._check_rows(data)
        if isinstance(data, LinearRows):
            return _apply_rows(self.operands(data.shape), data.rows,
                               strategy=self.strategy, q=data.q)
        return _DENSE[self.strategy](*self.operands(data.shape), data)

    def codeword(self, data: jax.Array) -> jax.Array:
        """``u8[..., q, n]`` -> ``u8[..., q + r, n]``: the data rows
        followed by the product's, a systematic encode's fragments in
        one array. Under ``pallas`` the kernel writes both (the rows
        pass through it as read, rs_pallas ``_apply_3d``), so nothing
        joins two arrays afterwards; ``gather`` concatenates."""
        if self.strategy != "pallas":
            return jnp.concatenate([data, self(data)], axis=-2)
        self._check_rows(data)
        return _codeword_pallas(*self.operands(data.shape), data)


def _placed_on(device):
    """The placement scope of ``device`` (None: whatever is active)."""
    return contextlib.nullcontext() if device is None \
        else jax.default_device(device)


def _placement_device():
    """The device a dispatch issued RIGHT NOW would land on: the
    active ``jax.default_device`` scope's device (the pool's per-lane
    placement, serve/engine.py ``_lane_placement``), or None when no
    scope is active — JAX's backend default. This is the device
    component of the key of what is kept per device, a matrix's
    placed operands: operands put under device 0's scope must never be
    handed to a program dispatched inside device 3's."""
    return jax.config.jax_default_device


def default_strategy() -> Strategy:
    """Platform -> lowering, the only selector there is: ``gather``
    on the CPU (the test mesh, the degraded fallback), ``pallas`` on
    every other backend (every cell of the benchmark, every line of
    PERF_LEDGER.jsonl; PERF.md section 5)."""
    return "gather" if jax.default_backend() == "cpu" else "pallas"


class TPUCodec:
    """Systematic RS(k, m) over GF(2^8) on the JAX device path.

    Same surface as rs_ref.ReferenceCodec (encode / encode_parity /
    reconstruct / decode_data); shards are uint8 [..., rows, n] with
    arbitrary leading batch dims — vmap is implicit via batched shapes.

    What it keeps, and how much: the newest ``MATRICES`` decode /
    repair matrices (an LRU of ``_MatrixApply``, each with its device
    operands: a matrix takes a fraction of a millisecond to rebuild).
    The programs are jit's to keep, one per (matrix shape, data shape,
    placement).

    ``strategy`` stays an argument (here, ``RegenCodec``, ``make_codec``,
    ``PipelineConfig``) because two platforms need two values: it is
    how tier-1 runs the chip's kernel in interpret mode and how
    tests/test_tpu_compile.py compiles it for the TPU from a CPU box.
    """

    MATRICES = 64

    def __init__(self, k: int, m: int, strategy: Strategy | None = None):
        if k < 1 or m < 0 or k + m > gf.FIELD:
            raise ValueError(f"invalid RS geometry k={k}, m={m}")
        self.k = k
        self.m = m
        self.strategy = strategy or default_strategy()
        self._parity_apply = _MatrixApply(gf.cauchy_parity_matrix(k, m), self.strategy)
        # (kind, present, missing) -> _MatrixApply, newest last; shared
        # by the engine's batcher, its pool lanes and warm-path callers
        self._cache: "collections.OrderedDict[tuple, _MatrixApply]" = \
            collections.OrderedDict()
        self._mu = threading.Lock()

    # -- encode -------------------------------------------------------------
    def encode_parity(self, data: jax.Array) -> jax.Array:
        """[..., k, n] uint8 -> [..., m, n] parity shards."""
        return self._parity_apply(jnp.asarray(data, dtype=jnp.uint8))

    def encode(self, data: jax.Array) -> jax.Array:
        """[..., k, n] -> [..., k+m, n] coded shards (systematic).

        Fault seam ``rs.encode`` (cess_tpu/resilience): hooks sit on
        the DEVICE codec only — the CPU ReferenceCodec stays
        injection-free, so a chaos plan failing the device path leaves
        the breaker's fallback clean."""
        faults.inject("rs.encode")
        data = jnp.asarray(data, dtype=jnp.uint8)
        if data.shape[-2] != self.k:
            raise ValueError(f"expected {self.k} data shards, got {data.shape[-2]}")
        return jnp.concatenate([data, self.encode_parity(data)], axis=-2)

    # -- decode -------------------------------------------------------------
    def _build_matrix(self, kind: str, present: tuple[int, ...],
                      missing: tuple[int, ...]) -> np.ndarray:
        if kind == "decode":
            return gf.decode_matrix(self.k, self.m, present)
        return gf.repair_matrix(self.k, self.m, present, missing)

    def _matrix_for(self, kind: str, present: tuple[int, ...],
                    missing: tuple[int, ...] = (), shape=None,
                    sink: dict | None = None) -> _MatrixApply:
        """The pattern's matrix from the LRU. One the codec does not
        hold is built now, under the stage ``repair.matrix``
        (obs.trace.stage: ``cess:repair.matrix`` in a profiler trace,
        ``[count, seconds]`` into ``sink``): the host's Gauss-Jordan
        and table expansion (``repair.matrix.build`` inside it) and,
        given the data ``shape``, the put of its device operands."""
        key = (kind, present, missing)
        with self._mu:
            apply_ = self._cache.get(key)
            if apply_ is not None:
                self._cache.move_to_end(key)
                return apply_
        with trace.stage("repair.matrix", sink):
            with trace.stage("repair.matrix.build", sink):
                apply_ = _MatrixApply(
                    self._build_matrix(kind, present, missing),
                    self.strategy)
            if shape is not None:
                apply_.operands(shape)
        with self._mu:
            apply_ = self._cache.setdefault(key, apply_)
            while len(self._cache) > self.MATRICES:
                self._cache.popitem(last=False)
        return apply_

    def _pattern(self, present, missing) -> tuple[tuple, tuple]:
        present = tuple(present)
        if missing is None:
            missing = tuple(i for i in range(self.k + self.m)
                            if i not in present)
        return present, tuple(missing)

    def _warm_program(self, pattern: tuple, shape, device) -> None:
        """Compile, once, the program that serves ``pattern``'s shape
        at ``shape`` on ``device`` (None: the current placement), and
        stage this pattern's operands there: one run of the strategy's
        jitted program over zeros. jit keeps one executable per (matrix
        shape, data shape, placement), and every later matrix of that
        shape is only its argument."""
        apply_ = self._matrix_for(*pattern)
        with _placed_on(device):
            jax.block_until_ready(apply_(jnp.zeros(shape, jnp.uint8)))

    def _apply(self, pattern: tuple, data,
               sink: dict | None = None) -> jax.Array:
        """Apply the pattern's matrix to ``data`` (an array, or
        ``LinearRows`` already on the device): the strategy's jitted
        program with the matrix as its operands."""
        if not isinstance(data, LinearRows):
            data = jnp.asarray(data, dtype=jnp.uint8)
        return self._matrix_for(*pattern, shape=data.shape, sink=sink)(data)

    def warm_reconstruct(self, present, missing=None, shape=None,
                         device=None):
        """Pre-compile + pre-stage the reconstruct program for the
        SHAPE of one erasure pattern — ``(len(present), len(missing))``
        rows — and one exact survivor shape (the restoral-market warm
        path): a later ``reconstruct`` of ANY pattern of that shape at
        that survivor shape under the same placement runs the program
        compiled here, its matrix an argument — no tracing, no compile
        in the latency budget (the benchmark's repair cells warm their
        shapes in set-up and count 0 compilations in their windows).
        The named pattern's matrix is built and its operands staged
        too.

        ``device`` pins the device the program is compiled for (the
        device-pool path warms once per lane); None warms for the
        CURRENT placement — the active jax.default_device scope, else
        the backend default. A program is bound to the placement it
        was compiled under: a ``reconstruct`` under another device's
        scope never runs an executable bound to a different chip
        (tests/test_pool.py pins the two-device case)."""
        if shape is None:
            raise ValueError("warm_reconstruct needs the exact "
                             "survivor shape, e.g. (k, fragment_size)")
        self._warm_program(
            ("repair",) + self._pattern(present, missing), shape, device)

    def reconstruct(self, survivors, present: tuple[int, ...],
                    missing: tuple[int, ...] | None = None, *,
                    sink: dict | None = None) -> jax.Array:
        """Recover missing shards from any k survivors.

        survivors: [..., k, n] rows ordered as ``present``, or the same
        as ``LinearRows``; returns
        [..., len(missing), n] (missing defaults to all absent rows).
        Compiles nothing when the exact shape has been warmed (see
        warm_reconstruct). ``sink``: an
        obs.trace.stage sink that learns of a pattern the codec held no
        matrix for (``_matrix_for``).
        """
        faults.inject("rs.reconstruct")
        return self._apply(
            ("repair",) + self._pattern(present, missing), survivors, sink)

    def decode_data(self, survivors, present: tuple[int, ...],
                    *, sink: dict | None = None) -> jax.Array:
        """Recover the k data shards from any k survivors."""
        faults.inject("rs.decode")
        return self._apply(("decode", tuple(present), ()), survivors, sink)


# ---------------------------------------------------------------------------
# ErasureCodec factory — the trait boundary of the north star
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def make_codec(k: int, m: int, backend: str = "cpu", strategy: Strategy | None = None):
    """The ``ErasureCodec`` gate: CPU path is the default, TPU opt-in.

    Mirrors the north-star design (BASELINE.json): erasure coding is
    gated behind a codec trait with the CPU reference implementation as
    default and the JAX/TPU path selectable. backend: "cpu" | "native"
    (C++ via ctypes) | "tpu"/"jax" | "regen" (regenerating-code repair
    plane, ops/regen.py) | "auto" (tpu if a TPU is present).
    strategy: the device codecs' lowering, for a test that names the
    chip's on a CPU box (see TPUCodec); None lets the platform choose.
    """
    if backend == "auto":
        backend = "tpu" if jax.default_backend() != "cpu" else "cpu"
    if backend == "cpu":
        from .rs_ref import ReferenceCodec

        return ReferenceCodec(k, m)
    if backend == "native":
        try:
            from .rs_native import NativeCodec
        except ImportError as e:
            raise NotImplementedError(
                "native (C++) ErasureCodec backend not built; run "
                "`make -C cess_tpu/native` or use backend='cpu'"
            ) from e
        return NativeCodec(k, m)
    if backend == "tpu" and jax.default_backend() == "cpu":
        # an EXPLICIT accelerator request must fail loudly, like
        # audit_backend._device_for: "jax" is the name for "wherever
        # JAX runs" (the CPU test mesh included)
        raise RuntimeError(
            "ErasureCodec 'tpu' requested but no accelerator is "
            "present; use 'jax', 'cpu' or 'auto'")
    if backend in ("tpu", "jax"):
        return TPUCodec(k, m, strategy=strategy)
    if backend == "regen":
        # regenerating-code repair plane (ops/regen.py); imported lazily
        # because regen builds on this module
        from .regen import RegenCodec

        return RegenCodec(k, m, strategy=strategy)
    raise ValueError(f"unknown ErasureCodec backend {backend!r}")


# ---------------------------------------------------------------------------
# The rows entry (PR 51): a systematic encode over linear rows
# ---------------------------------------------------------------------------
# Appended below everything else on purpose: the persistent compile
# cache's key holds source locations, and the lines above keep theirs.


@functools.partial(jax.jit, static_argnames=("q",))
def _codeword_rows_pallas(bmat: jax.Array, rows, *, q: int) -> jax.Array:
    """The B * q linear rows of a batch -> its codeword, fragment-major
    ``u8[q + r, B, n]``, through the kernel's rows entry
    (rs_pallas.apply_rows_operand): no ``_stack_rows`` in front, no
    ``u8[B, q, n]`` array at all."""
    from . import rs_pallas

    return rs_pallas.apply_rows_operand(bmat, rows, q)


def rows_direct(parity: _MatrixApply, batch: int, n: int) -> bool:
    """Whether :func:`codeword_rows` hands ``batch * q`` linear rows of
    ``n`` bytes to the kernel as they lie: the chip's lowering, and a
    batch the rows entry takes (rs_pallas.rows_tile: by shape, 8 or 16
    segments, rows of whole column tiles)."""
    if parity.strategy != "pallas":
        return False
    from . import rs_pallas

    return bool(rs_pallas.rows_tile(batch, n))


def codeword_rows(parity: _MatrixApply, rows, q: int) -> jax.Array:
    """``_MatrixApply.codeword`` for a caller that holds the batch as
    its ``B * q`` linear rows ``u8[n]`` (``LinearRows.rows``, traced:
    the fused ingest step, models/pipeline.py): ``u8[B, q + r, n]``,
    the data rows followed by the product's.

    Under ``pallas`` the kernel reads the rows as they were put and
    writes the codeword fragment-major, ``u8[q + r, B, n]``, the layout
    the chip keeps a ``u8[B, q + r, n]`` batch in anyway (B, a multiple
    of 8, is the dimension it tiles): the ``swapaxes`` here moves no
    byte. A batch the rows entry does not take (``rows_direct``: other
    than 8 or 16 segments, rows of no whole column tile) and the
    ``gather`` lowering stack the rows and go the array's way."""
    rows = tuple(rows)
    b, n = len(rows) // q, rows[0].shape[0]
    if not rows_direct(parity, b, n):
        return parity.codeword(_stack_rows(rows, q))
    return jnp.swapaxes(
        _codeword_rows_pallas(*parity.operands((b, q, n)), rows, q=q),
        0, 1)
