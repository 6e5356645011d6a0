"""Bit-sliced executor for compiled XOR schedules (cess_tpu/ops/xor_sched).

Instead of materialising 0/1 bit-planes (8x expansion) or riding the
MXU (rs_pallas.py), this path keeps the data packed: 4 data bytes of
a row (one from each quarter of the byte axis, see ``_pack_u32``)
share one uint32 lane, and bit-plane b of byte row j is
``(row_u32 >> b) & 0x01010101`` — the information bit of every
byte sits at bit position 0 of its byte lane, so every schedule op is
one full-lane uint32 XOR over the column tile, covering 4 data bytes
per lane. Unpack is a shift+mask per touched input plane, pack is a
shift+or per output plane; byte order round-trips exactly because no
op ever mixes bit positions across byte lanes, so WHICH four bytes
share a word is free as long as unpacking inverts packing.

Two executors run the SAME schedule, bit-identical to
rs.py::_apply_bitmatrix by construction (both compute the same GF(2)
linear map exactly — pinned in tests/test_xor_sched.py):

- a Pallas TPU kernel: grid over (batch row, column tile), input and
  output tiles plus the schedule's liveness-allocated scratch slots
  in VMEM, every op a full-lane VPU uint32 instruction;
- a pure-jnp fallback executing the same op list wherever the kernel
  would only be interpreted (the CPU test mesh default).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import target
from .xor_sched import OP_ACC, OP_COPY, OP_XOR, XorSchedule

DEFAULT_TILE_LANES = 8192          # uint32 lanes per column tile
_MASK = 0x01010101                 # bit 0 of each packed byte


def _run_ops(sched: XorSchedule, read_input, zeros):
    """Trace the schedule once in SSA form: ``read_input(plane)``
    yields an input bit-plane lane vector, ``zeros()`` a zero vector.
    Returns (scratch_writes, out_planes): the ordered scratch-slot
    write list the Pallas kernel replays into VMEM, and the r8 output
    plane values. The jnp fallback ignores scratch_writes — its slots
    live as SSA values keyed by the same addresses."""
    q8, ob = sched.q8, sched.out_base
    vals: dict[int, jax.Array] = {}
    scratch_writes: list[tuple[int, jax.Array]] = []

    def get(i):
        if i not in vals:
            if i >= q8:
                raise AssertionError(f"read before write at {i}")
            vals[i] = read_input(i)
        return vals[i]

    for op, d, a, b in sched.ops:
        if op == OP_XOR:
            v = get(a) ^ get(b)
        elif op == OP_ACC:
            v = get(d) ^ get(a)
        elif op == OP_COPY:
            v = get(a)
        else:
            v = zeros()
        vals[d] = v
        if q8 <= d < ob:
            scratch_writes.append((d - q8, v))
    return scratch_writes, [vals[ob + i] for i in range(sched.r8)]


def _pack_rows(sched: XorSchedule, out_planes):
    """Fold the r8 output bit-planes back into r packed byte rows."""
    rows = []
    for i in range(sched.r8 // 8):
        word = out_planes[8 * i]
        for a in range(1, 8):
            word = word | (out_planes[8 * i + a] << a)
        rows.append(word)
    return rows


@functools.partial(jax.jit, static_argnums=(0,))
def _apply_jnp(sched: XorSchedule, u32: jax.Array) -> jax.Array:
    """u32 [B, q, n4] -> [B, r, n4]; the pure-jnp schedule executor."""
    mask = jnp.uint32(_MASK)

    def read_input(plane):
        j, b = divmod(plane, 8)
        return (u32[:, j, :] >> b) & mask

    _, out_planes = _run_ops(sched, read_input,
                             lambda: jnp.zeros_like(u32[:, 0, :]))
    return jnp.stack(_pack_rows(sched, out_planes), axis=1)


def _make_kernel(sched: XorSchedule, tile_lanes: int):
    def kernel(in_ref, out_ref, scratch_ref):
        mask = jnp.uint32(_MASK)

        def read_input(plane):
            j, b = divmod(plane, 8)
            return (in_ref[0, j, :] >> b) & mask

        scratch_writes, out_planes = _run_ops(
            sched, read_input,
            lambda: jnp.zeros((tile_lanes,), jnp.uint32))
        # replay the liveness-allocated slot writes into VMEM: the
        # scratch high-water mark bounds live intermediates per tile
        for slot, v in scratch_writes:
            scratch_ref[slot, :] = v
        for i, word in enumerate(_pack_rows(sched, out_planes)):
            out_ref[0, i, :] = word

    return kernel


@functools.partial(jax.jit, static_argnums=(0, 1))
def _apply_pallas(sched: XorSchedule, tile_lanes: int,
                  u32: jax.Array) -> jax.Array:
    """u32 [B, q, n4] -> [B, r, n4] through the bit-sliced VPU kernel."""
    b, q, n4 = u32.shape
    r = sched.r8 // 8
    grid = (b, n4 // tile_lanes)
    return pl.pallas_call(
        _make_kernel(sched, tile_lanes),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, q, tile_lanes), lambda i, t: (i, 0, t),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, r, tile_lanes),
                               lambda i, t: (i, 0, t),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((b, r, n4), jnp.uint32,
                                       vma=jax.typeof(u32).vma),
        scratch_shapes=[
            pltpu.VMEM((sched.n_scratch, tile_lanes), jnp.uint32),
        ],
        interpret=target.interpret(),
    )(u32)


def _pack_u32(data: jax.Array) -> jax.Array:
    """[..., n] uint8 (n % 4 == 0) -> [..., n/4] uint32: byte lane j
    of word i is byte ``i + j*n/4``. A quarter-strided packing instead
    of a ``[..., n/4, 4]`` bitcast view: a minor dimension of 4 is
    padded to 128 by the TPU's tiled layout (32x the batch in HBM)."""
    n4 = data.shape[-1] // 4
    word = data[..., :n4].astype(jnp.uint32)
    for j in range(1, 4):
        word = word | (data[..., j * n4:(j + 1) * n4].astype(jnp.uint32)
                       << (8 * j))
    return word


def _unpack_u32(word: jax.Array) -> jax.Array:
    """Inverse of :func:`_pack_u32`: [..., n4] uint32 -> [..., 4*n4]."""
    return jnp.concatenate(
        [((word >> (8 * j)) & 0xFF).astype(jnp.uint8) for j in range(4)],
        axis=-1)


def apply_schedule(sched: XorSchedule, data: jax.Array,
                   tile_lanes: int = DEFAULT_TILE_LANES,
                   force: str | None = None) -> jax.Array:
    """Apply a compiled schedule to [..., q, n] uint8 data.

    Returns [..., r, n] uint8. ``force`` pins the executor ("pallas" |
    "jnp"); default is the Pallas kernel where it compiles for the TPU
    and the jnp fallback where it would be interpreted. n is padded to
    the lane/tile multiple
    (zero columns produce zero outputs — harmless, stripped)."""
    q, r = sched.q8 // 8, sched.r8 // 8
    data = jnp.asarray(data, dtype=jnp.uint8)
    *lead, q_in, n = data.shape
    if q_in != q:
        raise ValueError(f"data rows {q_in} != schedule inputs {q}")
    use_pallas = force == "pallas" or (
        force is None and not target.interpret())
    step = 4 * tile_lanes if use_pallas else 4
    pad = (-n) % step
    if pad:
        data = jnp.pad(data, [(0, 0)] * len(lead) + [(0, 0), (0, pad)])
    n_pad = n + pad
    u32 = _pack_u32(data.reshape(-1, q, n_pad))           # [B, q, n4]
    if use_pallas:
        out32 = _apply_pallas(sched, tile_lanes, u32)
    else:
        out32 = _apply_jnp(sched, u32)
    out = _unpack_u32(out32).reshape(*lead, r, n_pad)
    if pad:
        out = out[..., :n]
    return out
