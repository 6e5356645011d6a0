"""Pallas-fused GF(2^8) matrix apply for the RS codec (TPU).

The pure-XLA bitmatrix path (cess_tpu/ops/rs.py:_apply_bitmatrix)
materialises the 8x bit-plane expansion and the f32 matmul output in
HBM — ~5.8 GiB/s on v5e. This kernel fuses the whole chain
(unpack bits -> MXU matmul -> parity (&1) -> pack bytes) inside VMEM,
tiled along the byte axis, so HBM traffic is just the uint8 input and
output rows.

Round-4 probe findings (v5e, 2 GiB resident batches; taken on an
earlier stack and not re-measured on this code — see PERF.md):
- throughput was FLAT across group {1,2,4,8} x tile {8k..128k} x
  subtile interleave {1,2,4}: the kernel is NOT MXU-slot-bound, so
  kron-segment-grouping buys nothing (levers kept as tuning knobs);
- the VPU byte-PACK (bit-parity -> weighted sublane reduction) cost
  ~45% of runtime: skipping it measured 42.2 GiB/s vs 23.4 full;
- hence ``mxupack``: the pack is a SECOND small int8 matmul — packed
  byte = sum_b w_b * parity_b with w = [1,2,4,8,16,32,64,-128] (the
  -128 exploits two's-complement wraparound of the uint8 cast), so
  the sublane reduction rides the idle MXU instead of the VPU;
- iota-broadcast bit ops are the fast VPU lowering: jnp.stack of 8
  strided slices forces sublane relayouts, measured ~3x slower.

Layout contract: data [..., q, n] uint8 is viewed as [B, q, n] (segment
rows are contiguous); the grid walks (segment-group, column-tile) and
each step applies the (8rg x 8qg) GF(2) block-diagonal bit-matrix
``kron(I_group, expand_bitmatrix(mat))`` to one (g x q x TILE_N) tile
and writes the (g x r x TILE_N) product: [B, q, n] -> [B, r, n].

With the static ``passthrough`` (PR 44; the fused ingest step over an
ARRAY, ops/rs.py ``codeword``) a step's output tile is (g x (q + r) x
TILE_N): the input tile as read, then the product's r rows: the
codeword [B, q + r, n] out of the one call. Without it the kernel body,
grid and block specs are the ones above: every other caller's program
(the codec's encode / decode, the repair and restoral classes) is what
it was. The ROWS entry (PR 51, at the end of this file; the fused step
over the stream's linear rows, ops/rs.py ``codeword_rows``) has the
other contract: in, the batch's B * q rows u8[n] as B * q operands, each
read as [n / 128, 128]; out, the codeword FRAGMENT-MAJOR [q + r, B, n].
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import target

DEFAULT_TILE_N = 32768
DEFAULT_GROUP = 2      # v5e probe: mxupack g=2/32k 51.9 GiB/s, the peak
DEFAULT_SUBTILES = 1
PACK_W = (1, 2, 4, 8, 16, 32, 64, -128)   # int8-safe byte weights
# The kernel's name on the device: its events in a profiler trace are
# "%_apply_3d.<n> = ..." custom calls, which is what the benchmark's
# rs_kernel_roofline.* readers match. Pinned here (and in
# tests/test_kernel_names.py) so that renaming the jitted wrapper
# cannot silently empty them.
KERNEL_NAME = "_apply_3d"


def _make_kernel(q: int, r: int, g: int, tile_n: int, subtiles: int,
                 acc_dtype, mxu_pack: bool, passthrough: bool = False):
    op_dtype = jnp.bfloat16 if acc_dtype == jnp.float32 else jnp.int8
    ts = tile_n // subtiles

    # the product's rows of a step's output tile
    out_rows = slice(q, q + r) if passthrough else slice(None)

    def kernel(bmat_ref, pack_ref, data_ref, out_ref):
        if passthrough:
            # the systematic rows: the step's input tile, stored as read
            out_ref[:, :q, :] = data_ref[...]
        for s in range(subtiles):
            sl = slice(s * ts, (s + 1) * ts)
            data = data_ref[:, :, sl].astype(jnp.int32)      # [g, q, ts]
            # unpack bit-planes: contraction row g_i*8q + 8j + b
            shifts = jax.lax.broadcasted_iota(jnp.int32, (1, 1, 8, 1), 2)
            bits = (data[:, :, None, :] >> shifts) & 1       # [g, q, 8, ts]
            bits = bits.reshape(8 * q * g, ts).astype(op_dtype)
            prod = jnp.dot(bmat_ref[:], bits,
                           preferred_element_type=acc_dtype)
            if mxu_pack:
                y = (prod.astype(jnp.int32) & 1).astype(jnp.int8)
                packed = jnp.dot(pack_ref[:], y,
                                 preferred_element_type=jnp.int32)
                out_ref[:, out_rows, sl] = packed.reshape(
                    g, r, ts).astype(jnp.uint8)
            else:
                obits = prod.astype(jnp.int32) & 1           # parity == XOR
                obits = obits.reshape(g, r, 8, ts)
                weights = jax.lax.broadcasted_iota(
                    jnp.int32, (1, 1, 8, 1), 2)
                packed = jnp.sum(obits << weights, axis=2)   # [g, r, ts]
                out_ref[:, out_rows, sl] = packed.astype(jnp.uint8)

    return kernel


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6, 7, 9, 10))
def _apply_3d(bmat: jax.Array, packmat: jax.Array, q: int, r: int, g: int,
              tile_n: int, subtiles: int, use_int8: bool,
              data3d: jax.Array, mxu_pack: bool,
              passthrough: bool = False) -> jax.Array:
    """bmat [8rg, 8qg] block-diag; data3d [B, q, n] -> [B, r, n], or
    with ``passthrough`` [B, q + r, n]: the input rows, then the
    product's."""
    b, _, n = data3d.shape
    acc_dtype = jnp.int32 if use_int8 else jnp.float32
    kernel = _make_kernel(q, r, g, tile_n, subtiles, acc_dtype, mxu_pack,
                          passthrough)
    grid = (b // g, n // tile_n)
    rows = q + r if passthrough else r
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((8 * r * g, 8 * q * g), lambda i, t: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((r * g, 8 * r * g), lambda i, t: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((g, q, tile_n), lambda i, t: (i, 0, t),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((g, rows, tile_n), lambda i, t: (i, 0, t),
                               memory_space=pltpu.VMEM),
        # vma: inside shard_map (parallel/mesh.py) the output varies
        # over the same mesh axes as the data shard it was made from
        out_shape=jax.ShapeDtypeStruct((b, rows, n), jnp.uint8,
                                       vma=jax.typeof(data3d).vma),
        interpret=target.interpret(),
        name=KERNEL_NAME,
    )(bmat, packmat, data3d)


@functools.lru_cache(maxsize=64)
def _pack_np(r: int, g: int) -> np.ndarray:
    # cache NUMPY only: a jnp array created inside a jit trace would be
    # a tracer, and caching a tracer leaks it across traces.
    # pack matrix [rg, 8rg]: row i selects its 8 bit-rows with weights
    return np.kron(np.eye(r * g, dtype=np.int8),
                   np.asarray(PACK_W, dtype=np.int8)[None, :])


def group_for(batch: int, group: int = DEFAULT_GROUP) -> int:
    """Segments per grid step for a flattened batch of ``batch``:
    ``group`` degraded to its largest power-of-two divisor of it."""
    g = group
    while batch % g:
        g //= 2
    return g


def operand_np(bmat_np: np.ndarray, g: int,
               use_int8: bool = True) -> np.ndarray:
    """The kernel's matrix operand on the host: the expanded (8r x 8q)
    bit-matrix as the block-diagonal ``kron(I_g, bmat)`` the grid step
    applies to ``g`` segments, in the MXU operand's dtype. The one part
    of a call that depends on the matrix's VALUES: everything else in
    ``apply_operand`` follows from shapes, so a program compiled for
    one ``(q, r, n, batch)`` serves every matrix of that shape."""
    big = np.kron(np.eye(g, dtype=np.uint8), bmat_np.astype(np.uint8))
    return big.astype(np.int8) if use_int8 else big.astype(np.float32)


def apply_operand(bmat: jax.Array, data: jax.Array,
                  tile_n: int = DEFAULT_TILE_N, use_int8: bool = True,
                  group: int = DEFAULT_GROUP,
                  subtiles: int = DEFAULT_SUBTILES,
                  mxu_pack: bool = True,
                  passthrough: bool = False) -> jax.Array:
    """Apply the matrix operand ``bmat`` (``operand_np`` for this
    batch's group, on the device or traced) to [..., q, n] uint8 data.

    Returns [..., r, n] uint8, or with ``passthrough`` [..., q + r, n]:
    the data rows as read, then the product's (the module note). n is
    padded to a multiple of tile_n if needed (zero columns encode to
    zero parity — harmless, stripped).
    """
    data = jnp.asarray(data, dtype=jnp.uint8)
    *lead, q, n = data.shape
    pad = (-n) % tile_n
    if pad:
        data = jnp.pad(data, [(0, 0)] * len(lead) + [(0, 0), (0, pad)])
    flat = data.reshape(-1, q, data.shape[-1])  # [B, q, n_pad]
    g = group_for(flat.shape[0], group)
    r = bmat.shape[0] // (8 * g)
    assert bmat.shape == (8 * r * g, 8 * q * g), \
        f"matrix operand {bmat.shape} for {g} x {q} data rows"
    sub = subtiles
    while tile_n % sub:
        sub //= 2
    out = _apply_3d(bmat, jnp.asarray(_pack_np(r, g)), q, r, g, tile_n,
                    sub, use_int8, flat, mxu_pack, passthrough)
    out = out.reshape(*lead, out.shape[-2], data.shape[-1])
    if pad:
        out = out[..., :n]
    return out



# ---------------------------------------------------------------------------
# The rows entry (PR 51): linear rows in, the codeword fragment-major out
# ---------------------------------------------------------------------------

# Columns of a batch of 8 the rows entry takes a step. A step holds
# every segment of its column tile (the batch is the result's
# second-minor dimension), so the tile is a quarter of DEFAULT_TILE_N:
# at 16384 and 8 segments a step the kernel does not fit VMEM at
# RS(4,8) or RS(10,4) (described-v5e compiles, PR 51), and on the chip
# 4096 / 8192 / 16384 ran within 4% of each other where they fit.
ROWS_TILE_N = 8192
LANES = 128     # a linear row on the chip is a [n / 128, 128] array


def rows_tile(batch: int, n: int) -> int:
    """The rows entry's column tile for ``batch`` segments of ``n``-byte
    rows, or 0 where the entry does not take the batch and its caller
    stacks instead (ops/rs.py ``codeword_rows``). By shape alone: the
    batch is the second-minor dimension of the ``u8[q + r, B, n]``
    result, which the chip tiles in eights, and a step holds all of it,
    so 8 or 16 segments (16 at half the tile); a row is read as
    ``[n / 128, 128]`` in blocks of whole column tiles."""
    if batch not in (8, 16):
        return 0
    tile = min(n, ROWS_TILE_N * 8 // batch)
    return tile if n % tile == 0 and tile % LANES == 0 else 0


@functools.lru_cache(maxsize=16)
def _rows_out_np(q: int, r: int, g: int, b: int):
    """The rows entry's two packing matrices, NUMPY only (as _pack_np):
    int8 ``pack [b / g, r * b, 8rg]`` and ``keep [b / g, q * b, 8qg]``.
    Group G's holds ``PACK_W`` where a bit-row of its segments' product
    (``pack``) or of their data (``keep``) goes into byte-row
    ``row * b + segment`` of the step's output: the MXU packs the bits
    to bytes, as in ``_pack_np``, and in the same pass puts every
    segment's row where the fragment-major block wants it, so no
    sublane of the result is moved afterwards."""
    w = np.asarray(PACK_W, dtype=np.int8)
    pack = np.zeros((b // g, r * b, 8 * r * g), dtype=np.int8)
    keep = np.zeros((b // g, q * b, 8 * q * g), dtype=np.int8)
    for grp in range(b // g):
        for gi in range(g):
            seg = grp * g + gi
            for i in range(r):
                at = 8 * (gi * r + i)
                pack[grp, i * b + seg, at:at + 8] = w
            for j in range(q):
                at = 8 * (gi * q + j)
                keep[grp, j * b + seg, at:at + 8] = w
    return pack, keep


def _make_rows_kernel(q: int, r: int, g: int, b: int, ts: int):
    def kernel(bmat_ref, pack_ref, keep_ref, *refs):
        *row_refs, out_ref = refs
        shifts = jax.lax.broadcasted_iota(jnp.int32, (1, 8, 1), 1)
        parity = data_rows = None
        for grp in range(b // g):
            # the group's g * q row blocks [ts / 128, 128], unpacked,
            # then flattened to lanes in VMEM: an int32 word holds one
            # byte, so the flatten moves whole words (the byte shuffle
            # _stack_rows pays in HBM is the unpack the kernel does
            # anyway)
            blocks = jnp.stack([ref[...] for ref in
                                row_refs[grp * g * q:(grp + 1) * g * q]])
            data = blocks.astype(jnp.int32).reshape(g * q, ts)
            # from here _make_kernel's arithmetic: bit-planes ->
            # int8 MXU product -> & 1 -> the packing matmul
            bits = (data[:, None, :] >> shifts) & 1          # [gq, 8, ts]
            bits = bits.reshape(8 * q * g, ts).astype(jnp.int8)
            prod = jnp.dot(bmat_ref[:], bits,
                           preferred_element_type=jnp.int32)
            y = (prod & 1).astype(jnp.int8)
            part = jnp.dot(pack_ref[grp], y,
                           preferred_element_type=jnp.int32)
            parity = part if parity is None else parity + part
            # the systematic rows: the same bits packed back to bytes
            part = jnp.dot(keep_ref[grp], bits,
                           preferred_element_type=jnp.int32)
            data_rows = part if data_rows is None else data_rows + part
        out_ref[:q] = data_rows.reshape(q, b, ts).astype(jnp.uint8)
        out_ref[q:] = parity.reshape(r, b, ts).astype(jnp.uint8)

    return kernel


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _apply_rows_3d(bmat: jax.Array, q: int, r: int, g: int, ts: int,
                   rows: tuple) -> jax.Array:
    """bmat [8rg, 8qg] block-diag; ``rows`` the B * q linear rows
    u8[n] of a batch (row j of segment i at i * q + j) -> the codeword
    u8[q + r, B, n], fragment-major: the input rows, then the
    product's."""
    n = rows[0].shape[0]
    b = len(rows) // q
    pack, keep = (jnp.asarray(t) for t in _rows_out_np(q, r, g, b))

    def whole(t):
        return pl.BlockSpec(t.shape, lambda c: (0,) * t.ndim,
                            memory_space=pltpu.VMEM)

    row_spec = pl.BlockSpec((ts // LANES, LANES), lambda c: (c, 0),
                            memory_space=pltpu.VMEM)
    return pl.pallas_call(
        _make_rows_kernel(q, r, g, b, ts),
        grid=(n // ts,),
        in_specs=[whole(bmat), whole(pack), whole(keep)]
        + [row_spec] * len(rows),
        out_specs=pl.BlockSpec((q + r, b, ts), lambda c: (0, 0, c),
                               memory_space=pltpu.VMEM),
        # vma: as _apply_3d's, the codeword varies as the rows do
        out_shape=jax.ShapeDtypeStruct((q + r, b, n), jnp.uint8,
                                       vma=jax.typeof(rows[0]).vma),
        interpret=target.interpret(),
        name=KERNEL_NAME,
    )(bmat, pack, keep,
      *[row.reshape(n // LANES, LANES) for row in rows])


def apply_rows_operand(bmat: jax.Array, rows, q: int) -> jax.Array:
    """The rows entry: the matrix operand ``bmat`` (``operand_np`` for
    this batch's group) applied to a batch still as its ``B * q`` linear
    rows ``u8[n]``, each taken as ``u8[n / 128, 128]`` (a bitcast on the
    chip). Returns the codeword ``u8[q + r, B, n]``, fragment-major.
    Only for a batch ``rows_tile`` takes."""
    n = rows[0].shape[0]
    b = len(rows) // q
    ts = rows_tile(b, n)
    assert ts, f"the rows entry does not take {b} x u8[{n}]"
    g = group_for(b)
    r = bmat.shape[0] // (8 * g)
    assert bmat.shape == (8 * r * g, 8 * q * g), \
        f"matrix operand {bmat.shape} for {g} x {q} data rows"
    return _apply_rows_3d(bmat, q, r, g, ts, tuple(rows))
