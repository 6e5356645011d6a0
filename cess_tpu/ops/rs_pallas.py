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

With the static ``passthrough`` (PR 44; only the fused ingest step
passes it, models/pipeline.py fused_step through ops/rs.py
``codeword``) a step's output tile is (g x (q + r) x TILE_N): its first
q rows are the step's input tile, stored as read (a VMEM copy, no MXU
work), the product's r rows follow — a systematic encode's codeword
[B, q + r, n] out of the one call, so no ``concatenate`` joins data and
parity behind it. Without it the kernel body, the grid and the block
specs are exactly the ones above: every other caller's program (the
codec's encode and decode, the repair and restoral classes) is what it
was.
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

