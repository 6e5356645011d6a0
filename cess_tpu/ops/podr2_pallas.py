"""Pallas-fused PoDR2 tag generation (TPU).

The pure-XLA tag path (podr2.tag_from_elems) materialises the packed
field elements [F, blocks, sectors] u32 (2x the fragment bytes) plus
the partial-product reduction traffic in HBM. This kernel reads the
RAW fragment bytes once and produces tags — nothing else touches HBM.

The trick that removes byte-unpacking entirely: the MAC is linear, so
    sum_j m_j * alpha_j
      = sum_j (b_{2j} + 256 b_{2j+1}) * alpha_j
      = sum_i b_i * W_i          with  W_{2j}   = alpha_j
                                       W_{2j+1} = 256 * alpha_j mod p
— an INTERLEAVED field-weight vector over the natural byte lanes. W is
split into 16-bit limbs (w0, w1) host-side; every in-kernel partial
product b_i * w ( < 2^8 * 2^16 = 2^24 ) accumulates exactly in 32-bit
lanes over <= 256-term chunks (256 * 255 * 65535 < 2^32), with one
modular fold per chunk per output element. Measured on v5e (r05):
~7.3k frags/s for 8 MiB fragments at limbs=2 (block tile 128) — vs
~3.1k for a u16 bitcast variant and ~1.9k for the jnp path — because
the kernel's HBM traffic is exactly one pass over the u8 input.

Mosaic constraints shaping this design: no unsigned reductions (sums
run in int32 and bitcast back — bit-exact below 2^32), no in-kernel
bitwidth-changing bitcasts, and strided u8 gathers ICE the compiler —
the interleaved weights avoid all three.

Layout contract:
- data [F, blocks, 2*sectors] uint8 (a reshape of the fragment bytes,
  [F, bytes] or still in their batch's shape [B, rows, bytes]:
  ``tag_fragments_fused``);
- w0/w1 [limbs, 2*sectors] int32: the 16-bit limbs of W per MAC limb;
- prf   [F, limbs, blocks] uint32 (limb-major: block axis on lanes);
- out   [F, limbs, blocks] uint32, transposed by the caller to the
  protocol's [F, blocks, limbs].

Interpret mode runs the identical kernel on the CPU test mesh; tests
pin it byte-equal to the jnp path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pfield as pf
from . import target

# v5e interleaved A/B sweep (r05): tile 128 runs ~7.3k frags/s vs
# ~6.2k at 256 and ~6.4k at 512-1024 (8 MiB fragments, 128-resident)
DEFAULT_BLOCK_TILE = 128
_CHUNK = 256        # max exactly-accumulable terms per 32-bit sum


def _kernel(limbs: int, lanes: int):
    chunk = min(_CHUNK, lanes)

    def kernel(w0_ref, w1_ref, f_ref, d_ref, out_ref):
        d = d_ref[0].astype(jnp.int32)             # [bt, lanes]

        def fold(t):
            """Exact 32-bit chunk sums -> one field element [bt]."""
            acc = None
            for lo in range(0, lanes, chunk):
                s = jax.lax.bitcast_convert_type(
                    jnp.sum(t[:, lo:lo + chunk], axis=1,
                            dtype=jnp.int32), jnp.uint32)
                s = pf.to_field(s)
                acc = s if acc is None else pf.addmod(acc, s)
            return acc

        for limb in range(limbs):
            acc0 = fold(d * w0_ref[limb][None, :])
            acc1 = fold(d * w1_ref[limb][None, :])
            out_ref[0, limb] = pf.addmod(
                f_ref[0, limb], pf.addmod(acc0, pf.rotk(acc1, 16)))

    return kernel


# The kernel's name on the device: "%_tags_3d.<n> = ..." custom calls
# in a profiler trace, matched by the benchmark's
# tag_kernel_roofline.ingest reader (pinned as in ops/rs_pallas.py).
KERNEL_NAME = "_tags_3d"


@functools.partial(jax.jit, static_argnums=(4, 5, 6),
                   donate_argnums=(2,))
def _tags_3d(w0: jax.Array, w1: jax.Array, prf: jax.Array,
             data: jax.Array, limbs: int, lanes: int,
             block_tile: int) -> jax.Array:
    """data [F, blocks, lanes] u8 + prf [F, limbs, blocks] ->
    [F, limbs, blocks] tags.

    prf is DONATED: the caller's limb-major transpose is fresh per
    call (tag_fragments_fused builds it with moveaxis) and exactly
    matches the output shape/dtype, so XLA can write the tags into
    the PRF buffer instead of allocating a second [F, limbs, blocks]
    u32 array — on an 8 MiB x 128-fragment batch that is ~16 MiB of
    HBM per limb that never has to coexist. data is NOT donated: it
    is a reshape VIEW of the caller's fragment buffer, which the
    fused pipeline forward returns to its caller."""
    fcount, blocks, _ = data.shape
    return pl.pallas_call(
        _kernel(limbs, lanes),
        grid=(fcount, blocks // block_tile),
        in_specs=[
            pl.BlockSpec((limbs, lanes), lambda i, t: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((limbs, lanes), lambda i, t: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, limbs, block_tile), lambda i, t: (i, 0, t),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_tile, lanes), lambda i, t: (i, t, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, limbs, block_tile),
                               lambda i, t: (i, 0, t),
                               memory_space=pltpu.VMEM),
        # vma: inside shard_map (parallel/mesh.py) the tags vary over
        # the same mesh axes as the fragment bytes they were made from
        out_shape=jax.ShapeDtypeStruct((fcount, limbs, blocks),
                                       jnp.uint32,
                                       vma=jax.typeof(data).vma),
        interpret=target.interpret(),
        name=KERNEL_NAME,
    )(w0, w1, prf, data)


def supported(sectors: int, blocks: int) -> bool:
    """The fused path's shape envelope; callers fall back to the jnp
    path outside it (protocol results are identical either way).
    Deliberately narrow: sectors == 256 (the protocol geometry,
    512 byte lanes) is the only shape validated through the real
    Mosaic toolchain (tests/test_tpu_compile.py compiles it for a
    described v5e) — the compiler refuses patterns that interpret
    mode happily runs, so an interpret-green shape is NOT evidence
    the TPU path works (review-caught when a vacuous bound replaced
    the alignment gate).

    The block gate tracks DEFAULT_BLOCK_TILE: blocks must either fit
    in one tile or divide it evenly. Retuning the tile (256 -> 128,
    r05) therefore SHIFTS the envelope — e.g. blocks=192 now takes the
    jnp fallback, blocks=384 now fuses — which is intended: every
    admitted shape is the same kernel with a different grid count, and
    tests/test_podr2.py pins the membership."""
    return (sectors == 256
            and blocks % min(blocks, DEFAULT_BLOCK_TILE) == 0)


@functools.lru_cache(maxsize=16)
def _weight_limbs(alpha_key) -> tuple[np.ndarray, np.ndarray]:
    """(w0, w1) int32 [limbs, 2*sectors] from alpha bytes (cached on
    the raw key material — numpy only, never tracers)."""
    sectors, limbs, raw = alpha_key
    alpha = np.frombuffer(raw, dtype=np.uint32).reshape(
        sectors, limbs).astype(np.uint64)
    w = np.empty((limbs, 2 * sectors), dtype=np.uint64)
    w[:, 0::2] = alpha.T
    w[:, 1::2] = (alpha.T * 256) % pf.P
    return ((w & 0xFFFF).astype(np.int32), (w >> 16).astype(np.int32))


def weight_limbs(alpha) -> tuple[np.ndarray, np.ndarray]:
    """The kernel's weights (w0, w1) for a CONCRETE alpha [sectors,
    limbs]: host arithmetic on the key's own words, so a caller whose
    alpha is traced (podr2.TAG_PROGRAM) makes them once a key and hands
    them in as operands."""
    alpha_np = np.asarray(jax.device_get(alpha), dtype=np.uint32)
    return _weight_limbs(alpha_np.shape + (alpha_np.tobytes(),))


def tag_fragments_fused(weights, prf: jax.Array,
                        fragments: jax.Array) -> jax.Array:
    """fragments [F, bytes] uint8, prf [F, blocks, limbs] ->
    tags [F, blocks, limbs] (the tag_from_elems contract, fused).
    ``weights``: ``weight_limbs(alpha)``, host arrays or traced.

    The fragments may keep their batch's shape, [B, rows, bytes] (F =
    B * rows, row-major: models/pipeline.py fused_step): the kernel's
    view is then taken by splitting the MINOR dimension first,
    [B, rows, blocks, lanes], the one relayout of the bytes (a uint8
    array is tiled over its last two dimensions), and the two leading
    dimensions, no longer tiled, are swapped and merged at no cost:
    the TPU keeps a ``u8[B, rows, bytes]`` batch with B second-minor
    (rows = k + m is no multiple of the 8-row tile, a batch of 8 is),
    so the kernel walks the fragments row-major in THAT order and only
    the PRF values and the tags, 1/64 of the bytes, are reordered.
    No ``[B * rows, bytes]`` copy is made in front."""
    *lead, nbytes = fragments.shape
    w0, w1 = weights
    limbs, lanes = w0.shape
    blocks = nbytes // lanes
    tile = min(blocks, DEFAULT_BLOCK_TILE)
    prf = jnp.moveaxis(prf, -1, 1)                  # [F, limbs, blocks]
    view = fragments.reshape(*lead, blocks, lanes)
    if len(lead) == 2:
        b, rows = lead
        view = jnp.swapaxes(view, 0, 1).reshape(rows * b, blocks, lanes)
        prf = jnp.swapaxes(prf.reshape(b, rows, limbs, blocks), 0, 1)
        prf = prf.reshape(rows * b, limbs, blocks)
    out = _tags_3d(jnp.asarray(w0), jnp.asarray(w1), prf, view,
                   limbs, lanes, tile)
    if len(lead) == 2:
        out = jnp.swapaxes(out.reshape(rows, b, limbs, blocks), 0, 1)
        out = out.reshape(b * rows, limbs, blocks)
    return jnp.moveaxis(out, 1, -1)                 # [F, blocks, limbs]
