"""Mersenne prime field F_p, p = 2^31 - 1, in 32-bit lane arithmetic.

TPUs have no native 64-bit integer path, so the PoDR2 field math
(tags, proof aggregation, verification) runs entirely in uint32 with
16-bit limb splitting and the M31 rotation identity (2^31 == 1 mod p,
so multiplying by 2^k is a 31-bit rotation). Every op keeps all
intermediates < 2^32 — exact, overflow-free, and pure VPU work.

The same functions trace under JAX (device path) and execute eagerly
on NumPy arrays (host oracle); tests/test_pfield.py checks both against
Python bigint arithmetic.

Why M31 and not GF(2^8): PoDR2 needs a field big enough that the
Shacham-Waters MAC check sigma == sum(nu_i f_k(i)) + sum(alpha_j mu_j)
has negligible forgery probability per element (~2^-31 here); the
reference's own PoDR2 lives in its external TEE repos and only the
on-chain contract (opaque proof blob <= SIGMA_MAX=2048 B,
/root/reference/runtime/src/lib.rs:992) constrains the design.
"""
from __future__ import annotations

import numpy as np

P = (1 << 31) - 1  # 2147483647, Mersenne prime M31
MASK16 = 0xFFFF


def _xp(x):
    """numpy/jax dispatch: use the module of the input array."""
    import jax

    return jax.numpy if isinstance(x, jax.Array) else np


def _cond_sub_p(xp, r):
    """r - P where r >= P, else r — without evaluating an underflowing
    branch (numpy's where computes both sides eagerly)."""
    return r - (r >= P).astype(xp.uint32) * xp.uint32(P)


def to_field(x):
    """Reduce arbitrary uint32 values into [0, p)."""
    xp = _xp(x)
    x = x.astype(xp.uint32)
    r = (x & P) + (x >> 31)  # < 2^31 + 1
    return _cond_sub_p(xp, r)


def addmod(a, b):
    """(a + b) mod p for a, b in [0, p)."""
    xp = _xp(a)
    s = a.astype(xp.uint32) + b.astype(xp.uint32)  # < 2^32 - 2: no overflow
    return _cond_sub_p(xp, s)


def submod(a, b):
    xp = _xp(a)
    a = a.astype(xp.uint32)
    b = b.astype(xp.uint32)
    return xp.where(a >= b, a - b, a + P - b)


def negmod(a):
    xp = _xp(a)
    a = a.astype(xp.uint32)
    return xp.where(a == 0, a, P - a)


def rotk(x, k: int):
    """x * 2^k mod p for x in [0, p), 0 <= k < 31: 31-bit rotation."""
    if k == 0:
        return x
    return ((x << k) & P) | (x >> (31 - k))


def _rot16(x):
    return rotk(x, 16)


def mulmod(a, b):
    """(a * b) mod p for a, b in [0, p), all intermediates < 2^32.

    Limb split a = a1*2^16 + a0 (a1 < 2^15), same for b:
    a*b = 2*a1*b1 + (a1*b0 + a0*b1)*2^16 + a0*b0  (mod p, 2^32 == 2).
    """
    xp = _xp(a)
    a = a.astype(xp.uint32)
    b = b.astype(xp.uint32)
    a0, a1 = a & MASK16, a >> 16
    b0, b1 = b & MASK16, b >> 16
    t_hi = to_field(a1 * b1 * 2)          # a1*b1 < 2^30 -> *2 < 2^31
    lo = to_field(a0 * b0)                # < 2^32
    m1 = a1 * b0                          # < 2^31
    m2 = a0 * b1                          # < 2^31
    mid = addmod(_rot16(_cond_sub_p(xp, m1)), _rot16(_cond_sub_p(xp, m2)))
    return addmod(addmod(t_hi, mid), lo)


def dot_u16_deferred(m, b, axis):
    """sum_j m_j * b_j mod p with DEFERRED reduction, for m in
    [0, 2^16), b in [0, p), and the contracted axis <= 256.

    The hot-loop trick behind PoDR2 tag-gen: split m into 8-bit and b
    into 16-bit limbs; every partial product is < 2^24, so a PLAIN
    uint32 sum over <= 256 terms cannot overflow (256 * 255 * 65535 =
    4,278,124,800 < 2^32) — one modular fold per OUTPUT element
    instead of a full mulmod + limb-split sum per INPUT element
    (~2.5x fewer VPU ops than mulmod_u16 + summod; measured on chip).
    """
    xp = _xp(m)
    n = m.shape[axis]
    assert n <= 256, f"deferred dot bound: axis dim {n} > 256"
    m = m.astype(xp.uint32)
    b = b.astype(xp.uint32)
    mlo, mhi = m & 0xFF, m >> 8
    b0, b1 = b & MASK16, b >> 16
    s00 = xp.sum(mlo * b0, axis=axis, dtype=xp.uint32)
    s10 = xp.sum(mhi * b0, axis=axis, dtype=xp.uint32)
    s01 = xp.sum(mlo * b1, axis=axis, dtype=xp.uint32)
    s11 = xp.sum(mhi * b1, axis=axis, dtype=xp.uint32)
    return addmod(addmod(to_field(s00), rotk(to_field(s10), 8)),
                  addmod(rotk(to_field(s01), 16),
                         rotk(to_field(s11), 24)))


def mulmod_u16(a, b):
    """(a * b) mod p for a in [0, 2^16), b in [0, p).

    The data-side fast path: PoDR2 packs fragment bytes two-per-element
    (pack_bytes width 2), so the m operand of every MAC/proof multiply
    is < 2^16 and its high limb is structurally zero — half of the
    generic mulmod disappears. With a < 2^16:
      a*b0 < 2^32 (one to_field), a*b1 <= (2^16-1)(2^15-1) < p (rot16
      directly). When b is a constant (alpha), XLA hoists its limb
      split, leaving ~2 multiplies + 2 reductions per element.
    """
    xp = _xp(a)
    a = a.astype(xp.uint32)
    b = b.astype(xp.uint32)
    return addmod(to_field(a * (b & MASK16)), _rot16(a * (b >> 16)))


def summod(x, axis=-1):
    """Exact modular sum along an axis; requires dim size <= 65535.

    Values in [0, p) are limb-split so the plain uint32 sums cannot
    overflow, then recombined mod p.
    """
    xp = _xp(x)
    n = x.shape[axis]
    if n > 65535:
        raise ValueError(f"summod axis dim {n} > 65535; fold first")
    x = x.astype(xp.uint32)
    lo = xp.sum(x & MASK16, axis=axis, dtype=xp.uint32)   # <= n * (2^16-1) < 2^32
    hi = xp.sum(x >> 16, axis=axis, dtype=xp.uint32)      # <= n * 2^15 < 2^31
    return addmod(_rot16(to_field(hi)), to_field(lo))


def dotmod(a, b, axis=-1):
    """Modular dot product sum_i a_i * b_i along an axis."""
    return summod(mulmod(a, b), axis=axis)


def psum_mod(x, axis_name: str):
    """Exact modular psum across a mesh axis (JAX only).

    Values in [0, p) are limb-split so plain uint32 psums cannot
    overflow for any device count <= 65536 (lo/hi <= ndev * (2^16 - 1)
    < 2^32), then recombined exactly mod p: both psum results are first
    reduced into [0, p) before the final addmod, so no intermediate can
    exceed 2^32 at any device count the limb bound admits.
    """
    import jax

    lo = jax.lax.psum(x & MASK16, axis_name)
    hi = jax.lax.psum(x >> 16, axis_name)
    return addmod(to_field(lo), _rot16(to_field(hi)))


def powmod(a: int, e: int) -> int:
    """Host-side scalar pow (for matrix inversion / host checks)."""
    return pow(int(a), int(e), P)


def invmod(a: int) -> int:
    if int(a) % P == 0:
        raise ZeroDivisionError("inverse of 0 in F_p")
    return pow(int(a), P - 2, P)


# -- byte packing ----------------------------------------------------------
#
# Elements embed bytes injectively into [0, p). Width 2 (16-bit) divides
# every power-of-two fragment size into whole blocks (8 MiB / 512 B
# blocks exactly), which keeps the PoDR2 block grid aligned with the
# reference's power-of-two segment/fragment geometry; width 3 (24-bit)
# is denser but leaves remainder bytes on power-of-two sizes.

BYTES_PER_ELEM = 2


def pack_bytes(data, width: int = BYTES_PER_ELEM, xp=None):
    """uint8 [..., width*L] -> uint32 field elements [..., L] (little-endian)."""
    if xp is None:
        xp = _xp(data)
    *lead, n = data.shape
    assert n % width == 0, f"byte length {n} not divisible by {width}"
    assert 1 <= width <= 3  # width 4 would not embed into [0, p)
    if xp is not np and width == 2 and data.dtype == xp.uint8:
        # device fast path: a u8-pair -> u16 BITCAST is the same
        # little-endian combine as the shift-or below but lowers to a
        # relayout instead of two shifted adds — measured 1.75x on the
        # tag-gen pack stage (v5e, r05); the numpy branch stays the
        # canonical oracle and tests pin both paths byte-equal
        import jax

        h = jax.lax.bitcast_convert_type(
            data.reshape(*lead, n // 2, 2), xp.uint16)
        return h.astype(xp.uint32)
    d = data.reshape(*lead, n // width, width).astype(xp.uint32)
    out = d[..., 0]
    for i in range(1, width):
        out = out | (d[..., i] << (8 * i))
    return out


def unpack_bytes(elems, width: int = BYTES_PER_ELEM, xp=None):
    """Inverse of pack_bytes: uint32 [..., L] (< 2^(8*width)) -> uint8."""
    if xp is None:
        xp = _xp(elems)
    e = elems.astype(xp.uint32)
    parts = xp.stack([(e >> (8 * i)) & 0xFF for i in range(width)], axis=-1)
    return parts.reshape(*e.shape[:-1], e.shape[-1] * width).astype(xp.uint8)
