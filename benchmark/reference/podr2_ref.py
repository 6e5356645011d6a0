"""Plain-jnp PoDR2 (Shacham-Waters over F_p^2, p = 2^31 - 1): key,
PRF, tag, challenge, aggregated prove and verify.

A frozen copy (PR 24) of the equations of cess_tpu/ops/podr2.py with
the Pallas dispatch taken out: ``tag_fragment`` is the materialised
pack -> MAC path, one fragment at a time. Callers run it on the CPU
device (``on_cpu``), so the chip holds only the program's state.
threefry is counter-based and platform-deterministic, so CPU and TPU
agree bit-exactly; every comparison against this file is equality.
"""
from __future__ import annotations

import contextlib
import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np

from . import pfield as pf

SECTORS = 256                       # field elements per block
LIMBS = 2
BLOCK_BYTES = SECTORS * pf.BYTES_PER_ELEM   # 512
CHALLENGE_RATE_NUM = 46             # c-pallets/audit/src/lib.rs:956
CHALLENGE_RATE_DEN = 1000


def on_cpu():
    """Scope in which every jnp op of the reference runs on the host."""
    return jax.default_device(jax.devices("cpu")[0])


@dataclasses.dataclass(frozen=True)
class Key:
    alpha: jax.Array        # [sectors, limbs] uint32 in [0, p)
    prf_key: jax.Array      # jax PRNG key


def generate_key(seed: int, sectors: int = SECTORS,
                 limbs: int = LIMBS) -> Key:
    k_alpha, k_prf = jax.random.split(jax.random.key(seed))
    alpha = pf.to_field(jax.random.bits(k_alpha, (sectors, limbs),
                                        jnp.uint32))
    return Key(alpha=alpha, prf_key=k_prf)


def fragment_id_from_hash(fragment_hash: bytes) -> np.ndarray:
    v = int.from_bytes(fragment_hash[:8], "little")
    return np.array([v & 0xFFFFFFFF, v >> 32], dtype=np.uint32)


def _fragment_key(prf_key, fragment_id):
    fid = jnp.asarray(fragment_id)
    if fid.ndim == 1 and fid.shape[0] == 2:       # (lo, hi) pair
        lo, hi = fid[0].astype(jnp.uint32), fid[1].astype(jnp.uint32)
    else:                                          # 32-bit scalar id
        lo, hi = fid.astype(jnp.uint32), jnp.uint32(0)
    return jax.random.fold_in(jax.random.fold_in(prf_key, lo), hi)


def prf_elems_at(prf_key, fragment_id, block_idx, limbs: int = LIMBS):
    """f_k(fragment_id, b) for the given block indices [c, limbs]."""
    key = _fragment_key(prf_key, fragment_id)

    def one(b):
        return pf.to_field(jax.random.bits(
            jax.random.fold_in(key, b), (limbs,), jnp.uint32))

    return jax.vmap(one)(jnp.asarray(block_idx).astype(jnp.uint32))


def fragment_to_elems(fragment, sectors: int = SECTORS):
    """uint8 [..., bytes] -> uint32 [..., blocks, sectors]."""
    *lead, nbytes = fragment.shape
    elems = pf.pack_bytes(fragment)
    return elems.reshape(*lead, nbytes // (sectors * pf.BYTES_PER_ELEM),
                         sectors)


@jax.jit
def _tag(alpha, prf_key, fragment_id, fragment):
    m = fragment_to_elems(fragment, alpha.shape[0])           # [B, s]
    f = prf_elems_at(prf_key, fragment_id,
                     jnp.arange(m.shape[0], dtype=jnp.uint32),
                     alpha.shape[1])
    mac = pf.summod(pf.mulmod_u16(m[..., None], alpha[None, :, :]),
                    axis=-2)
    return pf.addmod(f, mac)


def tag_fragment(key: Key, fragment_id, fragment) -> np.ndarray:
    """tag[b] = f_k(id, b) + sum_j alpha[j] * m[b, j]; uint8 [bytes]
    -> uint32 [blocks, limbs]."""
    return np.asarray(_tag(key.alpha, key.prf_key,
                           jnp.asarray(fragment_id),
                           jnp.asarray(fragment)))


def gen_challenge(seed_bytes: bytes, num_blocks: int):
    """(indices [c], nu [c]) from the round's randomness: 46/1000 of
    the blocks."""
    count = max(1, num_blocks * CHALLENGE_RATE_NUM // CHALLENGE_RATE_DEN)
    digest = hashlib.sha256(seed_bytes).digest()
    w0 = int.from_bytes(digest[:4], "little")
    w1 = int.from_bytes(digest[4:8], "little")
    key = jax.random.fold_in(jax.random.key(np.uint32(w0)), np.uint32(w1))
    k_idx, k_nu = jax.random.split(key)
    idx = jax.random.randint(k_idx, (count,), 0, num_blocks,
                             dtype=jnp.int32)
    nu = pf.to_field(jax.random.bits(k_nu, (count,), jnp.uint32))
    return idx, nu


def aggregate_coeffs(seed_bytes: bytes, fragment_ids) -> jax.Array:
    """r[F], PRF-derived from the round seed and each fragment id."""
    digest = hashlib.sha256(b"cess-podr2-agg:" + seed_bytes).digest()
    w0 = int.from_bytes(digest[:4], "little")
    w1 = int.from_bytes(digest[4:8], "little")
    key = jax.random.fold_in(jax.random.key(np.uint32(w0)), np.uint32(w1))
    ids = jnp.asarray(fragment_ids).reshape(-1, 2)

    def one(fid):
        k = jax.random.fold_in(jax.random.fold_in(key, fid[0]), fid[1])
        return pf.to_field(jax.random.bits(k, (), jnp.uint32))

    return jax.vmap(one)(ids)


@jax.jit
def _prove_one(alpha, prf_key, fragment_id, fragment, idx, nu):
    """(mu_f, sigma_f) of one fragment, its tags made here at the
    challenged blocks only."""
    m_i = jnp.take(fragment_to_elems(fragment, alpha.shape[0]), idx,
                   axis=0)                                    # [c, s]
    f_i = prf_elems_at(prf_key, fragment_id, idx, alpha.shape[1])
    tags_i = pf.addmod(f_i, pf.summod(
        pf.mulmod_u16(m_i[..., None], alpha[None, :, :]), axis=-2))
    mu = pf.summod(pf.mulmod_u16(m_i, nu[:, None]), axis=0)   # [s]
    sigma = pf.dotmod(nu[:, None], tags_i, axis=0)
    return mu, sigma


def prove_aggregate(key: Key, fragment_ids, fragments, idx, nu, r):
    """mu = sum_f r_f * mu_f, sigma = sum_f r_f * sigma_f over the set
    (fragments [F, bytes] host), one fragment at a time."""
    idx, nu, r = jnp.asarray(idx), jnp.asarray(nu), jnp.asarray(r)
    mu = sigma = None
    for f in range(len(fragments)):
        mu_f, sigma_f = _prove_one(key.alpha, key.prf_key,
                                   jnp.asarray(fragment_ids[f]),
                                   jnp.asarray(fragments[f]), idx, nu)
        mu_f = pf.mulmod(r[f], mu_f)
        sigma_f = pf.mulmod(r[f], sigma_f)
        mu = mu_f if mu is None else pf.addmod(mu, mu_f)
        sigma = sigma_f if sigma is None else pf.addmod(sigma, sigma_f)
    return np.asarray(mu), np.asarray(sigma)


def verify_aggregate(key: Key, fragment_ids, idx, nu, r, mu, sigma) -> bool:
    """sigma ?= sum_f r_f sum_i nu_i f_k(id_f, I_i) + sum_j alpha_j mu_j,
    per limb; both must hold."""
    ids = jnp.asarray(fragment_ids).reshape(-1, 2)
    idx, nu, r = jnp.asarray(idx), jnp.asarray(nu), jnp.asarray(r)
    limbs = key.alpha.shape[1]
    f_i = jax.vmap(lambda i: prf_elems_at(key.prf_key, i, idx, limbs))(ids)
    lhs_f = jax.vmap(lambda f: pf.dotmod(nu[:, None], f, axis=0))(f_i)
    lhs = pf.addmod(pf.dotmod(r[:, None], lhs_f, axis=0),
                    pf.dotmod(key.alpha, jnp.asarray(mu)[:, None], axis=0))
    return bool(jnp.all(lhs == jnp.asarray(sigma)))
