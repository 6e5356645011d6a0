"""A TEE worker's audit round by the published equation, mission by
mission, and an honest-proof maker from the key (PR 33).

The deployment ``tee-verify-caps``: one TEE judges a round's missions,
each an aggregated proof (mu [sectors], sigma [limbs]) against the set
of fragment ids the chain says the miner owes. Per mission and limb

    sigma ?= sum_f r_f * sum_i nu_i * f_k(id_f, I_i) + sum_j alpha_j * mu_j

with (I, nu) the round's challenge, r the round's aggregation
coefficients and f_k the per-block PRF, all as ``podr2_ref`` (frozen)
defines them. The PRF folds are evaluated in plain ``jax.numpy``, a
fixed number of rows at a time so that 100,000 owed fragments fit
anywhere; everything after them (r, the sums over a mission, alpha . mu,
the comparison) is NumPy uint64 arithmetic mod p = 2^31 - 1, written
without the limb splitting the program uses. Nothing here imports the
program, and nothing here is handed anything the program made.

``honest_proofs`` makes what an honest miner would send WITHOUT the
miner's data: mu is drawn from a seed, uniform in F_p^sectors (which is
how an honest fold of random fragments is distributed), and sigma is the
right-hand side above. A proof over real bytes comes from
``podr2_ref.prove_aggregate``; both verify here and in the program.
"""
from __future__ import annotations

import functools

import jax
import numpy as np

from . import pfield as pf
from . import podr2_ref

P = np.uint64(pf.P)
ROWS = 2048     # per jitted call: [2048, 753, 2] uint32 = 12 MiB of PRF


@functools.partial(jax.jit, static_argnames=("limbs",))
def _row_folds(prf_key, ids, idx, nu, limbs: int):
    """sum_i nu_i * f_k(id_f, I_i) for every row f -> [rows, limbs]."""
    f_i = jax.vmap(
        lambda i: podr2_ref.prf_elems_at(prf_key, i, idx, limbs))(ids)
    return jax.vmap(lambda f: pf.dotmod(nu[:, None], f, axis=0))(f_i)


def row_folds(key: podr2_ref.Key, ids, idx, nu) -> np.ndarray:
    """The PRF fold of every owed fragment, ids [T, 2] -> uint64
    [T, limbs], ROWS at a time on the device the caller chose."""
    ids = np.asarray(ids, dtype=np.uint32).reshape(-1, 2)
    limbs = int(key.alpha.shape[1])
    out = np.zeros((len(ids), limbs), dtype=np.uint64)
    for at in range(0, len(ids), ROWS):
        part = np.zeros((ROWS, 2), dtype=np.uint32)
        n = min(ROWS, len(ids) - at)
        part[:n] = ids[at:at + n]
        out[at:at + n] = np.asarray(
            _row_folds(key.prf_key, part, idx, nu, limbs))[:n]
    return out


def mission_sums(key: podr2_ref.Key, seed: bytes, blocks: int, ids,
                 sizes) -> np.ndarray:
    """sum_f r_f * sum_i nu_i * f_k(id_f, I_i) over each mission's owed
    fragments: ids [T, 2] in mission order, sizes [M] >= 1 -> uint64
    [M, limbs] in [0, p)."""
    sizes = np.asarray(sizes, dtype=np.int64)
    if sizes.min() < 1 or sizes.sum() != len(ids):
        raise ValueError("sizes [M] >= 1 must sum to the rows of ids")
    idx, nu = podr2_ref.gen_challenge(seed, blocks)
    r = np.asarray(podr2_ref.aggregate_coeffs(seed, ids)).astype(np.uint64)
    terms = (r[:, None] * row_folds(key, ids, idx, nu)) % P   # < 2^62
    starts = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    return np.add.reduceat(terms, starts, axis=0) % P          # < 2^31 a term


def alpha_dot(key: podr2_ref.Key, mu) -> np.ndarray:
    """sum_j alpha_j * mu_j per proof: mu [M, sectors] -> uint64
    [M, limbs]."""
    alpha = np.asarray(key.alpha).astype(np.uint64)            # [s, limbs]
    mu = np.asarray(mu).astype(np.uint64)
    return ((mu[:, :, None] * alpha[None]) % P).sum(axis=1) % P


def honest_proofs(key: podr2_ref.Key, seed: bytes, blocks: int, ids,
                  sizes, mu_seed: int):
    """(mu [M, sectors], sigma [M, limbs]) uint32 that verify: mu from
    ``mu_seed``, uniform in [0, p); sigma the equation's right side."""
    rng = np.random.default_rng(mu_seed)
    mu = rng.integers(0, int(P), (len(sizes), int(key.alpha.shape[0])),
                      dtype=np.uint64)
    sigma = (mission_sums(key, seed, blocks, ids, sizes)
             + alpha_dot(key, mu)) % P
    return mu.astype(np.uint32), sigma.astype(np.uint32)


def verdicts(key: podr2_ref.Key, seed: bytes, blocks: int, owed_ids,
             proofs) -> list[bool]:
    """One verdict a mission, by the equation. ``owed_ids[m]`` is
    mission m's ids [F_m, 2]; ``proofs[m]`` its (mu, sigma), or None for
    bytes that do not decode to a proof of the deployment's widths (a
    failed audit). An empty owed set passes only under the all-zero
    proof. Both limbs must hold; a sigma outside [0, p) equals nothing."""
    out = [False] * len(proofs)
    live = []
    for m, (ids, proof) in enumerate(zip(owed_ids, proofs)):
        if proof is None:
            continue
        if len(ids):
            live.append(m)
        else:
            out[m] = not np.any(proof[0]) and not np.any(proof[1])
    if live:
        flat = np.concatenate([np.asarray(owed_ids[m]).reshape(-1, 2)
                               for m in live])
        want = (mission_sums(key, seed, blocks, flat,
                             [len(owed_ids[m]) for m in live])
                + alpha_dot(key, np.stack([proofs[m][0] for m in live]))) % P
        got = np.stack([proofs[m][1] for m in live]).astype(np.uint64)
        for m, ok in zip(live, np.all(want == got, axis=1)):
            out[m] = bool(ok)
    return out
