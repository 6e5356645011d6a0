"""A storage miner's audit round by the published equations, fragment by
fragment (PR 38).

The deployment ``miner-deal-cap``: one miner holds a deal's share (1,000
fragments of 8 MiB at the protocol's geometry) and answers a round with
ONE aggregated proof over every owed fragment it holds. With (I, nu) the
round's challenge and r the round's aggregation coefficients, all as
``podr2_ref`` (frozen) derives them from the seed,

    mu_j   = sum_f r_f * sum_i nu_i * m_f[I_i, j]        (mod p)
    sigma  = sum_f r_f * sum_i nu_i * tag_f[I_i]         (mod p, per limb)

where m_f[b, j] is the j-th little-endian 16-bit word of block b of
fragment f and tag_f its tags ``[blocks, limbs]``. Everything is NumPy
uint64 arithmetic mod p = 2^31 - 1, written without the limb splitting,
the batching or the chunking the program uses, one fragment at a time so
that a set of any size fits anywhere: a term nu * m is below 2^47 and a
sum of 65,535 of them below 2^63. Nothing here imports the program.

``prove`` takes the tags the miner holds (the guarantee is "the proof's
bytes equal the reference's on the same held bytes and tags"); whether
those tags are the key's is what the verifier decides, and the reference
verifier is ``verify_round_ref.verdicts`` (``accepted`` below), which is
handed the key and the ids and nothing of the miner's.
"""
from __future__ import annotations

import numpy as np

from . import pfield as pf
from . import podr2_ref
from . import verify_round_ref

P = np.uint64(pf.P)


def ids_from_hashes(hashes) -> np.ndarray:
    """[F, 2] uint32: each hash's low 8 bytes as (lo, hi) words."""
    return np.stack([podr2_ref.fragment_id_from_hash(h) for h in hashes]) \
        if len(hashes) else np.zeros((0, 2), np.uint32)


def prove(seed: bytes, hashes, fragments, tags, blocks: int,
          sectors: int = podr2_ref.SECTORS):
    """(mu [sectors], sigma [limbs]) uint32 of the set: ``hashes[f]``
    names fragment f, ``fragments[f]`` are its bytes (anything
    ``np.frombuffer`` reads), ``tags[f]`` its tags [blocks, limbs]. An
    empty set is the all-zero proof of ``podr2_ref.LIMBS`` limbs."""
    if not len(hashes):
        return (np.zeros(sectors, np.uint32),
                np.zeros(podr2_ref.LIMBS, np.uint32))
    with podr2_ref.on_cpu():
        idx, nu = podr2_ref.gen_challenge(seed, blocks)
        r = np.asarray(podr2_ref.aggregate_coeffs(
            seed, ids_from_hashes(hashes))).astype(np.uint64)
    idx = np.asarray(idx)
    nu = np.asarray(nu).astype(np.uint64)[:, None]
    if len(idx) > 65535:
        raise ValueError("a sum of more than 65,535 terms needs folding")
    mu = np.zeros(sectors, np.uint64)
    sigma = np.zeros(np.asarray(tags[0]).shape[1], np.uint64)
    for f in range(len(hashes)):
        m = np.frombuffer(fragments[f], dtype="<u2").reshape(
            blocks, sectors)[idx].astype(np.uint64)             # [c, s]
        t = np.asarray(tags[f])[idx].astype(np.uint64)          # [c, limbs]
        mu_f = (nu * m).sum(axis=0) % P
        sigma_f = ((nu * t) % P).sum(axis=0) % P
        mu = (mu + r[f] * mu_f % P) % P
        sigma = (sigma + r[f] * sigma_f % P) % P
    return mu.astype(np.uint32), sigma.astype(np.uint32)


def accepted(key: podr2_ref.Key, seed: bytes, blocks: int, hashes,
             mu, sigma) -> bool:
    """The reference verifier's verdict on one miner's proof against the
    owed hashes (``verify_round_ref.verdicts``, a round of one
    mission)."""
    with podr2_ref.on_cpu():
        return verify_round_ref.verdicts(
            key, seed, blocks, [ids_from_hashes(hashes)],
            [(np.asarray(mu), np.asarray(sigma))])[0]
