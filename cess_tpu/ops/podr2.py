"""PoDR2 proof-of-storage ops: batched tag-gen / prove / verify on TPU.

The reference's PoDR2 flow (SURVEY.md §3.3): a TEE worker computes
per-fragment tags off-chain; each challenge round, snapshotted miners
compute an aggregated (sigma, mu) proof over ~47 randomly challenged
chunks (c-pallets/audit/src/lib.rs:956-974), and a TEE verifies it
against the network PoDR2 key. The tag/proof math itself lives in
CESS's external TEE repos; on-chain only the contract shows: proof blob
<= SIGMA_MAX = 2048 bytes (runtime/src/lib.rs:992), challenge = chunk
indices + 20-byte randoms.

Here the scheme is a Shacham-Waters private-verification PoR with the
MAC over F_p^2, p = 2^31 - 1 (data stays in F_p), redesigned for
batched TPU execution:

- A fragment (FRAGMENT_SIZE bytes) is split into ``blocks`` of
  ``sectors`` field elements (2 bytes each, so power-of-two fragment
  sizes divide into whole 512-byte blocks). For 8 MiB fragments and
  sectors=256: 16384 blocks.
- TagGen (TEE secret key (alpha[sectors, 2], prf_key)): alpha and the
  PRF live in F_p^2 = F_p[i]/(i^2+1) (p == 3 mod 4 so irreducible);
  data m and challenge coefficients nu stay in the base field, so
  every F_p^2 operation used below is COMPONENTWISE — two
  independently-keyed copies of the base-field MAC, one per limb:
      tag[b] = f_k(fragment_id, b) + sum_j alpha[j] * m[b, j]  in F_p^2
  (tags are [blocks, 2] uint32).
- Challenge: ``count`` block indices I and coefficients nu in F_p
  (both PRF-derived from the round randomness, mirroring audit's
  46/1000 coverage and 20-byte randoms).
- Prove (miner, needs only data + tags, no secrets):
      mu[j]  = sum_{i in I} nu[i] * m[I[i], j]   (mod p, base field)
      sigma  = sum_{i in I} nu[i] * tag[I[i]]    (componentwise, F_p^2)
  Proof size: see PROOF_BYTES below — the ONE authoritative statement
  of the raw payload size and its relation to the framed wire size.
- Verify (TEE), one equation per limb, BOTH must hold:
      sigma ?= sum_i nu[i] * f_k(id, I[i]) + sum_j alpha[j] * mu[j]

SOUNDNESS: a forged (mu', sigma') with mu' != mu must hit
sum_j alpha_j (mu'_j - mu_j) in F_p^2 with alpha unknown and uniform:
acceptance probability p^-2 ~= 2^-62 per verification (vs ~2^-31 for
the r03 single-equation scheme; the reference's BLS check is ~2^-128
but needs pairings, /root/reference/utils/verify-bls-signatures/
src/lib.rs:1-247 via primitives/enclave-verify/src/lib.rs:230-235).
Grinding headroom: at 8000 miners x 14400 rounds/day (caps from
runtime/src/lib.rs:988) a 2^-62 break still needs ~10^11 years.

Everything is batch-first over a fragment axis and jit/vmap/pjit-able;
the byte/block axis shards across the mesh with psum aggregation
(cess_tpu/parallel/mesh.py).
"""
from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import threading

import jax
import jax.numpy as jnp
import numpy as np

from .. import constants
from ..obs import trace
from . import pfield as pf

SECTORS = 256                       # field elements per block
BLOCK_BYTES = SECTORS * pf.BYTES_PER_ELEM   # 512
# Default MAC limb count: F_p^LIMBS, soundness ~p^-LIMBS per verify.
# MEASURED on the real v5e chip (r05, 128 x 8 MiB resident batches,
# jnp path): LIMBS=2 (soundness ~2^-62) tags at ~1926 frags/s,
# LIMBS=3 (~2^-93) at ~1681 — the third limb costs ~13% of tag
# throughput, and per-limb cost scales the same through the fused
# kernel (ops/podr2_pallas.py, ~6.4k frags/s at limbs=2 — tag-gen is
# the dominant audit stage; verify evaluates the PRF only at the
# challenged blocks and is width-insensitive). 2 stays the default:
# at protocol caps (8000 miners x 14400 rounds/day) a 2^-62 forgery
# still needs ~10^11 years, and the audit path is throughput-critical
# (100k fragments per round). Deployments wanting ~2^-93 pass
# Podr2Params(limbs=3) end to end (tests run both widths).
LIMBS = 2
# THE authoritative aggregated-proof size statement (three separate
# prose copies drifted to 1032/1028/1058 before r06; everything else
# refers here). The RAW payload is mu [SECTORS] + sigma [LIMBS]
# uint32: (SECTORS + LIMBS) * 4 = 1032 bytes at the defaults. On the
# wire the payload travels codec-framed (node/offchain.py Proof: two
# fixed-width ndarrays, so dtype/shape/length headers add a CONSTANT
# overhead independent of F — 26 bytes at the defaults, 1058 B framed,
# pinned by tests/test_podr2.py test_aggregate_proof_wire_size_constant
# via node/offchain.py proof_wire_bytes(), which lives next to Proof
# because framing is node-layer knowledge the ops layer must not
# import). Both forms stay under SIGMA_MAX = 2048
# (runtime/src/lib.rs:992), limbs=3 included.
PROOF_BYTES = (SECTORS + LIMBS) * 4
assert (SECTORS + 3) * 4 <= constants.SIGMA_MAX   # limbs=3 fits too


@dataclasses.dataclass(frozen=True)
class Podr2Params:
    sectors: int = SECTORS
    limbs: int = LIMBS          # MAC limb count (see module doc)

    def blocks_for(self, fragment_bytes: int) -> int:
        block_bytes = self.sectors * pf.BYTES_PER_ELEM
        assert fragment_bytes % block_bytes == 0, (
            f"fragment {fragment_bytes} B not divisible by block {block_bytes} B")
        return fragment_bytes // block_bytes


@dataclasses.dataclass(frozen=True)
class Podr2Key:
    """TEE-held secret key (the reference's TeePodr2Pk analog is the
    public handle; private verification keeps the whole key in the TEE,
    SURVEY.md §2.1 tee-worker)."""

    alpha: jax.Array        # [sectors, limbs] uint32 in [0, p)
    prf_key: jax.Array      # jax PRNG key

    @property
    def limbs(self) -> int:
        return self.alpha.shape[1]

    @staticmethod
    def generate(seed: int, params: Podr2Params = Podr2Params()) -> "Podr2Key":
        root = jax.random.key(seed)
        k_alpha, k_prf = jax.random.split(root)
        alpha = pf.to_field(
            jax.random.bits(k_alpha, (params.sectors, params.limbs),
                            jnp.uint32))
        return Podr2Key(alpha=alpha, prf_key=k_prf)


def keys_equal(a: Podr2Key, b: Podr2Key) -> bool:
    """Value equality of two PoDR2 keys (alpha + PRF key material).

    Security-sensitive single source of truth: components that accept
    an externally-built device stack (e.g. a submission engine's
    AuditBackend) must refuse a key that differs from their own, or
    tags/verdicts silently diverge from the protocol."""
    if a is b:
        return True
    return (np.array_equal(np.asarray(a.alpha), np.asarray(b.alpha))
            and np.array_equal(jax.random.key_data(a.prf_key),
                               jax.random.key_data(b.prf_key)))


def fragment_id_from_hash(fragment_hash: bytes) -> np.ndarray:
    """Protocol fragment id = low 8 bytes of the on-chain fragment hash,
    as a (lo, hi) uint32 pair (x32 mode cannot carry 64-bit scalars).

    SECURITY CONTRACT: tag-gen ids must be unique per key — reusing an
    id for different data under one key lets an adversary difference
    two tag sets and solve for alpha. Hash-derived ids give uniqueness
    for free (distinct fragments have distinct hashes).
    """
    v = int.from_bytes(fragment_hash[:8], "little")
    return np.array([v & 0xFFFFFFFF, v >> 32], dtype=np.uint32)


def fragment_ids_from_hashes(hashes) -> np.ndarray:
    """``fragment_id_from_hash`` of a whole owed list in one pass ->
    [F, 2] uint32 (an audit round at the protocol's caps names 100,000
    fragments: one join and one view, not a Python call a hash)."""
    hashes = list(hashes)
    width = len(hashes[0]) if hashes else 0
    if width < 8 or width % 4 or set(map(len, hashes)) != {width}:
        return np.stack([fragment_id_from_hash(h) for h in hashes]) \
            if hashes else np.zeros((0, 2), np.uint32)
    words = np.frombuffer(b"".join(hashes), dtype="<u4")
    return np.ascontiguousarray(
        words.reshape(len(hashes), width // 4)[:, :2], dtype=np.uint32)


def _fragment_key(prf_key, fragment_id):
    """Per-fragment PRF key: fragment_id (possibly 64-bit) folds in as
    two 32-bit words (x32 mode cannot carry 64-bit scalars)."""
    if isinstance(fragment_id, int):
        lo = np.uint32(fragment_id & 0xFFFFFFFF)
        hi = np.uint32((fragment_id >> 32) & 0xFFFFFFFF)
    else:
        fid = jnp.asarray(fragment_id)
        if fid.ndim == 1 and fid.shape[0] == 2:   # (lo, hi) pair
            lo, hi = fid[0].astype(jnp.uint32), fid[1].astype(jnp.uint32)
        else:                                      # plain 32-bit scalar id
            lo, hi = fid.astype(jnp.uint32), jnp.uint32(0)
    return jax.random.fold_in(jax.random.fold_in(prf_key, lo), hi)


def prf_elems_at(prf_key, fragment_id, block_idx, limbs: int = LIMBS):
    """f_k(fragment_id, b) for the GIVEN block indices only
    [len(block_idx), limbs].

    The PRF is defined PER BLOCK — f_k(id, b) = bits(fold_in(key_id, b))
    — precisely so callers can evaluate it sparsely: a challenge names
    ~4.6% of a fragment's blocks (audit's 46/1000 coverage), and the
    verifier regenerating all 16384 was the dominant verify cost
    (measured ~40x on the real chip, r05). threefry is counter-based
    and platform-deterministic, so CPU and TPU paths agree bit-exactly
    (a protocol invariant, like the codec).
    """
    key = _fragment_key(prf_key, fragment_id)

    def one(b):
        return pf.to_field(jax.random.bits(
            jax.random.fold_in(key, b), (limbs,), jnp.uint32))

    return jax.vmap(one)(jnp.asarray(block_idx).astype(jnp.uint32))


def prf_elems(prf_key, fragment_id, n: int, limbs: int = LIMBS):
    """f_k(fragment_id, 0..n-1): the full per-block PRF range
    [n, limbs] (tag-gen side). Identical by construction to
    prf_elems_at over arange(n) — sharded executions slice their local
    range so tags are identical regardless of mesh topology."""
    return prf_elems_at(prf_key, fragment_id,
                        jnp.arange(n, dtype=jnp.uint32), limbs)


def tag_from_elems(alpha, f, m):
    """tags [B, limbs] from PRF slice f [B, limbs] and packed data
    m [B, s].

    m is base-field, alpha [s, limbs] is F_p^limbs: the product is
    componentwise, so each limb is an independent base-field MAC.
    m < 2^16 by the pack_bytes width-2 embedding, and sectors <= 256,
    so the deferred-reduction dot applies (the MAC is the tag-gen hot
    loop: 4M elements x limbs per 8 MiB fragment; see
    pf.dot_u16_deferred)."""
    if m.shape[-1] <= 256:
        return pf.addmod(f, pf.dot_u16_deferred(
            m[..., None], alpha[None, :, :], axis=-2))
    return pf.addmod(f, pf.summod(
        pf.mulmod_u16(m[..., None], alpha[None, :, :]), axis=-2))


def fragment_to_elems(fragment, sectors: int = SECTORS):
    """uint8 [..., fragment_bytes] -> uint32 [..., blocks, sectors]."""
    *lead, nbytes = fragment.shape
    elems = pf.pack_bytes(fragment)
    return elems.reshape(*lead, nbytes // (sectors * pf.BYTES_PER_ELEM), sectors)


def tag_fragment(key: Podr2Key, fragment_id, fragment) -> jax.Array:
    """Tags for one fragment: uint8 [fragment_bytes] -> uint32 [blocks, 2]."""
    m = fragment_to_elems(fragment, key.alpha.shape[0])     # [B, s]
    return tag_from_elems(key.alpha, prf_elems(key.prf_key, fragment_id,
                                               m.shape[0], key.limbs), m)


def _tag_batch(key: Podr2Key, fragment_ids, fragments, weights):
    """THE one definition of batched tag-gen, traced by every caller
    (eager, the fused ingest step, TAG_PROGRAM). ``weights``: the fused
    kernel's weight limbs of ``key.alpha`` (podr2_pallas.weight_limbs)
    or None — they are host arithmetic on a concrete alpha, so a caller
    whose key is traced brings them. The lowering follows the shape:
    the Pallas kernel inside its envelope, the plain-jnp MAC outside it
    (and without weights): identical results either way. ``fragments``:
    [F, bytes], or the fused ingest step's [B, rows, bytes] (F = B *
    rows, row-major), which only the kernel's entry point takes apart
    (podr2_pallas.tag_fragments_fused)."""
    from . import podr2_pallas

    sectors = key.alpha.shape[0]
    blocks = fragments.shape[-1] // (sectors * pf.BYTES_PER_ELEM)
    if weights is not None and podr2_pallas.supported(sectors, blocks):
        prf = jax.vmap(
            lambda i: prf_elems(key.prf_key, i, blocks,
                                key.limbs))(fragment_ids)
        return podr2_pallas.tag_fragments_fused(weights, prf, fragments)
    if fragments.ndim == 3:
        fragments = fragments.reshape(-1, fragments.shape[-1])
    return jax.vmap(lambda i, d: tag_fragment(key, i, d))(fragment_ids,
                                                          fragments)


def tag_fragments(key: Podr2Key, fragment_ids, fragments) -> jax.Array:
    """Batched tag-gen: ids [F], fragments [F, fragment_bytes] (or
    still in their batch's shape, [B, rows, fragment_bytes] with F =
    B * rows, row-major) -> [F, blocks, limbs]. Routes through the
    fused Pallas kernel (ops/podr2_pallas.py) when the shape envelope
    allows — identical results, one VMEM pass instead of materialised
    pack/MAC stages.
    Eager, or inside the caller's own trace with the key its constants
    (models/pipeline.py fused_step); a tag batch a call, with the key
    as operands, is ``tag_dispatch``."""
    from . import podr2_pallas

    # a TRACED alpha (key passed as a jit argument) cannot feed the
    # kernel's host-side weight precompute; the jnp path traces fine
    alpha_concrete = not isinstance(key.alpha, jax.core.Tracer)
    return _tag_batch(key, fragment_ids, jnp.asarray(fragments),
                      podr2_pallas.weight_limbs(key.alpha)
                      if alpha_concrete else None)


def _tag_program(fragment_ids, fragments, alpha, prf_key_data, weights, *,
                 prf_impl: str):
    key = Podr2Key(alpha, jax.random.wrap_key_data(prf_key_data,
                                                   impl=prf_impl))
    return _tag_batch(key, fragment_ids, fragments, weights)


# jitted once for the process, as the round programs below: the key
# and the kernel's weights are operands, so one executable a batch
# shape (ids, fragments) and device serves every key and every backend
TAG_PROGRAM = jax.jit(_tag_program, static_argnames=("prf_impl",))


def tag_operands(key: Podr2Key) -> tuple:
    """The key as TAG_PROGRAM takes it: ``key_operands`` plus the fused
    kernel's weight limbs, all host arrays, made once a key."""
    from . import podr2_pallas

    alpha, prf_key_data, prf_impl = key_operands(key)
    return (alpha, prf_key_data, podr2_pallas.weight_limbs(alpha),
            prf_impl)


def tag_dispatch(tag_ops: tuple, fragment_ids, fragments) -> jax.Array:
    """``tag_fragments`` as ONE enqueue: TAG_PROGRAM over
    ``tag_operands(key)``, placed wherever its caller places it. Tags
    bit-identical to ``tag_fragments(key, ...)``."""
    alpha, prf_key_data, weights, prf_impl = tag_ops
    return TAG_PROGRAM(fragment_ids, fragments, alpha, prf_key_data,
                       weights, prf_impl=prf_impl)


# the round's two derivations, counted for the process: stage name ->
# [calls, seconds] (stage_counters). Their callers are agents' threads,
# so the account has its own lock and the stage no sink.
_STAGE_MU = threading.Lock()
_STAGES = {"podr2.challenge": [0, 0.0], "podr2.coeffs": [0, 0.0]}


def _staged(name: str, body, dispatch, *args):
    """One round derivation over ``args``, the seed's words first. At the
    top level it is ``dispatch(*args)`` — the call of the derivation's
    compiled program, one enqueue — as one stage of a round, one a call
    (obs.trace.stage: ``cess:<name>`` in a profiler trace, a child of
    the caller's span, ``[calls, seconds]`` in ``stage_counters()``).
    Only that entry is a stage: reached while JAX traces a caller
    (``jit``, ``vmap``: ``trace_ctx`` is not at its top level) the call
    is a piece of that program, made once a trace and timing nothing
    of a round, and runs its plain ``body(*args)`` inline and bare."""
    if not jax.core.trace_ctx.is_top_level():
        return body(*args)
    with trace.stage(name) as stage:
        out = dispatch(*args)
    with _STAGE_MU:
        acc = _STAGES[name]
        acc[0] += 1
        acc[1] += stage.seconds
    return out


def stage_counters() -> dict:
    """``{"podr2.challenge": {"n", "s", "programs"}, "podr2.coeffs":
    {...}}``: the top-level calls of ``gen_challenge`` /
    ``aggregate_coeffs`` in this process, the host seconds they took
    (the calls as the host sees them: each enqueues one compiled program
    and returns device arrays that may still be in flight) and the
    shapes their programs were compiled for (CHALLENGE_PROGRAM: one a
    geometry; COEFFS_PROGRAM: one a power of two of F; either: one more
    a device they were placed on). ``n`` rising while ``programs``
    stands is the mechanism at work; ``programs`` rising with ``n`` is
    a process that compiles in its rounds."""
    with _STAGE_MU:
        return {name: {"n": n, "s": s,
                       "programs": _PROGRAMS[name]._cache_size()}
                for name, (n, s) in _STAGES.items()}


def stage_metrics() -> dict[str, float]:
    """``stage_counters()`` as ``cess_podr2_challenge_seconds`` /
    ``_count`` / ``_programs`` and ``cess_podr2_coeffs_...``
    (node/metrics.py)."""
    out = {}
    for name, acc in stage_counters().items():
        short = name.partition(".")[2]
        out[f"cess_podr2_{short}_seconds"] = acc["s"]
        out[f"cess_podr2_{short}_count"] = float(acc["n"])
        out[f"cess_podr2_{short}_programs"] = float(acc["programs"])
    return out


def _seed_words(data: bytes) -> np.ndarray:
    """64-bit fold of round randomness -> [2] uint32. jax.random.key
    truncates its seed to 32 bits under x32, so a derivation seeds its
    key with the first word and takes the second in via fold_in."""
    return np.frombuffer(hashlib.sha256(data).digest()[:8],
                         dtype="<u4").astype(np.uint32)


def gen_challenge(seed_bytes: bytes | int, num_blocks: int,
                  count: int | None = None):
    """Derive (indices [c] int32, nu [c] uint32) from round randomness.

    Coverage mirrors audit's 46/1000 of chunks (SURVEY.md §3.3); the
    reference draws 20-byte randoms per index, here nu in F_p.
    The host keeps what is not arithmetic (the coverage rule, the
    seed's two words); the rest is CHALLENGE_PROGRAM, one compiled
    program a geometry — ``num_blocks`` and ``count`` its static
    arguments, the words its operand, so a new seed never compiles.
    A top-level call is the stage ``podr2.challenge`` (``_staged``).
    """
    if count is None:
        count = max(1, num_blocks * constants.CHALLENGE_RATE_NUM
                    // constants.CHALLENGE_RATE_DEN)
    if isinstance(seed_bytes, bytes):
        words = _seed_words(seed_bytes)
    else:
        words = np.array([int(seed_bytes) & 0xFFFFFFFF,
                          (int(seed_bytes) >> 32) & 0xFFFFFFFF], np.uint32)
    return _staged("podr2.challenge", _gen_challenge, CHALLENGE_PROGRAM,
                   words, num_blocks, count)


def _gen_challenge(words, num_blocks: int, count: int):
    """THE one definition of the challenge's arithmetic, over the seed's
    two words (host words or traced ones)."""
    key = jax.random.fold_in(jax.random.key(words[0]), words[1])
    k_idx, k_nu = jax.random.split(key)
    idx = jax.random.randint(k_idx, (count,), 0, num_blocks, dtype=jnp.int32)
    nu = pf.to_field(jax.random.bits(k_nu, (count,), jnp.uint32))
    return idx, nu


# jitted once for the process, as TAG_PROGRAM above and the round
# programs below: (words u32[2]) -> (idx i32[count], nu u32[count])
CHALLENGE_PROGRAM = jax.jit(_gen_challenge,
                            static_argnames=("num_blocks", "count"))


def prove_at(blocks_i, tags_i, nu):
    """The proof from the challenged blocks alone -> (mu [sectors],
    sigma [limbs]): nothing outside them enters it.

    blocks_i [c, ...] are the c challenged blocks in challenge order
    (a block named twice appears twice) — raw bytes uint8
    [c, sectors*2], or the same bytes already read as little-endian
    field elements [c, sectors] (uint16 from a host ``view``, uint32
    from fragment_to_elems); tags_i [c, limbs] their tags. THE one
    definition of the proof: ``prove`` is this after its gather, and
    the submission engine gathers on the host and ships only these.
    """
    m_i = (pf.pack_bytes(blocks_i) if blocks_i.dtype == jnp.uint8
           else blocks_i.astype(jnp.uint32))                 # [c, s]
    # m < 2^16 (pack_bytes width 2): data-side fast multiply
    mu = pf.summod(pf.mulmod_u16(m_i, nu[:, None]), axis=0)  # [s]
    sigma = pf.dotmod(nu[:, None], tags_i, axis=0)
    return mu, sigma


def prove(fragment, tags, idx, nu, sectors: int = SECTORS):
    """Miner-side proof for one fragment -> (mu [sectors], sigma [2]).

    Needs only public data: the fragment bytes and its tags [blocks, 2].
    """
    m = fragment_to_elems(fragment, sectors)       # [B, s]
    return prove_at(jnp.take(m, idx, axis=0),
                    jnp.take(tags, idx, axis=0), nu)


def prove_batch(fragments, tags, idx, nu, sectors: int = SECTORS):
    """[F, bytes], [F, blocks, 2] -> (mu [F, sectors], sigma [F, 2])."""
    return jax.vmap(lambda d, t: prove(d, t, idx, nu, sectors))(fragments, tags)


def aggregate_words(seed_bytes: bytes) -> np.ndarray:
    """The round seed's two aggregation key words [2] uint32: all of
    ``aggregate_coeffs`` that is not arithmetic. A verifier that derives
    r on the device (``round_fold``) ships these eight bytes a round."""
    return _seed_words(b"cess-podr2-agg:" + seed_bytes)


def _aggregate_key(words):
    """The aggregation PRF key from ``aggregate_words`` (host words or
    traced ones: the same two operations either way)."""
    return jax.random.fold_in(jax.random.key(words[0]), words[1])


def _coeffs(agg_key, ids):
    """r [F] for ids [F, 2] under the round's aggregation key."""
    def one(fid):
        k = jax.random.fold_in(jax.random.fold_in(agg_key, fid[0]), fid[1])
        return pf.to_field(jax.random.bits(k, (), jnp.uint32))

    return jax.vmap(one)(ids)


def aggregate_coeffs(seed_bytes: bytes, fragment_ids) -> jax.Array:
    """Per-fragment random linear-combination coefficients r[F] for
    cross-fragment proof aggregation, PRF-derived from the round seed
    and each fragment id — the prover cannot choose them.

    Aggregation (the SIGMA_MAX fix, runtime/src/lib.rs:992): instead
    of shipping (mu, sigma) PER fragment (O(F KiB) on the wire), the
    miner folds all its fragments into ONE (mu, sigma):

        mu_total    = sum_f r_f * mu_f
        sigma_total = sum_f r_f * sigma_f

    The Shacham-Waters verification equation is linear in (mu, sigma),
    so the TEE checks the fold against the fragment set the CHAIN says
    the miner owes — a constant-size proof regardless of F
    (PROOF_BYTES raw payload + constant codec framing; see the
    authoritative statement at PROOF_BYTES, framed total computed by
    node/offchain.py proof_wire_bytes).
    The host keeps ``aggregate_words``; the rest is COEFFS_PROGRAM over
    the ids padded with zero rows to the next power of two (a miner
    holds any F: one compiled program a power of two, not one an F),
    and ``r[:F]`` comes back. r is a per-row PRF, so a real row's r
    knows nothing of the pad.
    A top-level call is the stage ``podr2.coeffs`` (``_staged``).
    """
    return _staged("podr2.coeffs", _aggregate_coeffs, _coeffs_dispatch,
                   aggregate_words(seed_bytes), fragment_ids)


def _aggregate_coeffs(words, fragment_ids) -> jax.Array:
    return _coeffs(_aggregate_key(words),
                   jnp.asarray(fragment_ids).reshape(-1, 2))


# jitted once for the process: (words u32[2], ids u32[F', 2]) -> r u32[F']
COEFFS_PROGRAM = jax.jit(_aggregate_coeffs)
# the round derivations' programs by stage (stage_counters)
_PROGRAMS = {"podr2.challenge": CHALLENGE_PROGRAM,
             "podr2.coeffs": COEFFS_PROGRAM}


def _rows_bucket(rows: int) -> int:
    """The power of two that ``rows`` pads to (1 for none): the row
    count a program of this module is compiled for."""
    return 1 << max(rows - 1, 0).bit_length()


def _coeffs_dispatch(words, fragment_ids) -> jax.Array:
    """COEFFS_PROGRAM over host ids [F, 2], zero rows up to the next
    power of two; the first F of its r."""
    ids = np.asarray(fragment_ids).reshape(-1, 2)
    f = len(ids)
    padded = np.zeros((_rows_bucket(f), 2), np.uint32)
    padded[:f] = ids
    r = COEFFS_PROGRAM(words, padded)
    return r if f == len(padded) else r[:f]


def _fold_proofs(mu_f, sigma_f, r):
    """Per-fragment proofs (mu [F, sectors], sigma [F, limbs]) folded
    by r [F] into the one aggregated proof (see aggregate_coeffs)."""
    mu = pf.summod(pf.mulmod(r[:, None], mu_f), axis=0)
    sigma = pf.dotmod(r[:, None], sigma_f, axis=0)
    return mu, sigma


def prove_aggregate(fragments, tags, idx, nu, r, sectors: int = SECTORS):
    """[F, bytes], [F, blocks, 2], r [F] -> (mu [sectors], sigma [2]).

    The constant-size aggregated proof across all of a miner's
    challenged fragments (see aggregate_coeffs)."""
    return _fold_proofs(*prove_batch(fragments, tags, idx, nu, sectors), r)


def prove_aggregate_at(blocks_i, tags_i, nu, r):
    """prove_aggregate from the challenged blocks alone: blocks_i
    [F, c, ...] and tags_i [F, c, limbs] as prove_at takes them per
    fragment, r [F] -> (mu [sectors], sigma [limbs])."""
    return _fold_proofs(*jax.vmap(
        lambda b, t: prove_at(b, t, nu))(blocks_i, tags_i), r)


# -- a miner's round over what it holds -----------------------------------
#
# A storage miner answers a round over its whole frozen owed set: one
# largest deal's share is SEGMENT_COUNT_MAX = 1,000 fragments of 8 MiB
# (7.8 GiB of host memory), of which the round reads 753 blocks of 512 B
# a fragment. The fragments stay where the miner holds them; what the
# round reads is gathered on the host PROVE_CHUNK fragments at a time
# into two reused buffers, put, and folded into a running (mu, sigma) on
# the device, the gather of the next chunk running while the last one
# goes up and folds. The fold is linear mod p over canonical residues,
# so the chunks' sums are the whole set's, bit for bit. The chunk is the
# only shape past PROVE_CHUNK fragments: custody that grows compiles
# nothing new.
PROVE_CHUNK = 64        # fragments a device step takes: 24 MiB of
                        # challenged blocks at the protocol's geometry
COEFF_ROWS = 1024       # ids a call of COEFFS_PROGRAM takes of a
                        # miner's round past that many (round_coeffs)


class HeldRows(tuple):
    """A miner's fragments (or their tags) as they lie: one array a
    fragment, each still wherever the miner holds it (a view of a
    store's ``bytes``), where ``[F, ...]`` would have cost a copy of the
    store to build. It answers ``shape`` / ``nbytes`` as that array
    would."""

    @property
    def shape(self) -> tuple:
        return (len(self),) + self[0].shape

    @property
    def nbytes(self) -> int:
        return sum(row.nbytes for row in self)


def held_rows(data, dtype, ndim: int):
    """``data`` as the prover takes it: one contiguous array
    ``[F, ...]`` of ``ndim`` dimensions, or — kept so, nothing stacked —
    a non-empty sequence of F arrays of one shape (``HeldRows``)."""
    if isinstance(data, (list, tuple)):
        rows = HeldRows(np.ascontiguousarray(np.asarray(a, dtype=dtype))
                        for a in data)
        if not rows or rows[0].ndim != ndim - 1 \
                or len({a.shape for a in rows}) != 1:
            raise ValueError(f"expected a sequence of equal arrays of "
                             f"{ndim - 1} dimensions")
        return rows
    arr = np.ascontiguousarray(np.asarray(data, dtype=dtype))
    if arr.ndim != ndim:
        raise ValueError(f"expected an array of {ndim} dimensions, got "
                         f"{arr.shape}")
    return arr


def chunk_plan(rows: int) -> tuple[int, int]:
    """(fragments a device step, steps) for a held set of ``rows``: the
    power-of-two bucket in one step up to PROVE_CHUNK, whole chunks
    past it."""
    if rows <= PROVE_CHUNK:
        return _rows_bucket(rows), 1
    return PROVE_CHUNK, -(-rows // PROVE_CHUNK)


def gather_challenged(fragments, tags, lo: int, hi: int, idx,
                      blocks_out, tags_out) -> int:
    """The challenged blocks of fragments ``lo .. hi - 1`` and their tag
    rows, read on the host from where the fragments lie into the first
    ``hi - lo`` rows of ``blocks_out [n, c, sectors]`` uint16 (the
    bytes read as little-endian field elements, pfield.pack_bytes'
    embedding: a free view on a little-endian host) and ``tags_out
    [n, c, limbs]``. ``idx`` was range-checked by the caller. Returns
    the bytes gathered."""
    n, sectors = hi - lo, blocks_out.shape[-1]
    if isinstance(fragments, HeldRows):
        for i in range(n):
            np.take(fragments[lo + i].view("<u2").reshape(-1, sectors),
                    idx, axis=0, out=blocks_out[i], mode="clip")
    else:
        # mode="clip": unbuffered writes into the batch
        np.take(fragments[lo:hi].view("<u2").reshape(n, -1, sectors),
                idx, axis=1, out=blocks_out[:n], mode="clip")
    if isinstance(tags, HeldRows):
        for i in range(n):
            np.take(tags[lo + i], idx, axis=0, out=tags_out[i],
                    mode="clip")
    else:
        np.take(tags[lo:hi], idx, axis=1, out=tags_out[:n], mode="clip")
    return blocks_out[:n].nbytes + tags_out[:n].nbytes


def fold_chunks(chunks: int, bufs, fill, call,
                stage=lambda name: contextlib.nullcontext()):
    """The host loop of a chunked prove. Step j fills ``bufs[j % 2]`` on
    the host (``fill(j, buf)``: the gather) and calls the device program
    on it (``call(acc, buf) -> acc``, ``acc`` None at the first step: an
    enqueue, the buffer's put and the fold run behind it), so the gather
    of chunk j + 1 runs while chunk j goes up and folds. A buffer is
    filled again only once the step that read it has finished. Returns
    the last ``acc``, still in flight; ``stage(name)`` wraps the
    ``assemble`` / ``dispatch`` / ``wait`` parts of every step."""
    acc, reader = None, [None] * len(bufs)
    for j in range(chunks):
        slot = j % len(bufs)
        if reader[slot] is not None:
            with stage("wait"):
                jax.block_until_ready(reader[slot])
        with stage("assemble"):
            fill(j, bufs[slot])
        with stage("dispatch"):
            acc = call(acc, bufs[slot])
        reader[slot] = acc
    return acc


def prove_buffers(shape: tuple, count: int) -> list:
    """``count`` host buffers of one device step: (blocks ``[..., fb, c,
    sectors]`` uint16, tags ``[..., fb, c, limbs]``, r ``[..., fb]``)
    for ``shape = (*lead, fb, c, sectors, limbs)``."""
    *lead, c, sectors, limbs = shape
    return [(np.zeros((*lead, c, sectors), np.uint16),
             np.zeros((*lead, c, limbs), np.uint32),
             np.zeros(tuple(lead), np.uint32)) for _ in range(count)]


def prove_step_at(mu, sigma, blocks_i, tags_i, nu, r):
    """A later step of a chunked prove: the running (mu [sectors],
    sigma [limbs]) plus ``prove_aggregate_at`` of one more chunk. The
    sum is over canonical residues mod p, so the steps' total is the
    one-step proof, bit for bit."""
    mu_c, sigma_c = prove_aggregate_at(blocks_i, tags_i, nu, r)
    return pf.addmod(mu, mu_c), pf.addmod(sigma, sigma_c)


# the engine-less prover's two programs, one executable a chunk shape
_HELD_FIRST = jax.jit(prove_aggregate_at)
_HELD_STEP = jax.jit(prove_step_at)


def prove_held(fragments, tags, idx, nu, r):
    """``prove_aggregate`` over a held set without the engine and
    without a copy of it: fragments ``[F, bytes]`` or a sequence of F
    byte arrays, tags likewise, the challenge and r ``[F]`` as host
    arrays -> (mu [sectors], sigma [limbs]) in flight. Bit-identical to
    ``prove_aggregate``: only the challenged blocks go to the device,
    ``chunk_plan`` fragments a step (pad rows carry r = 0: exact modular
    zeros)."""
    fragments = held_rows(fragments, np.uint8, 2)
    tags = held_rows(tags, np.uint32, 3)
    idx = np.asarray(idx)
    nu = np.asarray(nu, dtype=np.uint32)
    r = np.asarray(r, dtype=np.uint32)
    fb, chunks = chunk_plan(len(r))
    bufs = prove_buffers((fb, len(idx), fragments.shape[-1]
                          // (tags.shape[1] * pf.BYTES_PER_ELEM),
                          tags.shape[2]), min(chunks, 2))

    def fill(j, buf):
        lo, hi = j * fb, min((j + 1) * fb, len(r))
        gather_challenged(fragments, tags, lo, hi, idx, buf[0], buf[1])
        buf[2][:hi - lo] = r[lo:hi]
        buf[2][hi - lo:] = 0

    def call(acc, buf):
        blocks_i, tags_i, rs = buf
        if acc is None:
            return _HELD_FIRST(blocks_i, tags_i, nu, rs)
        return _HELD_STEP(*acc, blocks_i, tags_i, nu, rs)

    return fold_chunks(chunks, bufs, fill, call)


def round_coeffs(seed_bytes: bytes, fragment_ids) -> np.ndarray:
    """``aggregate_coeffs`` for a miner's round, as host words [F]:
    COEFFS_PROGRAM over the ids in calls of fixed shapes — the next
    power of two up to COEFF_ROWS (the executables ``aggregate_coeffs``
    uses), COEFF_ROWS ids a call past it — and the pad cut off on the
    host, so a held set that grows compiles nothing past COEFF_ROWS and
    no slice program at any F. One stage ``podr2.coeffs`` a call."""
    ids = np.asarray(fragment_ids, dtype=np.uint32).reshape(-1, 2)

    def dispatch(words, ids):
        f = len(ids)
        piece = min(_rows_bucket(f), COEFF_ROWS)
        padded = np.zeros((-(-f // piece) * piece, 2), np.uint32)
        padded[:f] = ids
        parts = [COEFFS_PROGRAM(words, padded[at:at + piece])
                 for at in range(0, len(padded), piece)]
        return np.concatenate([np.asarray(p) for p in parts])[:f]

    return _staged("podr2.coeffs", _aggregate_coeffs, dispatch,
                   aggregate_words(seed_bytes), ids)


def verify_aggregate(key: Podr2Key, fragment_ids, num_blocks: int,
                     idx, nu, r, mu, sigma):
    """TEE-side check of an aggregated proof against the owed fragment
    set (ids [F, 2]). Returns a scalar bool — true only when BOTH
    F_p^2 limb equations hold (soundness ~p^-2, see module doc)."""
    ids = jnp.asarray(fragment_ids).reshape(-1, 2)
    f_i = jax.vmap(
        lambda i: prf_elems_at(key.prf_key, i, idx,
                               key.limbs))(ids)       # [F, c, limbs]
    lhs_f = jax.vmap(
        lambda f: pf.dotmod(nu[:, None], f, axis=0))(f_i)       # [F, limbs]
    lhs = pf.addmod(pf.dotmod(r[:, None], lhs_f, axis=0),
                    pf.dotmod(key.alpha, mu[:, None], axis=0))
    return jnp.all(lhs == jnp.asarray(sigma))


# -- a round's missions judged together ----------------------------------
#
# A TEE worker's verify queue holds up to VERIFY_MISSION_MAX = 500
# missions a round (runtime/src/lib.rs:990), their owed sets ragged by
# miner size (29 to 14,721 fragments in the benchmark's round of
# 100,000). Stacked [missions, F-bucket] they would need a program per
# pair of buckets and a PRF intermediate of gigabytes; here the owed
# fragments of every mission lie FLAT, one row each with the index of
# its mission, and one program folds them into [missions, limbs] a
# fixed number of rows at a time. Its shape knows the mission bucket
# and nothing of how the sizes spread.
ROUND_ROWS = 16384      # flat rows handed to one call of the fold
ROUND_SUB = 512         # rows per loop step: bounds the PRF intermediate
                        # ([512, 753, 2] uint32 = 3 MiB at the protocol's)


def mission_buckets(missions: int):
    """The mission counts a round's programs are shaped for, up to the
    one that holds ``missions``: 8, 64, 512, 4096... (three shapes up
    to the protocol's cap). A pad mission costs a row of the fold's
    mask and one zero equation."""
    b = 8
    while b < missions:
        yield b
        b <<= 3
    yield b


def mission_bucket(missions: int) -> int:
    """The bucket a round of ``missions`` missions runs in."""
    *_, bucket = mission_buckets(missions)
    return bucket


def round_fold(key: Podr2Key, agg_words, ids, seg, steps, acc, idx, nu):
    """acc [missions, limbs] += per mission m:
    sum_{rows f: seg[f] == m} r_f * sum_i nu_i * f_k(id_f, I_i)
    over the first ``steps * ROUND_SUB`` of the flat rows ids [rows, 2].

    ``seg`` [rows] int32 is each row's mission; pad rows carry -1 and
    are in none. ``steps`` is an operand (a loop bound, not a shape),
    so a short round pays for its own rows only. r_f is derived HERE
    from the round's aggregation key words (``aggregate_words``) and
    the row's id, bit for bit ``aggregate_coeffs``: the verifier needs
    no r from outside. Every row's PRF is evaluated at every challenged
    block, both limbs, whatever its mission's size."""
    agg_key = _aggregate_key(agg_words)
    slots = jnp.arange(acc.shape[0], dtype=jnp.int32)

    def step(i, acc):
        ids_s = jax.lax.dynamic_slice_in_dim(ids, i * ROUND_SUB, ROUND_SUB)
        seg_s = jax.lax.dynamic_slice_in_dim(seg, i * ROUND_SUB, ROUND_SUB)
        f_i = jax.vmap(lambda fid: prf_elems_at(
            key.prf_key, fid, idx, key.limbs))(ids_s)    # [sub, c, limbs]
        lhs_f = jax.vmap(
            lambda f: pf.dotmod(nu[:, None], f, axis=0))(f_i)
        v = pf.mulmod(_coeffs(agg_key, ids_s)[:, None], lhs_f)
        mine = seg_s[:, None] == slots[None, :]         # [sub, missions]
        part = pf.summod(jnp.where(mine[:, :, None], v[:, None, :],
                                   jnp.uint32(0)), axis=0)
        return pf.addmod(acc, part)

    return jax.lax.fori_loop(0, steps, step, acc)


def round_close(alpha, acc, mu, sigma):
    """bool [missions]: acc + sum_j alpha_j * mu_j == sigma, BOTH limb
    equations, for proofs mu [missions, sectors], sigma [missions,
    limbs]. A pad mission (all zeros) holds; the caller slices it off."""
    rhs = jax.vmap(lambda u: pf.dotmod(alpha, u[:, None], axis=0))(mu)
    return jnp.all(pf.addmod(acc, rhs) == sigma, axis=-1)


def _round_fold_program(ids, seg, steps, acc, idx, nu, agg_words, alpha,
                        prf_key_data, *, prf_impl: str):
    key = Podr2Key(alpha, jax.random.wrap_key_data(prf_key_data,
                                                   impl=prf_impl))
    return round_fold(key, agg_words, ids, seg, steps, acc, idx, nu)


# jitted once for the process: the round (idx, nu, the aggregation
# words), the chunk and the key are operands, so one executable a
# mission bucket serves every round, key and spread of sizes
ROUND_FOLD = jax.jit(_round_fold_program, static_argnames=("prf_impl",))
ROUND_CLOSE = jax.jit(round_close)


@dataclasses.dataclass(frozen=True)
class RoundRows:
    """A round's missions laid out for the fold (host arrays): the owed
    sets and nothing of the proofs, which only the close reads
    (``round_proofs``), so the folds can be on the device before a
    proof is decoded."""
    ids: np.ndarray         # [calls * ROUND_ROWS, 2] uint32, zero pad
    seg: np.ndarray         # [calls * ROUND_ROWS] int32, -1 on pad rows
    steps: tuple            # loop steps of each call
    missions: int           # real missions (the first of the bucket)
    rows: int               # real rows

    @property
    def bucket(self) -> int:
        """The mission bucket the round's programs are shaped for."""
        return mission_bucket(self.missions)

    @property
    def rows_issued(self) -> int:
        """Rows whose PRF the fold evaluates: the real ones and the
        pad of each call's last step."""
        return sum(self.steps) * ROUND_SUB


def round_rows(ids, sizes) -> RoundRows:
    """Lay out missions for the fold: ids [T, 2] in mission order,
    sizes [M] (every one >= 1, summing to T)."""
    sizes = np.asarray(sizes, dtype=np.int64)
    missions, total = len(sizes), int(sizes.sum())
    if missions < 1 or sizes.min() < 1 or total != len(ids):
        raise ValueError("expected ids [T, 2] and sizes [M] >= 1 "
                         "summing to T")
    calls = -(-total // ROUND_ROWS)
    ids_pad = np.zeros((calls * ROUND_ROWS, 2), dtype=np.uint32)
    ids_pad[:total] = ids
    seg = np.full(calls * ROUND_ROWS, -1, dtype=np.int32)
    seg[:total] = np.repeat(np.arange(missions, dtype=np.int32), sizes)
    steps = tuple(-(-min(ROUND_ROWS, total - c * ROUND_ROWS) // ROUND_SUB)
                  for c in range(calls))
    return RoundRows(ids_pad, seg, steps, missions, total)


def round_proofs(mu, sigma, bucket: int) -> tuple:
    """The missions' proofs as the close takes them: mu [M, sectors]
    and sigma [M, limbs] padded with zero proofs to the mission bucket
    (a pad mission's equation is 0 + 0 == 0)."""
    mu_pad = np.zeros((bucket,) + mu.shape[1:], dtype=np.uint32)
    mu_pad[:len(mu)] = mu
    sigma_pad = np.zeros((bucket,) + sigma.shape[1:], dtype=np.uint32)
    sigma_pad[:len(sigma)] = sigma
    return mu_pad, sigma_pad


def key_operands(key: Podr2Key) -> tuple:
    """The key as the round programs take it: (alpha, the PRF key's raw
    words) as host arrays and the PRF's implementation name. Host
    words, so the call is placed wherever its caller places it."""
    return (np.asarray(key.alpha),
            np.asarray(jax.random.key_data(key.prf_key)),
            str(jax.random.key_impl(key.prf_key)))


def round_folds(key_ops: tuple, rows: RoundRows, idx, nu, agg_words):
    """Enqueue the round's folds, one a ROUND_ROWS rows: everything of
    a round that needs no proof. Returns the device's accumulator
    [bucket, limbs]; nothing is waited for."""
    alpha, prf_key_data, prf_impl = key_ops
    acc = np.zeros((rows.bucket, alpha.shape[1]), dtype=np.uint32)
    for c, steps in enumerate(rows.steps):
        at = slice(c * ROUND_ROWS, (c + 1) * ROUND_ROWS)
        acc = ROUND_FOLD(rows.ids[at], rows.seg[at], np.int32(steps), acc,
                         idx, nu, agg_words, alpha, prf_key_data,
                         prf_impl=prf_impl)
    return acc


def round_verdicts(key_ops: tuple, acc, mu, sigma):
    """Enqueue the round's close over the folds' accumulator and the
    missions' proofs mu [M, sectors], sigma [M, limbs], M up to the
    accumulator's bucket. Returns the device's bool [bucket]; nothing
    is waited for."""
    return ROUND_CLOSE(key_ops[0], acc,
                       *round_proofs(mu, sigma, acc.shape[0]))


def round_dispatch(key_ops: tuple, rows: RoundRows, idx, nu, agg_words,
                   mu, sigma):
    """The whole round for a caller with its proofs in hand: the folds,
    then the close."""
    return round_verdicts(
        key_ops, round_folds(key_ops, rows, idx, nu, agg_words), mu, sigma)


def warm_round(key_ops: tuple, challenged: int, bucket: int):
    """Run the fold and the close of one mission bucket over zeros, for
    rounds of ``challenged`` blocks: after it such a round compiles
    nothing, whatever its sizes. Returns the device's result."""
    sectors, limbs = key_ops[0].shape
    rows = RoundRows(
        ids=np.zeros((ROUND_ROWS, 2), np.uint32),
        seg=np.full(ROUND_ROWS, -1, np.int32), steps=(1,),
        missions=bucket, rows=0)
    return round_dispatch(key_ops, rows, np.zeros((challenged,), np.int32),
                          np.zeros((challenged,), np.uint32),
                          np.zeros((2,), np.uint32),
                          np.zeros((bucket, sectors), np.uint32),
                          np.zeros((bucket, limbs), np.uint32))


def verify_from_f(alpha, f, idx, nu, mu, sigma):
    """The verification equation given precomputed PRF values
    f [blocks, 2] (shared by single-device verify and the sharded mesh
    step). Both limb equations must hold."""
    lhs = pf.dotmod(nu[:, None], jnp.take(f, idx, axis=0), axis=0)   # [2]
    rhs = pf.dotmod(alpha, mu[:, None], axis=0)                      # [2]
    return jnp.all(pf.addmod(lhs, rhs) == sigma)


def verify(key: Podr2Key, fragment_id, num_blocks: int, idx, nu, mu, sigma):
    """TEE-side check; returns bool[] (scalar) per call — vmap for
    batches. Evaluates the PRF only at the challenged blocks
    (prf_elems_at), the verifier fast path."""
    f_i = prf_elems_at(key.prf_key, fragment_id, idx, key.limbs)
    lhs = pf.dotmod(nu[:, None], f_i, axis=0)
    rhs = pf.dotmod(key.alpha, mu[:, None], axis=0)
    return jnp.all(pf.addmod(lhs, rhs) == jnp.asarray(sigma))


def verify_batch(key: Podr2Key, fragment_ids, num_blocks: int, idx, nu, mu, sigma):
    """ids [F, 2] hash word pairs (or [F] scalar ids), mu [F, sectors],
    sigma [F, limbs] -> bool [F]."""
    return jax.vmap(
        lambda i, u, s: verify(key, i, num_blocks, idx, nu, u, s)
    )(fragment_ids, mu, sigma)
