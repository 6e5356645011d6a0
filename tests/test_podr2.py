"""PoDR2 scheme tests: completeness, soundness smoke, batching, oracle parity."""
import numpy as np
import pytest

import jax.numpy as jnp

from cess_tpu.ops import pfield as pf
from cess_tpu.ops import podr2

FRAG_BYTES = 4 * podr2.BLOCK_BYTES * 4  # 16 blocks, small for tests


def make_fragments(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, 256, (n, FRAG_BYTES), dtype=np.uint8)


def test_tag_shapes_and_determinism():
    key = podr2.Podr2Key.generate(42)
    frags = make_fragments(3)
    ids = jnp.arange(3)
    tags = podr2.tag_fragments(key, ids, frags)
    blocks = podr2.Podr2Params().blocks_for(FRAG_BYTES)
    assert tags.shape == (3, blocks, podr2.LIMBS)
    tags2 = podr2.tag_fragments(key, ids, frags)
    np.testing.assert_array_equal(np.asarray(tags), np.asarray(tags2))
    # different key -> different tags
    key2 = podr2.Podr2Key.generate(43)
    assert not np.array_equal(np.asarray(tags),
                              np.asarray(podr2.tag_fragments(key2, ids, frags)))


def test_completeness_honest_proof_verifies():
    key = podr2.Podr2Key.generate(7)
    frags = make_fragments(4, seed=1)
    ids = jnp.arange(4)
    tags = podr2.tag_fragments(key, ids, frags)
    blocks = tags.shape[1]
    idx, nu = podr2.gen_challenge(b"round-1-randomness", blocks)
    mu, sigma = podr2.prove_batch(jnp.asarray(frags), tags, idx, nu)
    ok = podr2.verify_batch(key, ids, blocks, idx, nu, mu, sigma)
    assert bool(np.all(np.asarray(ok))), "honest proofs must verify"


def test_soundness_corrupted_data_fails():
    key = podr2.Podr2Key.generate(7)
    frags = make_fragments(2, seed=2)
    ids = jnp.arange(2)
    tags = podr2.tag_fragments(key, ids, frags)
    blocks = tags.shape[1]
    idx, nu = podr2.gen_challenge(b"round-2", blocks)
    corrupted = frags.copy()
    # flip one byte inside a challenged block
    target_block = int(np.asarray(idx)[0])
    corrupted[0, target_block * podr2.BLOCK_BYTES] ^= 0xFF
    mu, sigma = podr2.prove_batch(jnp.asarray(corrupted), tags, idx, nu)
    ok = np.asarray(podr2.verify_batch(key, ids, blocks, idx, nu, mu, sigma))
    assert not ok[0], "proof over corrupted data must fail"
    assert ok[1], "untouched fragment still verifies"


def test_soundness_wrong_sigma_and_replay():
    key = podr2.Podr2Key.generate(9)
    frags = make_fragments(1, seed=3)
    ids = jnp.arange(1)
    tags = podr2.tag_fragments(key, ids, frags)
    blocks = tags.shape[1]
    idx, nu = podr2.gen_challenge(b"round-3", blocks)
    mu, sigma = podr2.prove_batch(jnp.asarray(frags), tags, idx, nu)
    bad_sigma = pf.addmod(sigma, jnp.ones_like(sigma))
    ok = podr2.verify_batch(key, ids, blocks, idx, nu, mu, bad_sigma)
    assert not bool(np.asarray(ok)[0])
    # replaying the same proof against a different round's challenge fails
    idx2, nu2 = podr2.gen_challenge(b"round-4", blocks)
    ok2 = podr2.verify_batch(key, ids, blocks, idx2, nu2, mu, sigma)
    assert not bool(np.asarray(ok2)[0])


def test_soundness_each_limb_rejects_independently():
    """The F_p^2 check is two independently-keyed base-field equations;
    a forged sigma satisfying ONE limb but not the other must fail —
    i.e. acceptance requires both, giving the ~p^-2 = 2^-62 bound
    (VERDICT r3 Weak #2 fix)."""
    key = podr2.Podr2Key.generate(21)
    frags = make_fragments(1, seed=9)
    ids = jnp.arange(1)
    tags = podr2.tag_fragments(key, ids, frags)
    blocks = tags.shape[1]
    idx, nu = podr2.gen_challenge(b"limb-round", blocks)
    mu, sigma = podr2.prove_batch(jnp.asarray(frags), tags, idx, nu)
    good = np.asarray(sigma)          # [1, 2]
    for limb in range(podr2.LIMBS):
        forged = good.copy()
        forged[0, limb] = (forged[0, limb] + 1) % pf.P
        ok = podr2.verify_batch(key, ids, blocks, idx, nu, mu,
                                jnp.asarray(forged))
        assert not bool(np.asarray(ok)[0]), \
            f"sigma valid in the other limb but forged in limb {limb} passed"
    ok = podr2.verify_batch(key, ids, blocks, idx, nu, mu, sigma)
    assert bool(np.asarray(ok)[0])


def test_hash_derived_fragment_ids():
    """Hash-pair ids: unique per fragment, full 64-bit fold, batchable."""
    import jax.numpy as jnp

    key = podr2.Podr2Key.generate(3)
    frags = make_fragments(2, seed=8)
    h1, h2 = b"\xaa" * 32, (b"\xbb" * 8 + b"\xaa" * 24)
    ids = jnp.asarray(np.stack([podr2.fragment_id_from_hash(h1),
                                podr2.fragment_id_from_hash(h2)]))
    tags = podr2.tag_fragments(key, ids, frags)
    blocks = tags.shape[1]
    idx, nu = podr2.gen_challenge(b"hash-id-round", blocks)
    mu, sigma = podr2.prove_batch(jnp.asarray(frags), tags, idx, nu)
    ok = podr2.verify_batch(key, ids, blocks, idx, nu, mu, sigma)
    assert bool(np.all(np.asarray(ok)))
    # ids differing only in the HIGH word must produce different tags
    h3 = b"\xaa" * 4 + b"\xcc" * 4 + b"\xaa" * 24
    id3 = jnp.asarray(podr2.fragment_id_from_hash(h3)[None])
    tags3 = podr2.tag_fragments(key, id3, frags[:1])
    assert not np.array_equal(np.asarray(tags[:1]), np.asarray(tags3))


def test_proof_size_within_chain_cap():
    from cess_tpu.constants import SIGMA_MAX

    assert podr2.PROOF_BYTES <= SIGMA_MAX


def test_aggregate_proof_completeness_and_soundness():
    """Cross-fragment aggregation: one (mu, sigma) proves many
    fragments; omitting or corrupting any owed fragment fails."""
    key = podr2.Podr2Key.generate(11)
    frags = make_fragments(5, seed=6)
    hashes = [bytes([i]) * 32 for i in range(5)]
    ids = jnp.asarray(np.stack([podr2.fragment_id_from_hash(h)
                                for h in hashes]))
    tags = podr2.tag_fragments(key, ids, frags)
    blocks = tags.shape[1]
    seed = b"agg-round-randomness"
    idx, nu = podr2.gen_challenge(seed, blocks)
    r = podr2.aggregate_coeffs(seed, ids)
    mu, sigma = podr2.prove_aggregate(jnp.asarray(frags), tags, idx, nu, r)
    assert bool(np.asarray(podr2.verify_aggregate(
        key, ids, blocks, idx, nu, r, mu, sigma)))
    # dropping one owed fragment from the fold fails verification
    mu4, sigma4 = podr2.prove_aggregate(jnp.asarray(frags[:4]), tags[:4],
                                        idx, nu, r[:4])
    assert not bool(np.asarray(podr2.verify_aggregate(
        key, ids, blocks, idx, nu, r, mu4, sigma4)))
    # corrupting a challenged byte of any fragment fails
    bad = frags.copy()
    bad[2, int(np.asarray(idx)[0]) * podr2.BLOCK_BYTES] ^= 1
    mu_b, sigma_b = podr2.prove_aggregate(jnp.asarray(bad), tags, idx, nu, r)
    assert not bool(np.asarray(podr2.verify_aggregate(
        key, ids, blocks, idx, nu, r, mu_b, sigma_b)))


def test_aggregate_proof_wire_size_constant():
    """The codec-encoded aggregated proof stays under SIGMA_MAX no
    matter how many fragments it covers (VERDICT Weak #3 fix)."""
    from cess_tpu import codec
    from cess_tpu.constants import SIGMA_MAX
    from cess_tpu.node.offchain import Proof, build_proof

    key = podr2.Podr2Key.generate(12)
    sizes = []
    for count in (1, 50):
        frags = make_fragments(count, seed=13)
        hashes = [bytes([i % 256]) * 16 + i.to_bytes(16, "little")
                  for i in range(count)]
        ids = jnp.asarray(np.stack([podr2.fragment_id_from_hash(h)
                                    for h in hashes]))
        tags = np.asarray(podr2.tag_fragments(key, ids, frags))
        store = {h: frags[i].tobytes() for i, h in enumerate(hashes)}
        tagmap = {h: tags[i] for i, h in enumerate(hashes)}
        blob = build_proof(b"size-round", sorted(hashes), store, tagmap)
        assert isinstance(blob, bytes) and len(blob) <= SIGMA_MAX
        proof = codec.decode(blob)
        assert isinstance(proof, Proof)
        sizes.append(len(blob))
    assert sizes[0] == sizes[1], "proof size must not grow with F"
    # the authoritative size statement (podr2.PROOF_BYTES + constant
    # codec framing, r06 satellite) matches the real wire bytes
    from cess_tpu.node.offchain import proof_wire_bytes

    assert sizes[0] == proof_wire_bytes()
    assert proof_wire_bytes() - podr2.PROOF_BYTES == 26


def test_tag_oracle_parity_numpy_bigint():
    """Tag math matches a bigint reference implementation exactly."""
    key = podr2.Podr2Key.generate(5)
    frag = make_fragments(1, seed=4)[0]
    tags = np.asarray(podr2.tag_fragment(key, 0, frag))
    alpha = np.asarray(key.alpha)
    m = np.asarray(podr2.fragment_to_elems(jnp.asarray(frag)))
    f = np.asarray(podr2.prf_elems(key.prf_key, 0, m.shape[0]))
    for b in range(m.shape[0]):
        for limb in range(podr2.LIMBS):
            want = (int(f[b, limb])
                    + sum(int(a) * int(x)
                          for a, x in zip(alpha[:, limb], m[b]))) % pf.P
            assert int(tags[b, limb]) == want


def test_audit_backend_gate():
    """The AuditBackend half of the north-star trait pair: cpu default
    and device-pinned variants compute IDENTICAL results (platform
    determinism is a protocol invariant)."""
    import numpy as np

    from cess_tpu.ops import podr2
    from cess_tpu.ops.audit_backend import make_audit_backend

    key = podr2.Podr2Key.generate(3)
    cpu = make_audit_backend(key, "cpu")
    auto = make_audit_backend(key, "auto")
    rng = np.random.default_rng(0)
    frags = rng.integers(0, 256, (4, 2048), dtype=np.uint8)
    ids = np.arange(4, dtype=np.uint32)
    blocks = 2048 // podr2.BLOCK_BYTES
    tags_a = np.asarray(cpu.tag_fragments(ids, frags))
    tags_b = np.asarray(auto.tag_fragments(ids, frags))
    assert np.array_equal(tags_a, tags_b)
    idx, nu = cpu.gen_challenge(b"round", blocks)
    mu, sigma = cpu.prove_batch(frags, tags_a, idx, nu)
    ok = np.asarray(cpu.verify_batch(ids, blocks, idx, nu, mu, sigma))
    assert ok.all()
    # aggregated constant-size proof path
    ids2 = np.stack([ids, np.zeros(4, np.uint32)], axis=1)
    r = cpu.aggregate_coeffs(b"round", ids2)
    mu_t, sg_t = cpu.prove_aggregate(frags, tags_a, idx, nu, r)
    assert bool(np.asarray(cpu.verify_aggregate(
        ids2, blocks, idx, nu, r, mu_t, sg_t)))
    import pytest

    with pytest.raises(ValueError, match="unknown AuditBackend"):
        make_audit_backend(key, "quantum")


@pytest.mark.parametrize("limbs", [2, 3])
def test_limb_count_parametrized(limbs):
    """VERDICT r4 Weak #5 / Next #8: LIMBS is a measured option —
    limbs=2 (~2^-62) is the default, limbs=3 (~2^-93) a config knob.
    Completeness, single-limb forgery rejection, and aggregation all
    hold at either width."""
    params = podr2.Podr2Params(limbs=limbs)
    key = podr2.Podr2Key.generate(11, params)
    assert key.limbs == limbs
    frags = make_fragments(4, seed=9)
    ids = jnp.arange(4)
    tags = podr2.tag_fragments(key, ids, frags)
    blocks = tags.shape[1]
    assert tags.shape == (4, blocks, limbs)
    idx, nu = podr2.gen_challenge(b"limb-round", blocks)
    mu, sigma = podr2.prove_batch(jnp.asarray(frags), tags, idx, nu)
    assert sigma.shape == (4, limbs)
    ok = podr2.verify_batch(key, ids, blocks, idx, nu, mu, sigma)
    assert np.asarray(ok).all()
    # a sigma forged in ONE limb must fail (each limb is an
    # independent MAC equation; all must hold)
    for limb in range(limbs):
        bad = np.asarray(sigma).copy()
        bad[0, limb] = (bad[0, limb] + 1) % pf.P
        ok = podr2.verify_batch(key, ids, blocks, idx, nu, mu,
                                jnp.asarray(bad))
        assert not np.asarray(ok)[0]
        assert np.asarray(ok)[1:].all()
    # aggregated proof round-trips at this width too
    r = podr2.aggregate_coeffs(b"limb-agg", np.stack(
        [np.asarray(ids, np.uint32), np.zeros(4, np.uint32)], axis=1))
    mu_a, sigma_a = podr2.prove_aggregate(jnp.asarray(frags), tags,
                                          idx, nu, r)
    ids2 = np.stack([np.asarray(ids, np.uint32),
                     np.zeros(4, np.uint32)], axis=1)
    assert np.asarray(podr2.verify_aggregate(
        key, ids2, blocks, idx, nu, r, mu_a, sigma_a))


@pytest.mark.parametrize("limbs", [2, 3])
def test_offchain_proof_wire_respects_limb_width(limbs):
    """Review finding (r05, fixed): build_proof hardwired a 2-limb
    sigma and TeeAgent._verify required len == module LIMBS, so a
    limbs=3 deployment failed every honest audit. The wire layer now
    derives the width from the TEE-issued tags / the verifier's key."""
    from cess_tpu import codec
    from cess_tpu.node.offchain import Proof, build_proof

    params = podr2.Podr2Params(limbs=limbs)
    key = podr2.Podr2Key.generate(21, params)
    frags = make_fragments(3, seed=17)
    hashes = [bytes([40 + i]) * 32 for i in range(3)]
    ids = jnp.asarray(np.stack([podr2.fragment_id_from_hash(h)
                                for h in hashes]))
    tags = np.asarray(podr2.tag_fragments(key, ids, frags))
    store = {h: frags[i].tobytes() for i, h in enumerate(hashes)}
    tagmap = {h: tags[i] for i, h in enumerate(hashes)}
    blob = build_proof(b"limb-wire", sorted(hashes), store, tagmap)
    proof = codec.decode(blob)
    assert len(proof.sigma) == limbs

    # drive the TEE-side check exactly as the agent does
    class _FakeTee:
        pass
    from cess_tpu.node.offchain import TeeAgent

    tee = object.__new__(TeeAgent)
    tee.key = key
    tee.blocks = tags.shape[1]
    blocks = tags.shape[1]
    idx, nu = podr2.gen_challenge(b"limb-wire", blocks)
    assert TeeAgent._verify(tee, blob, sorted(hashes), b"limb-wire",
                            idx, nu)
    # empty-owed path: the zero sigma matches the deployment width
    empty = build_proof(b"limb-wire", [], {}, tagmap)
    assert TeeAgent._verify(tee, empty, [], b"limb-wire", idx, nu)
    # a WRONG-width sigma is a failed audit, not an exception
    wrong = codec.encode(Proof(mu=np.zeros((podr2.SECTORS,), np.uint32),
                               sigma=np.zeros((limbs + 1,), np.uint32)))
    assert not TeeAgent._verify(tee, wrong, [], b"limb-wire", idx, nu)
    # the legacy tuple-sigma wire shape is likewise a failed audit
    legacy = codec.encode(Proof(mu=np.zeros((podr2.SECTORS,), np.uint32),
                                sigma=(0,) * limbs))
    assert not TeeAgent._verify(tee, legacy, [], b"limb-wire", idx, nu)


def test_fillerless_miner_proof_width_limbs3():
    """Review-caught (r05): with an EMPTY tags map the proof width must
    come from the caller's key, not the module default — a fillerless
    miner in a limbs=3 deployment otherwise emits a 2-limb zero sigma
    and fails an audit it should pass."""
    from cess_tpu import codec
    from cess_tpu.node.offchain import TeeAgent, build_proof

    params = podr2.Podr2Params(limbs=3)
    key = podr2.Podr2Key.generate(31, params)
    blob = build_proof(b"seed", [], {}, {}, limbs=3)
    proof = codec.decode(blob)
    assert len(proof.sigma) == 3
    tee = object.__new__(TeeAgent)
    tee.key = key
    tee.blocks = 16
    idx, nu = podr2.gen_challenge(b"seed", 16)
    assert TeeAgent._verify(tee, blob, [], b"seed", idx, nu)


def test_tag_fragments_with_traced_key_falls_back():
    """Review-caught: the fused kernel precomputes weights host-side,
    so a key passed as a TRACED jit argument must route to the jnp
    path (identical results) instead of crashing on device_get."""
    import jax

    key = podr2.Podr2Key.generate(44)
    frags = make_fragments(2, seed=23)
    ids = jnp.arange(2)

    @jax.jit
    def tag_with_key(alpha, prf_key, f):
        k = podr2.Podr2Key(alpha=alpha, prf_key=prf_key)
        return podr2.tag_fragments(k, ids, f)

    got = np.asarray(tag_with_key(key.alpha, key.prf_key,
                                  jnp.asarray(frags)))
    want = np.asarray(podr2.tag_fragments(key, ids, frags))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("limbs", [2, 3])
@pytest.mark.parametrize("b,rows", [(1, 3), (3, 3), (8, 12), (2, 12), (5, 1)])
@pytest.mark.parametrize("path", ["kernel", "jnp"])
def test_tag_fragments_takes_the_batchs_shape(path, b, rows, limbs):
    """Fragments still in their batch's shape, ``[B, rows, bytes]`` (the
    fused ingest step since PR 44: no ``[B * rows, bytes]`` copy in
    front), tag to the same words as the same bytes flat, ids and tags
    row-major either way — through the kernel, which walks them rows
    first, and outside its envelope through the plain MAC."""
    from cess_tpu.ops import podr2_pallas

    blocks = 4 if path == "kernel" else 192        # 192: ragged grid
    assert podr2_pallas.supported(256, blocks) == (path == "kernel")
    key = podr2.Podr2Key.generate(46, podr2.Podr2Params(limbs=limbs))
    rng = np.random.default_rng(b * 31 + rows)
    frags = rng.integers(0, 256, (b, rows, blocks * podr2.BLOCK_BYTES),
                         dtype=np.uint8)
    ids = rng.integers(0, 2 ** 32, (b * rows, 2), dtype=np.uint32)
    flat = podr2.tag_fragments(key, ids, frags.reshape(b * rows, -1))
    shaped = podr2.tag_fragments(key, ids, frags)
    assert shaped.shape == (b * rows, blocks, limbs)
    np.testing.assert_array_equal(np.asarray(shaped), np.asarray(flat))
    one = podr2.tag_fragment(key, ids[-1], frags[-1, -1])
    np.testing.assert_array_equal(np.asarray(shaped[-1]), np.asarray(one))


def test_two_dimensional_tag_batch_traces_what_it_traced():
    """A 2-D ``[F, bytes]`` batch (``TAG_PROGRAM``, the engine's tag
    class) is untouched by the batch-shaped form: one reshape of the
    fragments into the kernel's view, the PRF's limb-major transpose
    and the tags' transpose back, nothing else moved."""
    import jax

    key = podr2.Podr2Key.generate(47)
    frags = jax.ShapeDtypeStruct((4, 4 * podr2.BLOCK_BYTES), jnp.uint8)
    ids = jax.ShapeDtypeStruct((4, 2), jnp.uint32)
    text = str(jax.make_jaxpr(
        lambda i, f: podr2.tag_fragments(key, i, f))(ids, frags))
    assert text.count("u8[4,4,512] = reshape") == 1
    assert text.count("transpose[permutation=(0, 2, 1)]") == 2
    assert "permutation=(1, 0" not in text


def test_fused_envelope_is_protocol_geometry_only():
    """Only sectors == 256 (the single Mosaic-validated shape) may
    route into the kernel; everything else takes the jnp path."""
    from cess_tpu.ops import podr2_pallas

    assert podr2_pallas.supported(256, 16)
    assert podr2_pallas.supported(256, 16384)
    for sectors in (64, 96, 128, 255):
        assert not podr2_pallas.supported(sectors, 256)
    # non-256 sectors still tag correctly (jnp route)
    params = podr2.Podr2Params(sectors=128)
    key = podr2.Podr2Key.generate(45, params)
    frag = np.random.default_rng(1).integers(
        0, 256, (1, 8 * 128 * 2), dtype=np.uint8)
    tags = podr2.tag_fragments(key, jnp.arange(1), frag)
    assert tags.shape == (1, 8, 2)


def test_fused_envelope_tracks_block_tile():
    """The block gate follows DEFAULT_BLOCK_TILE (r05 retune 256->128
    shifted membership in both directions — pin it): blocks fuse iff
    they fit one tile or divide it evenly."""
    from cess_tpu.ops import podr2_pallas as pp

    tile = pp.DEFAULT_BLOCK_TILE
    assert pp.supported(256, tile)           # one tile
    assert pp.supported(256, 3 * tile)       # whole grid steps
    assert pp.supported(256, tile // 2)      # sub-tile: tile == blocks
    assert not pp.supported(256, tile + tile // 2)   # ragged grid
    assert not pp.supported(256, 3 * tile // 2)


# -- the round's two derivations as compiled programs ------------------------
def _words(seed):
    """The seed's two key words, the test's own arithmetic."""
    import hashlib

    if isinstance(seed, bytes):
        digest = hashlib.sha256(seed).digest()
        return np.array([int.from_bytes(digest[:4], "little"),
                         int.from_bytes(digest[4:8], "little")], np.uint32)
    return np.array([seed & 0xFFFFFFFF, seed >> 32], np.uint32)


def _ids(f, seed=5):
    return np.random.default_rng(seed).integers(
        0, 2 ** 32, (f, 2), dtype=np.uint32)


SEEDS = [b"", b"round-7", bytes(range(40)), (1 << 40) + 12345]
# (blocks, count handed in, count that comes back); the protocol's
# 16384 blocks by the 46/1000 coverage rule, an explicit count, a rule
# that rounds down to its floor of one
GEOMETRIES = [(16384, None, 753), (64, 5, 5), (16, None, 1)]


@pytest.mark.parametrize("blocks,count,want_count", GEOMETRIES)
@pytest.mark.parametrize("seed", SEEDS, ids=["empty", "short", "bytes40",
                                             "int-above-2^32"])
def test_challenge_program_is_the_plain_body(seed, blocks, count,
                                             want_count):
    """CHALLENGE_PROGRAM is bit for bit its body run primitive by
    primitive, for every kind of seed."""
    import jax

    idx, nu = podr2.gen_challenge(seed, blocks, count)
    with jax.disable_jit():
        want_idx, want_nu = podr2._gen_challenge(_words(seed), blocks,
                                                 want_count)
    assert isinstance(idx, jax.Array) and isinstance(nu, jax.Array)
    assert idx.shape == nu.shape == (want_count,)
    assert idx.dtype == np.int32 and nu.dtype == np.uint32
    assert np.array_equal(idx, want_idx) and np.array_equal(nu, want_nu)
    assert 0 <= int(idx.min()) and int(idx.max()) < blocks
    assert int(nu.max()) < pf.P


@pytest.mark.parametrize("f", [1, 3, 32, 33, 100])
def test_coeffs_program_is_the_plain_body_whatever_the_pad(f):
    """COEFFS_PROGRAM over ids padded to a power of two hands back
    exactly F values, each the plain per-row PRF's: the pad changes no
    real row's r."""
    import jax

    seed, ids = b"agg-program", _ids(f)
    r = podr2.aggregate_coeffs(seed, ids)
    with jax.disable_jit():
        want = podr2._coeffs(
            podr2._aggregate_key(_words(b"cess-podr2-agg:" + seed)), ids)
    assert isinstance(r, jax.Array)
    assert r.shape == (f,) and r.dtype == np.uint32
    assert np.array_equal(r, want)
    # a list of id pairs and a device array are the same ids
    assert np.array_equal(podr2.aggregate_coeffs(seed, list(ids)), want)
    assert np.array_equal(podr2.aggregate_coeffs(seed, jnp.asarray(ids)),
                          want)


def _programs(stage):
    return podr2.stage_counters()[stage]["programs"]


@pytest.mark.parametrize("call,same,new", [
    pytest.param(lambda: podr2.gen_challenge(b"a", 977, 13),
                 lambda: podr2.gen_challenge(b"another seed", 977, 13),
                 [lambda: podr2.gen_challenge(b"a", 977, 14),
                  lambda: podr2.gen_challenge(b"a", 978, 13)],
                 id="podr2.challenge"),
    pytest.param(lambda: podr2.aggregate_coeffs(b"a", _ids(40)),
                 lambda: podr2.aggregate_coeffs(b"b", _ids(33, seed=6)),
                 [lambda: podr2.aggregate_coeffs(b"a", _ids(65))],
                 id="podr2.coeffs")])
def test_one_compile_a_shape(request, compiles, call, same, new):
    """A new seed, or another F under the same power of two, runs the
    program that is there; a new geometry or the next power of two is
    one more. ``programs`` is the count."""
    stage = request.node.callspec.id
    podr2._PROGRAMS[stage].clear_cache()
    assert _programs(stage) == 0
    call()
    assert _programs(stage) == 1
    n = podr2.stage_counters()[stage]["n"]
    before = compiles()
    same()
    assert _programs(stage) == 1
    assert podr2.stage_counters()[stage]["n"] == n + 1
    # the program compiles nothing; padded, r[:F] is one tiny slice
    assert compiles() - before <= (stage == "podr2.coeffs")
    for k, other in enumerate(new):
        other()
        assert _programs(stage) == 2 + k
    assert podr2.stage_metrics()[
        f"cess_podr2_{stage.partition('.')[2]}_programs"] == 1.0 + len(new)


def test_stage_counters_carry_programs_beside_n_and_s():
    podr2.gen_challenge(b"c", 16)
    podr2.aggregate_coeffs(b"c", _ids(2))
    counters = podr2.stage_counters()
    assert set(counters) == {"podr2.challenge", "podr2.coeffs"}
    metrics = podr2.stage_metrics()
    for stage, acc in counters.items():
        assert set(acc) == {"n", "s", "programs"}
        assert acc["n"] >= 1 and acc["s"] > 0 and acc["programs"] >= 1
        short = stage.partition(".")[2]
        assert metrics[f"cess_podr2_{short}_programs"] == acc["programs"]
        assert metrics[f"cess_podr2_{short}_count"] == acc["n"]
        assert metrics[f"cess_podr2_{short}_seconds"] == acc["s"]
