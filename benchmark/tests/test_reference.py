"""The frozen references agree with themselves and, at a small size on the
CPU, with the program they are there to judge."""
import numpy as np
import pytest

from reference import podr2_ref, rs_ref


@pytest.mark.parametrize("k,m", [(2, 1), (4, 8)])
def test_rs_reconstructs_any_lost_row(k, m):
    rng = np.random.default_rng(k * 16 + m)
    data = rng.integers(0, 256, (k, 1024), dtype=np.uint8)
    codec = rs_ref.ReferenceCodec(k, m)
    coded = codec.encode(data)
    assert np.array_equal(coded[:k], data)
    for lost in range(k + m):
        present = tuple(j for j in range(k + m) if j != lost)[:k]
        rec = codec.reconstruct(coded[list(present)], present, (lost,))
        assert np.array_equal(rec[0], coded[lost])


@pytest.mark.parametrize("k,m", [(2, 1), (4, 8)])
def test_rs_equals_the_programs_codec(k, m):
    from cess_tpu.ops.rs import TPUCodec

    data = np.random.default_rng(5).integers(0, 256, (3, k, 2048),
                                             dtype=np.uint8)
    assert np.array_equal(np.asarray(TPUCodec(k, m).encode(data)),
                          rs_ref.ReferenceCodec(k, m).encode(data))


def _set(seed=3, frags=3, nbytes=8192):
    rng = np.random.default_rng(seed)
    data = rng.integers(0, 256, (frags, nbytes), dtype=np.uint8)
    ids = rng.integers(0, 2 ** 32, (frags, 2), dtype=np.uint32)
    return data, ids


def test_podr2_honest_proof_verifies_and_a_flipped_byte_does_not():
    data, ids = _set()
    key = podr2_ref.generate_key(7)
    blocks = data.shape[1] // podr2_ref.BLOCK_BYTES
    idx, nu = podr2_ref.gen_challenge(b"round", blocks)
    r = podr2_ref.aggregate_coeffs(b"round", ids)
    mu, sigma = podr2_ref.prove_aggregate(key, ids, data, idx, nu, r)
    assert podr2_ref.verify_aggregate(key, ids, idx, nu, r, mu, sigma)
    bad = data.copy()
    bad[1, int(np.asarray(idx)[0]) * podr2_ref.BLOCK_BYTES + 3] ^= 0x40
    mu2, sigma2 = podr2_ref.prove_aggregate(key, ids, bad, idx, nu, r)
    # the miner cannot re-tag: sigma stays the honest one
    assert not podr2_ref.verify_aggregate(key, ids, idx, nu, r, mu2, sigma)


def test_podr2_equals_the_programs_equations():
    from cess_tpu.ops import podr2

    data, ids = _set(seed=9)
    blocks = data.shape[1] // podr2_ref.BLOCK_BYTES
    key, pkey = podr2_ref.generate_key(11), podr2.Podr2Key.generate(11)
    assert np.array_equal(np.asarray(key.alpha), np.asarray(pkey.alpha))
    tags = np.asarray(podr2.tag_fragments(pkey, ids, data))
    for f in range(len(data)):
        assert np.array_equal(
            podr2_ref.tag_fragment(key, ids[f], data[f]), tags[f])
    # scalar ids (the stream's default) fold in the same way
    assert np.array_equal(
        podr2_ref.tag_fragment(key, np.int32(5), data[0]),
        np.asarray(podr2.tag_fragment(pkey, 5, data[0])))
    idx, nu = podr2_ref.gen_challenge(b"r2", blocks)
    pidx, pnu = podr2.gen_challenge(b"r2", blocks)
    assert np.array_equal(np.asarray(idx), np.asarray(pidx))
    assert np.array_equal(np.asarray(nu), np.asarray(pnu))
    r = podr2_ref.aggregate_coeffs(b"r2", ids)
    assert np.array_equal(np.asarray(r),
                          np.asarray(podr2.aggregate_coeffs(b"r2", ids)))
    mu, sigma = podr2_ref.prove_aggregate(key, ids, data, idx, nu, r)
    pmu, psigma = podr2.prove_aggregate(data, tags, pidx, pnu,
                                        np.asarray(r))
    assert np.array_equal(mu, np.asarray(pmu))
    assert np.array_equal(sigma, np.asarray(psigma))
