"""The audit round through the engine as two compiled programs over the
challenged blocks only (serve/engine.py ``_op_prove`` / ``_op_verify_agg``,
ops/podr2.py ``prove_at``).

What is pinned: (a) the arithmetic — engine proofs and verdicts are
bit-identical to the direct ``podr2`` calls at both limb widths, for a
ragged coalesced batch, for a challenge that names a block twice, and on
the degraded CPU path; ``prove`` is ``prove_at`` after a gather; only
the challenged blocks enter a proof; (b) the mechanism — one program per
batch shape reused round after round (the round and the key are
operands), and ``operand_bytes`` counts what a round reads, not the set.
"""
import numpy as np
import pytest

from cess_tpu.ops import pfield as pf
from cess_tpu.ops import podr2
from cess_tpu.resilience import FaultPlan, ResilienceConfig, faults
from cess_tpu.serve import AdmissionPolicy, engine as engine_mod, make_engine

BLOCKS = 8
FRAG = BLOCKS * podr2.BLOCK_BYTES          # 4 KiB fragments


def rnd(shape, seed):
    return np.random.default_rng(seed).integers(0, 256, shape,
                                                dtype=np.uint8)


def _tag_inputs(f, frag_bytes, seed):
    """f seeded fragments and their (lo, hi) ids."""
    ids = np.stack([podr2.fragment_id_from_hash(bytes([seed % 251, j]) * 16)
                    for j in range(f)])
    return ids, rnd((f, frag_bytes), seed)


def _mission(key, f, seed, round_seed=b"round", frag_bytes=FRAG):
    """One miner's set of f fragments with its ids, tags and r."""
    ids, frags = _tag_inputs(f, frag_bytes, seed)
    tags = np.asarray(podr2.tag_fragments(key, ids, frags))
    r = np.asarray(podr2.aggregate_coeffs(round_seed, ids))
    return frags, ids, tags, r


def _challenge(case: str, round_seed=b"round", blocks=BLOCKS):
    if case == "duplicates":
        # randint draws with replacement; here every block is named
        # twice or more, out of order
        idx = np.array([5, 1, 5, 0, 1, 5, 7], dtype=np.int32)
        _, nu = podr2.gen_challenge(round_seed, blocks, count=len(idx))
        return idx, np.asarray(nu)
    idx, nu = podr2.gen_challenge(round_seed, blocks, count=5)
    return np.asarray(idx), np.asarray(nu)


# case -> (limbs, set sizes of the coalesced miners, engine kwargs)
CASES = {
    "limbs2": (2, (3,), {}),
    "limbs3": (3, (3,), {}),
    "ragged-coalesced": (2, (2, 3, 5), {}),
    "duplicates": (2, (4,), {}),
    "degraded-cpu": (2, (3,), {"resilience": ResilienceConfig()}),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_engine_audit_bit_identical_to_direct(case):
    limbs, sizes, kwargs = CASES[case]
    key = podr2.Podr2Key.generate(26, podr2.Podr2Params(limbs=limbs))
    idx, nu = _challenge(case)
    missions = [_mission(key, f, 40 + i) for i, f in enumerate(sizes)]
    eng = make_engine(podr2_key=key,
                      policy=AdmissionPolicy(max_delay=0.25), **kwargs)
    plan = FaultPlan.seeded(b"audit", {"engine.dispatch": (1.0, "raise")}) \
        if case == "degraded-cpu" else FaultPlan({})
    try:
        with faults.armed(plan):
            futs = [eng.submit_prove_aggregate(frags, tags, idx, nu, r)
                    for frags, _, tags, r in missions]
            proofs = [f.result(timeout=120) for f in futs]
            for (frags, _, tags, r), (mu, sigma) in zip(missions, proofs):
                dmu, dsigma = podr2.prove_aggregate(frags, tags, idx, nu, r)
                assert mu.dtype == sigma.dtype == np.uint32
                assert np.array_equal(mu, np.asarray(dmu))
                assert np.array_equal(sigma, np.asarray(dsigma))
                assert sigma.shape == (limbs,)
            # the verifier: every honest proof, and the first one with a
            # word of mu altered, in one coalesced batch
            bad_mu = proofs[0][0].copy()
            bad_mu[3] ^= 1
            checks = [(ids, r, mu, sigma) for (_, ids, _, r), (mu, sigma)
                      in zip(missions, proofs)]
            checks.append((missions[0][1], missions[0][3], bad_mu,
                           proofs[0][1]))
            futs = [eng.submit_verify_aggregate(ids, BLOCKS, idx, nu, r,
                                                mu, sigma)
                    for ids, r, mu, sigma in checks]
            got = [bool(f.result(timeout=120)) for f in futs]
        want = [bool(np.asarray(podr2.verify_aggregate(
            key, ids, BLOCKS, idx, nu, r, mu, sigma)))
            for ids, r, mu, sigma in checks]
        assert got == want == [True] * len(sizes) + [False]
        snap = eng.stats_snapshot()
        if case == "ragged-coalesced":
            # 3 miners in one batch: rb = 4, fb = 8 (the 5-set's bucket)
            assert snap["classes"]["prove"]["batches"] == 1
            assert snap["classes"]["prove"]["batch_occupancy"] == 3
            assert snap["classes"]["prove"]["pad_waste"] == \
                round(1 - 10 / 32, 4)
        if case == "degraded-cpu":
            assert plan.fired_log()
            # served by the CPU backend: as a failed batch's fallback,
            # or straight away once the breaker is open
            res = snap["resilience"]
            for cls in ("prove", "verify"):
                assert res["fallback_batches"].get(cls, 0) \
                    + res["degraded_batches"].get(cls, 0) >= 1
    finally:
        eng.close()


@pytest.mark.parametrize("limbs", [2, 3])
@pytest.mark.parametrize("form", ["bytes", "u16-view", "elems"])
def test_prove_is_prove_at_after_the_gather(form, limbs):
    """On raw arrays: the challenged blocks handed over as bytes, as a
    little-endian uint16 view of them, or packed — the same proof."""
    key = podr2.Podr2Key.generate(7, podr2.Podr2Params(limbs=limbs))
    frags, _, tags, _ = _mission(key, 1, 3)
    frag, tag = frags[0], tags[0]
    idx, nu = _challenge("duplicates")
    mu, sigma = (np.asarray(a) for a in podr2.prove(frag, tag, idx, nu))
    blocks = frag.reshape(BLOCKS, podr2.BLOCK_BYTES)
    gathered = {"bytes": blocks[idx],
                "u16-view": blocks.view("<u2")[idx],
                "elems": pf.pack_bytes(blocks)[idx]}[form]
    assert gathered.shape[0] == len(idx)
    mu_at, sigma_at = podr2.prove_at(gathered, tag[idx], nu)
    assert np.array_equal(np.asarray(mu_at), mu)
    assert np.array_equal(np.asarray(sigma_at), sigma)
    # and by the definition itself, in Python integers
    m = pf.pack_bytes(blocks).astype(object)
    want = [sum(int(n) * int(m[i, j]) for i, n in zip(idx, nu)) % pf.P
            for j in range(podr2.SECTORS)]
    assert mu.tolist() == want


@pytest.mark.parametrize("where", ["inside", "outside"])
def test_only_challenged_blocks_enter_the_proof(where):
    key = podr2.Podr2Key.generate(11)
    frags, ids, tags, r = _mission(key, 3, 9)
    idx, nu = _challenge("fresh")
    unchallenged = sorted(set(range(BLOCKS)) - set(idx.tolist()))
    assert unchallenged, "the challenge must leave a block out"
    block = int(idx[2]) if where == "inside" else unchallenged[0]
    bad = frags.copy()
    bad[1, block * podr2.BLOCK_BYTES + 17] ^= 0x40
    eng = make_engine(podr2_key=key,
                      policy=AdmissionPolicy(max_delay=0.002))
    try:
        mu, sigma = eng.prove_aggregate(frags, tags, idx, nu, r)
        mu_b, sigma_b = eng.prove_aggregate(bad, tags, idx, nu, r)
        accepted = eng.verify_aggregate(ids, BLOCKS, idx, nu, r, mu_b,
                                        sigma_b)
    finally:
        eng.close()
    if where == "inside":
        assert not np.array_equal(mu, mu_b)
        assert accepted is False
    else:
        assert np.array_equal(mu, mu_b) and np.array_equal(sigma, sigma_b)
        assert accepted is True


@pytest.mark.parametrize("what,idx,nu", [
    ("negative", [0, -1, 2], [1, 2, 3]),
    ("past-the-end", [0, BLOCKS, 2], [1, 2, 3]),
    ("not-integers", [0.0, 1.0, 2.0], [1, 2, 3]),
    ("nu-of-another-length", [0, 1, 2], [1, 2]),
])
def test_a_round_outside_the_fragment_is_refused_at_submit(what, idx, nu):
    key = podr2.Podr2Key.generate(11)
    frags, ids, tags, r = _mission(key, 2, 5)
    eng = make_engine(podr2_key=key)
    try:
        with pytest.raises(ValueError):
            eng.submit_prove_aggregate(frags, tags, np.array(idx),
                                       np.array(nu), r)
        with pytest.raises(ValueError):
            eng.submit_verify_aggregate(
                ids, BLOCKS, np.array(idx), np.array(nu), r,
                np.zeros(podr2.SECTORS, np.uint32), np.zeros(2, np.uint32))
        assert eng.stats_snapshot()["classes"]["prove"]["submitted"] == 0
    finally:
        eng.close()


def test_fragments_that_are_not_whole_blocks_are_refused():
    key = podr2.Podr2Key.generate(11)
    frags, _, tags, r = _mission(key, 2, 5)
    idx, nu = _challenge("fresh")
    eng = make_engine(podr2_key=key)
    try:
        with pytest.raises(ValueError, match="blocks"):
            eng.submit_prove_aggregate(frags[:, :-2], tags, idx, nu, r)
    finally:
        eng.close()


def test_one_program_per_shape_across_rounds_and_operands_are_what_a_round_reads():
    """The mechanism: >= 4 rounds with different seeds run the SAME two
    cached entries and the same two traces (the round and the key are
    operands), and prove hands the device the challenged blocks, their
    tags, r and nu — under 6% of the fragments' bytes at the protocol's
    challenge rate and block width."""
    blocks = 500                                   # c = 23: 4.6%
    frag_bytes = blocks * podr2.BLOCK_BYTES
    f, limbs, rounds = 4, 2, 5        # f fills its bucket: no pad rows
    key = podr2.Podr2Key.generate(5)
    eng = make_engine(podr2_key=key,
                      policy=AdmissionPolicy(max_delay=0.002))
    try:
        built, traced, per_batch = [], [], []
        frags = ids = tags = None
        for n in range(rounds):
            seed = b"round-%d" % n
            if frags is None:
                frags, ids, tags, _ = _mission(key, f, 77, seed, frag_bytes)
            r = np.asarray(podr2.aggregate_coeffs(seed, ids))
            idx, nu = podr2.gen_challenge(seed, blocks)
            before = eng.stats_snapshot()["classes"]["prove"]
            mu, sigma = eng.prove_aggregate(frags, tags, idx, nu, r)
            assert eng.verify_aggregate(ids, blocks, idx, nu, r, mu, sigma)
            dmu, dsigma = podr2.prove_aggregate(frags, tags, idx, nu, r)
            assert np.array_equal(mu, np.asarray(dmu))
            assert np.array_equal(sigma, np.asarray(dsigma))
            eng.flush()
            snap = eng.stats_snapshot()
            after = snap["classes"]["prove"]
            assert after["batches"] - before["batches"] == 1
            per_batch.append(after["operand_bytes"]
                             - before["operand_bytes"])
            built.append(snap["programs_built"])
            traced.append((engine_mod._PROVE_PROGRAM._cache_size(),
                           engine_mod._VERIFY_PROGRAM._cache_size()))
        # two programs after the first round, and never another
        assert built == [2] * rounds
        assert snap["programs_reused"] == 2 * (rounds - 1)
        assert len(set(traced)) == 1
        # rb x fb x c blocks of sectors x 2 bytes, as many tag rows of
        # 4 x limbs bytes, r [rb, fb] and nu [c]
        c, rb, fb = 23, 1, 4
        assert len(np.asarray(idx)) == c
        want = (rb * fb * c * podr2.SECTORS * 2 + rb * fb * c * 4 * limbs
                + rb * fb * 4 + c * 4)
        assert per_batch == [want] * rounds
        assert want < 0.06 * f * frag_bytes
        # the verifier's operands are KiB: ids, r, mu, sigma, idx, nu
        # and the key (alpha and the PRF key's two words)
        verify = snap["classes"]["verify"]
        assert verify["operand_bytes"] == rounds * (
            rb * fb * 8 + rb * fb * 4 + podr2.SECTORS * 4 + limbs * 4
            + c * 4 + c * 4 + podr2.SECTORS * limbs * 4 + 8)
        metrics = eng.stats_metrics()
        assert metrics["cess_engine_prove_operand_bytes"] == rounds * want
        assert metrics["cess_engine_encode_operand_bytes"] == 0
    finally:
        eng.close()


# -- the tag batch as one compiled program (PR 34) ---------------------------
#
# ops/podr2.py TAG_PROGRAM behind AuditBackend.tag_fragments: the key
# and the kernel's weights are operands, the lowering follows the shape.

def _plain_tags(key, ids, frags):
    """The plain-jnp MAC, a fragment at a time: no kernel, no batch."""
    return np.stack([np.asarray(podr2.tag_fragment(key, i, d))
                     for i, d in zip(ids, frags)])


# case -> (limbs, blocks, fragments of each coalesced request, engine
# kwargs). 8 blocks lie inside the kernel's envelope, 192 outside it
# (192 % 128 != 0: the plain-jnp MAC under the same program).
TAG_CASES = {
    "kernel": (2, 8, (4,), {}),
    "kernel-limbs3": (3, 8, (2,), {}),
    "jnp-outside-envelope": (2, 192, (2,), {}),
    "padded-bucket": (2, 8, (3,), {}),
    "ragged-coalesced": (2, 8, (1, 2, 3), {}),
    "degraded-cpu": (2, 8, (3,), {"resilience": ResilienceConfig()}),
    "pool-lane": (2, 8, (2,), {"pool": 2}),
}


@pytest.mark.parametrize("case", sorted(TAG_CASES))
def test_engine_tags_bit_identical_to_direct(case):
    from cess_tpu.ops import podr2_pallas

    limbs, blocks, sizes, kwargs = TAG_CASES[case]
    assert podr2_pallas.supported(podr2.SECTORS, blocks) \
        == (case != "jnp-outside-envelope")
    key = podr2.Podr2Key.generate(34, podr2.Podr2Params(limbs=limbs))
    reqs = [_tag_inputs(f, blocks * podr2.BLOCK_BYTES, 60 + i)
            for i, f in enumerate(sizes)]
    eng = make_engine(podr2_key=key,
                      policy=AdmissionPolicy(max_delay=0.25), **kwargs)
    plan = FaultPlan.seeded(b"tags", {"engine.dispatch": (1.0, "raise")}) \
        if case == "degraded-cpu" else FaultPlan({})
    try:
        with faults.armed(plan):
            futs = [eng.submit_tag(ids, frags) for ids, frags in reqs]
            got = [f.result(timeout=120) for f in futs]
        for (ids, frags), tags in zip(reqs, got):
            # rows beyond the request's own never come back
            assert tags.shape == (len(frags), blocks, limbs)
            assert tags.dtype == np.uint32
            assert np.array_equal(
                tags, np.asarray(podr2.tag_fragments(key, ids, frags)))
            assert np.array_equal(tags, _plain_tags(key, ids, frags))
        snap = eng.stats_snapshot()
        if case == "ragged-coalesced":
            assert snap["classes"]["tag"]["batches"] == 1
            assert snap["classes"]["tag"]["padded_rows"] == 2   # 6 of 8
        if case == "padded-bucket":
            assert snap["classes"]["tag"]["padded_rows"] == 1   # 3 of 4
        if case == "degraded-cpu":
            assert plan.fired_log()
            res = snap["resilience"]
            assert res["fallback_batches"].get("tag", 0) \
                + res["degraded_batches"].get("tag", 0) >= 1
    finally:
        eng.close()


@pytest.mark.parametrize("pairs", [True, False], ids=["pairs", "scalars"])
@pytest.mark.parametrize("blocks", [8, 192], ids=["kernel", "jnp"])
def test_tag_dispatch_is_tag_fragments(blocks, pairs):
    """On raw arrays, without an engine: the program over the key's
    operands against the eager call and the plain MAC, for (lo, hi) id
    pairs and for scalar ids, host and device-resident inputs."""
    import jax.numpy as jnp

    key = podr2.Podr2Key.generate(9)
    ids, frags = _tag_inputs(3, blocks * podr2.BLOCK_BYTES, 21)
    if not pairs:
        ids = ids[:, 0].copy()
    ops = podr2.tag_operands(key)
    assert all(isinstance(a, np.ndarray) for a in ops[:2] + ops[2])
    want = np.asarray(podr2.tag_fragments(key, ids, frags))
    assert np.array_equal(want, _plain_tags(key, ids, frags))
    got = podr2.tag_dispatch(ops, ids, frags)
    assert np.array_equal(np.asarray(got), want)
    got_dev = podr2.tag_dispatch(ops, jnp.asarray(ids), jnp.asarray(frags))
    assert np.array_equal(np.asarray(got_dev), want)


def test_one_tag_program_per_shape_across_keys_and_batches(compiles):
    """The mechanism: after one warm batch a second of the same shape
    compiles nothing and builds no program, and a second KEY (another
    engine, another backend) runs the same trace: the key is an
    operand. Its tags are its own."""
    key_a, key_b = podr2.Podr2Key.generate(1), podr2.Podr2Key.generate(2)
    eng_a = make_engine(podr2_key=key_a,
                        policy=AdmissionPolicy(max_delay=0.002))
    eng_b = make_engine(podr2_key=key_b,
                        policy=AdmissionPolicy(max_delay=0.002))
    try:
        ids, frags = _tag_inputs(4, FRAG, 5)
        first = eng_a.tag_fragments(ids, frags)
        eng_a.flush()
        built = eng_a.stats_snapshot()["programs_built"]
        traced, compiled = podr2.TAG_PROGRAM._cache_size(), compiles()
        ids2, frags2 = _tag_inputs(4, FRAG, 6)
        second = eng_a.tag_fragments(ids2, frags2)
        other = eng_b.tag_fragments(ids, frags)
        eng_a.flush()
        assert compiles() == compiled
        assert podr2.TAG_PROGRAM._cache_size() == traced
        snap = eng_a.stats_snapshot()
        assert snap["programs_built"] == built == 1
        assert snap["programs_reused"] == 1
        assert np.array_equal(
            second, np.asarray(podr2.tag_fragments(key_a, ids2, frags2)))
        assert np.array_equal(
            other, np.asarray(podr2.tag_fragments(key_b, ids, frags)))
        assert not np.array_equal(first, other)
    finally:
        eng_a.close()
        eng_b.close()
