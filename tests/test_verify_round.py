"""A TEE worker's audit round judged together (PR 33, the deployment
``tee-verify-caps``): ``TeeAgent.verify_round`` -> the engine's
``verify_round`` op -> ops/podr2.py ``round_fold`` / ``round_close``.

The verdicts are held to the benchmark's plain reference
(``benchmark/reference/verify_round_ref.py``: the published equation in
NumPy uint64 over plain-jnp PRF folds, nothing of the program in it) on
seeded ragged rounds, honest and under each tamper of the benchmark's
cell; to themselves however the missions are submitted (a round, one by
one, any order, with and without an engine: pad and batching
independence); and to the compile counter: a second round of other sizes
compiles nothing. Since PR 56 the round's folds go to the device before
a proof is decoded and the proofs reach the program at the close
(``LateProofs``): the verdicts are held, mission for mission, to the
order before it (decode first, the proofs in hand at the submit), and a
request's late proofs are its own to fail. Small sizes, CPU.
"""
import dataclasses
import importlib
import os
import sys
import types

import jax
import numpy as np
import pytest

from cess_tpu import codec, obs
from cess_tpu.chain import audit as chain_audit
from cess_tpu.node.offchain import Proof, TeeAgent
from cess_tpu.ops import pfield as pf
from cess_tpu.ops import podr2
from cess_tpu.serve import (AdmissionPolicy, EngineTimeout, LateProofs,
                            make_engine)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BLOCKS = 64
KEY_SEED = 24
SIZES = (5, 1, 23, 600, 2, 9, 1, 40, 3)     # ragged: 1 to 600 owed


@pytest.fixture(scope="module")
def ref():
    """The benchmark's reference package, as benchmark/run.py sees it."""
    sys.path.insert(0, os.path.join(REPO, "benchmark"))
    try:
        return types.SimpleNamespace(
            round=importlib.import_module("reference.verify_round_ref"),
            podr2=importlib.import_module("reference.podr2_ref"))
    finally:
        sys.path.remove(os.path.join(REPO, "benchmark"))


@pytest.fixture(scope="module")
def key():
    return podr2.Podr2Key.generate(KEY_SEED)


@pytest.fixture(scope="module")
def engine(key):
    eng = make_engine(2, 1, podr2_key=key,
                      policy=AdmissionPolicy(max_delay=0.001))
    yield eng
    eng.close()


def make_tee(key, engine=None) -> TeeAgent:
    tee = object.__new__(TeeAgent)
    tee.key, tee.blocks, tee.engine = key, BLOCKS, engine
    tee.controller, tee.bls_sk, tee._submitted = "tee0", None, set()
    return tee


def wire(mu, sigma) -> bytes:
    return codec.encode(Proof(mu=np.ascontiguousarray(mu, np.uint32),
                              sigma=np.ascontiguousarray(sigma, np.uint32)))


@dataclasses.dataclass
class Round:
    seed: bytes
    owed: list          # per mission, its fragment hashes
    mu: np.ndarray      # honest proofs, made by the reference
    sigma: np.ndarray

    @property
    def blobs(self):
        return [wire(u, s) for u, s in zip(self.mu, self.sigma)]


def make_round(ref, n: int, sizes=SIZES) -> Round:
    rng = np.random.default_rng(1000 + n)
    owed = [[rng.bytes(32) for _ in range(s)] for s in sizes]
    seed = b"round:%d" % n
    ids = np.concatenate([ref.podr2.fragment_id_from_hash(h)[None]
                          for hs in owed for h in hs])
    mu, sigma = ref.round.honest_proofs(
        ref.podr2.generate_key(KEY_SEED), seed, BLOCKS, ids, sizes, 77 + n)
    return Round(seed, owed, mu, sigma)


def ref_verdicts(ref, rnd: Round, owed, proofs) -> list:
    ids = [np.stack([ref.podr2.fragment_id_from_hash(h) for h in hs])
           if hs else np.zeros((0, 2), np.uint32) for hs in owed]
    return ref.round.verdicts(ref.podr2.generate_key(KEY_SEED), rnd.seed,
                              BLOCKS, ids, proofs)


# -- the pieces ------------------------------------------------------------
@pytest.mark.parametrize("hashes", [
    pytest.param([bytes([i]) * 32 for i in range(7)], id="sha256-width"),
    pytest.param([bytes([i, 255 - i]) * 4 for i in range(5)], id="8-bytes"),
    pytest.param([b"\x01" * 32, b"\x02" * 16, b"\x03" * 9], id="ragged"),
    pytest.param([], id="none")])
def test_ids_in_one_pass_equal_the_scalar_form(hashes):
    got = podr2.fragment_ids_from_hashes(iter(hashes))
    assert got.dtype == np.uint32 and got.shape == (len(hashes), 2)
    for row, h in zip(got, hashes):
        assert np.array_equal(row, podr2.fragment_id_from_hash(h))


def test_r_derived_inside_a_program_is_aggregate_coeffs():
    """``round_fold`` takes the round's aggregation key words and makes
    r itself; traced, that is bit for bit ``aggregate_coeffs``."""
    ids = np.random.default_rng(3).integers(
        0, 2 ** 32, (37, 2), dtype=np.uint32)
    for seed in (b"", b"round-7", bytes(range(40))):
        inside = jax.jit(lambda w, i: podr2._coeffs(
            podr2._aggregate_key(w), i))(podr2.aggregate_words(seed), ids)
        assert np.array_equal(np.asarray(inside),
                              np.asarray(podr2.aggregate_coeffs(seed, ids)))


def test_mission_buckets_are_three_up_to_the_cap():
    assert [podr2.mission_bucket(m) for m in (1, 8, 9, 64, 65, 500, 513)] \
        == [8, 8, 64, 64, 512, 512, 4096]


# -- program verdicts = reference verdicts ---------------------------------
def tampered(kind: str, rnd: Round):
    """(blobs, owed, proofs as the reference takes them, expected)."""
    mu, sigma = rnd.mu.copy(), rnd.sigma.copy()
    owed = [list(hs) for hs in rnd.owed]
    bad = set()
    if kind == "flip_mu":
        mu[3, 17] ^= 1
        bad = {3}
    elif kind == "flip_sigma":
        sigma[2, 1] ^= 4
        bad = {2}
    elif kind == "uncovered_fragment":
        owed[3][11] = b"\xee" * 32       # owed, and not in the proof
        bad = {3}
    elif kind == "swapped":
        mu[[0, 5]], sigma[[0, 5]] = mu[[5, 0]], sigma[[5, 0]]
        bad = {0, 5}
    blobs = [wire(u, s) for u, s in zip(mu, sigma)]
    proofs = list(zip(mu, sigma))
    if kind == "malformed":
        blobs[7], proofs[7] = blobs[7][:100], None
        bad = {7}
    return blobs, owed, proofs, [m not in bad for m in range(len(blobs))]


@pytest.mark.parametrize("kind", ["honest", "flip_mu", "flip_sigma",
                                  "uncovered_fragment", "swapped",
                                  "malformed"])
@pytest.mark.parametrize("through", ["engine", "direct"])
def test_verdicts_equal_the_reference(ref, key, engine, kind, through):
    rnd = make_round(ref, 1)
    blobs, owed, proofs, want = tampered(kind, rnd)
    tee = make_tee(key, engine if through == "engine" else None)
    got = tee.verify_round(blobs, owed, rnd.seed)
    assert got == want
    assert got == ref_verdicts(ref, rnd, owed, proofs)
    assert all(type(v) is bool for v in got)


def test_round_equals_the_per_mission_equation(ref, key):
    """Mission by mission the flat fold is ``podr2.verify_aggregate``
    with r from ``aggregate_coeffs`` (what ``audit-2p1.round`` runs)."""
    rnd = make_round(ref, 2)
    blobs, owed, _, _ = tampered("flip_sigma", rnd)
    idx, nu = podr2.gen_challenge(rnd.seed, BLOCKS)
    want = []
    for hs, blob in zip(owed, blobs):
        ids = podr2.fragment_ids_from_hashes(hs)
        proof = codec.decode(blob)
        want.append(bool(np.asarray(podr2.verify_aggregate(
            key, ids, BLOCKS, idx, nu,
            podr2.aggregate_coeffs(rnd.seed, ids), proof.mu, proof.sigma))))
    assert make_tee(key).verify_round(blobs, owed, rnd.seed) == want


# -- pad and batching independence -----------------------------------------
@pytest.mark.parametrize("how,through", [
    (how, through)
    for how in ("one_by_one", "reversed", "shuffled", "concurrent", "halves")
    for through in ("engine", "direct")
    if (how, through) != ("concurrent", "direct")])   # the engine coalesces
def test_verdicts_do_not_depend_on_how_missions_arrive(ref, key, engine,
                                                       how, through):
    rnd = make_round(ref, 3)
    blobs, owed, _, want = tampered("swapped", rnd)
    tee = make_tee(key, engine if through == "engine" else None)
    n = len(blobs)
    if how == "one_by_one":
        got = [tee.verify_round([blobs[m]], [owed[m]], rnd.seed)[0]
               for m in range(n)]
    elif how == "concurrent":
        # every mission a request of its own, all queued before any is
        # gathered: the engine coalesces them row-wise
        idx, nu = (np.asarray(a) for a in
                   podr2.gen_challenge(rnd.seed, BLOCKS))
        futs = []
        for m in range(n):
            proof = codec.decode(blobs[m])
            futs.append(engine.submit_verify_round(
                podr2.fragment_ids_from_hashes(owed[m]), [len(owed[m])],
                BLOCKS, idx, nu, podr2.aggregate_words(rnd.seed),
                proof.mu[None], proof.sigma[None]))
        got = [bool(f.result(timeout=60)[0]) for f in futs]
    elif how == "halves":
        got = tee.verify_round(blobs[:4], owed[:4], rnd.seed) \
            + tee.verify_round(blobs[4:], owed[4:], rnd.seed)
    else:
        order = list(range(n))[::-1] if how == "reversed" else \
            np.random.default_rng(5).permutation(n).tolist()
        back = tee.verify_round([blobs[m] for m in order],
                                [owed[m] for m in order], rnd.seed)
        got = [back[order.index(m)] for m in range(n)]
    assert got == want


# -- what stays held per mission -------------------------------------------
@pytest.mark.parametrize("what", ["garbage", "wrong_width_sigma",
                                  "sigma_not_below_p", "tuple_sigma",
                                  "empty_owed_nonzero_proof",
                                  "empty_owed_zero_proof", "not_bytes"])
@pytest.mark.parametrize("through", ["engine", "direct"])
def test_a_bad_mission_fails_alone_and_never_raises(ref, key, engine, what,
                                                    through):
    rnd = make_round(ref, 4, sizes=(3, 4, 2))
    blobs, owed = rnd.blobs, [list(hs) for hs in rnd.owed]
    zero = np.zeros((podr2.SECTORS,), np.uint32)
    want = [True, False, True]
    if what == "garbage":
        blobs[1] = b"\x00garbage\xff" * 9
    elif what == "wrong_width_sigma":
        blobs[1] = wire(rnd.mu[1], np.zeros((3,), np.uint32))
    elif what == "sigma_not_below_p":
        # the same field element under a second name must not pass
        blobs[1] = wire(rnd.mu[1], rnd.sigma[1] + np.uint32(pf.P)
                        * (rnd.sigma[1] < 2))
        if not (rnd.sigma[1] < 2).any():
            blobs[1] = wire(rnd.mu[1], np.full((2,), pf.P, np.uint32))
    elif what == "tuple_sigma":
        blobs[1] = codec.encode(Proof(mu=rnd.mu[1], sigma=(0, 0)))
    elif what == "empty_owed_nonzero_proof":
        owed[1] = []
    elif what == "empty_owed_zero_proof":
        owed[1], blobs[1] = [], wire(zero, zero[:2])
        want = [True, True, True]
    elif what == "not_bytes":
        blobs[1] = None
    tee = make_tee(key, engine if through == "engine" else None)
    assert tee.verify_round(blobs, owed, rnd.seed) == want
    assert tee.verify_round([blobs[1]], [owed[1]], rnd.seed) == [want[1]]


def test_the_owed_sets_alone_decide_what_reaches_the_device(key, engine):
    """A round with no owed fragment touches no device; an undecodable
    proof over an owed set has its rows folded (the folds go out before
    the decode) and its verdict forced False."""
    def submitted():
        return engine.stats_snapshot()["classes"]["verify"]["submitted"]

    before = submitted()
    tee = make_tee(key, engine)
    assert tee.verify_round([b"", b"x"], [[], []], b"s") == [False, False]
    assert tee.verify_round([], [], b"s") == []
    assert submitted() == before
    assert tee.verify_round([b"", b"x"], [[b"\x01" * 32], []], b"s") \
        == [False, False]
    assert submitted() == before + 1


# -- shapes: one program a mission bucket ----------------------------------
def test_a_second_round_of_other_sizes_compiles_nothing(ref, key, compiles):
    eng = make_engine(2, 1, podr2_key=key,
                      policy=AdmissionPolicy(max_delay=0.001))
    try:
        tee = make_tee(key, eng)
        # the reference compiles for each size it is asked for: every
        # round is made before the count starts
        first = make_round(ref, 5, sizes=(4, 1, 30))
        later = [make_round(ref, n, sizes=sizes) for n, sizes in (
            (6, (1,)), (7, (700, 2, 2, 19, 1, 1, 88)),
            (8, (2, 2, 2, 2, 2, 2, 2, 2)))]
        assert all(tee.verify_round(first.blobs, first.owed, first.seed))
        built, compiled = eng.stats_snapshot()["programs_built"], compiles()
        fold, close = (podr2.ROUND_FOLD._cache_size(),
                       podr2.ROUND_CLOSE._cache_size())
        for rnd in later:
            assert all(tee.verify_round(rnd.blobs, rnd.owed, rnd.seed))
        assert eng.stats_snapshot()["programs_built"] == built
        assert compiles() == compiled
        assert (podr2.ROUND_FOLD._cache_size(),
                podr2.ROUND_CLOSE._cache_size()) == (fold, close)
    finally:
        eng.close()


@pytest.mark.parametrize("through", ["engine", "direct"])
def test_warm_verify_loads_every_shape_a_round_can_meet(ref, through,
                                                        compiles):
    """A key of its own width (limbs 3): nothing any other test
    compiled serves it, so the warm-up is what loads the programs."""
    key3 = podr2.Podr2Key.generate(9, podr2.Podr2Params(limbs=3))
    eng = make_engine(2, 1, podr2_key=key3) if through == "engine" else None
    try:
        tee = make_tee(key3, eng)
        tee.warm_verify(missions=70)             # buckets 8, 64 and 512
        zero = wire(np.zeros(podr2.SECTORS, np.uint32),
                    np.zeros(3, np.uint32))
        # gen_challenge and the decode are the caller's own and warm
        # with the first round: not what this holds
        tee.verify_round([zero], [[b"\x07" * 32]], b"w")
        compiled = compiles()
        built = eng.stats_snapshot()["programs_built"] if eng else 0
        for missions in (3, 40, 70):
            got = tee.verify_round([zero] * missions,
                                   [[bytes([m]) * 32] * (1 + m % 5)
                                    for m in range(missions)], b"w")
            assert len(got) == missions and not any(got)
        assert compiles() == compiled
        assert eng is None \
            or eng.stats_snapshot()["programs_built"] == built
    finally:
        if eng is not None:
            eng.close()


def test_the_engine_counts_what_a_round_asked_of_the_device(ref, key):
    eng = make_engine(2, 1, podr2_key=key)
    try:
        rnd = make_round(ref, 9)
        a = eng.stats_snapshot()["classes"]["verify"]
        assert all(make_tee(key, eng).verify_round(rnd.blobs, rnd.owed,
                                                   rnd.seed))
        eng.flush()
        b = eng.stats_snapshot()["classes"]["verify"]
        rows = sum(SIZES)
        issued = -(-rows // podr2.ROUND_SUB) * podr2.ROUND_SUB
        challenged = len(podr2.gen_challenge(rnd.seed, BLOCKS)[0])
        assert b["batches"] - a["batches"] == 1
        assert b["missions"] - a["missions"] == len(SIZES)
        assert b["device_calls"] - a["device_calls"] == 2   # fold + close
        assert b["prf_evals"] - a["prf_evals"] == issued * challenged
        assert b["pad_waste"] == round((issued - rows) / issued, 4)
        metrics = eng.stats_metrics()
        for name in ("missions", "device_calls", "prf_evals"):
            assert metrics[f"cess_engine_verify_{name}"] == b[name]
            assert metrics[f"cess_engine_encode_{name}"] == 0
        for stage in ("queue", "assemble", "dispatch", "wait", "fetch",
                      "resolve"):
            assert b["stages"][stage]["n"] - a["stages"][stage]["n"] == 1
    finally:
        eng.close()


def test_more_rows_than_one_call_holds(ref, key, monkeypatch):
    """A round larger than ROUND_ROWS is folded a call at a time into
    one accumulator: shrunk here so three calls are a small round."""
    monkeypatch.setattr(podr2, "ROUND_ROWS", 2 * podr2.ROUND_SUB)
    sizes = (700, 3, 1500, 41)
    rnd = make_round(ref, 10, sizes=sizes)
    rows = podr2.round_rows(
        podr2.fragment_ids_from_hashes(h for hs in rnd.owed for h in hs),
        sizes)
    assert rows.steps == (2, 2, 1) and rows.rows_issued == 5 * podr2.ROUND_SUB
    assert rows.missions == 4 and rows.bucket == 8
    sigma = rnd.sigma.copy()
    sigma[2, 0] ^= 1            # the mission that spans all three calls
    blobs = [wire(u, s) for u, s in zip(rnd.mu, sigma)]
    assert make_tee(key).verify_round(blobs, rnd.owed, rnd.seed) \
        == [True, True, False, True]
    jax.clear_caches()          # the shrunk shape serves no later test


def test_submit_refuses_a_mis_shaped_round(key, engine):
    idx, nu = (np.asarray(a) for a in podr2.gen_challenge(b"s", BLOCKS))
    words = podr2.aggregate_words(b"s")
    ids = np.zeros((3, 2), np.uint32)
    mu = np.zeros((2, podr2.SECTORS), np.uint32)
    sigma = np.zeros((2, 2), np.uint32)
    for sizes in ([1, 1], [3, 0], [2]):        # short, an empty one, count
        with pytest.raises(ValueError):
            engine.submit_verify_round(ids, sizes, BLOCKS, idx, nu, words,
                                       mu, sigma)
    with pytest.raises(ValueError):
        engine.submit_verify_round(ids, [1, 2], BLOCKS, idx + BLOCKS, nu,
                                   words, mu, sigma)


# -- the proofs come late (PR 56) ------------------------------------------
def in_hand(key, engine, blobs, owed, seed) -> list:
    """The order before PR 56, kept as the reference: every proof is
    decoded first, only the decodable missions with an owed set go to
    the device, their proofs in hand at the submit."""
    tee = make_tee(key)
    decoded = [tee._decode_proof(blob) for blob in blobs]
    verdicts = [False] * len(blobs)
    live = []
    for i, (proof, hs) in enumerate(zip(decoded, owed)):
        if proof is None:
            continue
        if len(hs):
            live.append(i)
        else:
            verdicts[i] = not proof.sigma.any() and not proof.mu.any()
    if live:
        ids = np.concatenate([podr2.fragment_ids_from_hashes(owed[i])
                              for i in live])
        sizes = [len(owed[i]) for i in live]
        mu = np.stack([decoded[i].mu for i in live])
        sigma = np.stack([decoded[i].sigma for i in live])
        idx, nu = (np.asarray(a) for a in podr2.gen_challenge(seed, BLOCKS))
        words = podr2.aggregate_words(seed)
        if engine is not None:
            ok = engine.verify_round(ids, sizes, BLOCKS, idx, nu, words, mu,
                                     sigma, timeout=120)
        else:
            ok = np.asarray(podr2.round_dispatch(
                podr2.key_operands(key), podr2.round_rows(ids, sizes), idx,
                nu, words, mu, sigma))
        for i, good in zip(live, ok):
            verdicts[i] = bool(good)
    return verdicts


LATE_CASES = {                  # kind -> the verdict of mission 1
    "honest": True, "tampered": False, "undecodable": False,
    "sigma_not_below_p": False, "empty_owed_zero_proof": True,
    "empty_owed_nonzero_proof": False, "not_bytes": False}


@pytest.mark.parametrize("kind", sorted(LATE_CASES))
@pytest.mark.parametrize("through", ["engine", "direct"])
def test_late_proofs_judge_as_proofs_in_hand(ref, key, engine, kind, through):
    rnd = make_round(ref, 13, sizes=(3, 4, 2, 5))
    blobs, owed = rnd.blobs, [list(hs) for hs in rnd.owed]
    zero = np.zeros((podr2.SECTORS,), np.uint32)
    if kind == "tampered":
        blobs[1] = wire(rnd.mu[1] ^ np.uint32(2), rnd.sigma[1])
    elif kind == "undecodable":
        blobs[1] = blobs[1][:333]
    elif kind == "sigma_not_below_p":
        blobs[1] = wire(rnd.mu[1], np.full((2,), pf.P, np.uint32))
    elif kind == "empty_owed_zero_proof":
        owed[1], blobs[1] = [], wire(zero, zero[:2])
    elif kind == "empty_owed_nonzero_proof":
        owed[1] = []
    elif kind == "not_bytes":
        blobs[1] = None
    eng = engine if through == "engine" else None
    late = make_tee(key, eng).verify_round(blobs, owed, rnd.seed)
    assert late == in_hand(key, eng, blobs, owed, rnd.seed)
    assert late == [True, LATE_CASES[kind], True, True]


@pytest.mark.parametrize("through", ["engine", "direct"])
def test_a_partial_agent_still_judges(ref, key, engine, through):
    """As the benchmark's prove cell builds its verifier
    (traffic/prove_round.py): ``__new__`` and six attributes, a service
    proof over an owed tuple and the zero idle proof over none."""
    rnd = make_round(ref, 14, sizes=(6,))
    tee = object.__new__(TeeAgent)
    tee.key, tee.blocks = key, BLOCKS
    tee.engine = engine if through == "engine" else None
    tee.controller, tee.bls_sk, tee._submitted = "tee", None, set()
    tee.warm_verify(1)
    idle = wire(np.zeros(podr2.SECTORS, np.uint32), np.zeros(2, np.uint32))
    assert tee.verify_round([rnd.blobs[0], idle], [tuple(rnd.owed[0]), ()],
                            rnd.seed) == [True, True]
    assert tee.verify_round([idle, idle], [tuple(rnd.owed[0]), ()],
                            rnd.seed) == [False, True]


def _request(eng, rnd: Round, m: int, proofs=None, timeout=None):
    """Mission ``m`` of the round as a request of its own: its proofs
    late (``proofs``) or in hand."""
    idx, nu = (np.asarray(a) for a in podr2.gen_challenge(rnd.seed, BLOCKS))
    early = () if proofs is not None else (rnd.mu[m:m + 1],
                                           rnd.sigma[m:m + 1])
    return eng.submit_verify_round(
        podr2.fragment_ids_from_hashes(rnd.owed[m]), [len(rnd.owed[m])],
        BLOCKS, idx, nu, podr2.aggregate_words(rnd.seed), *early,
        timeout=timeout, proofs=proofs)


@pytest.fixture
def own_engine(key):
    eng = make_engine(2, 1, podr2_key=key)
    yield eng
    eng.close()


def _coalesced(eng, gate, rnd: Round, missions):
    """Requests with late proofs for these missions, in ONE batch: they
    queue behind a held batch and leave together when it is let go.
    Returns [(future, its LateProofs)]; every request's folds are out."""
    held = gate(eng, "verify_round")
    first = _request(eng, rnd, 0)
    assert held.running()
    reqs = []
    for m in missions:
        late = LateProofs()
        reqs.append((_request(eng, rnd, m, late, timeout=120), late))
    held.open()
    assert bool(first.result(timeout=120)[0])
    for _, late in reqs:
        assert late.folds_out(120)
    return reqs


def test_a_request_whose_decode_raises_fails_alone(ref, key, own_engine, gate):
    rnd = make_round(ref, 15, sizes=(3, 7, 2))
    a0 = own_engine.stats_snapshot()["classes"]["verify"]
    (bad, bad_late), (good, good_late) = _coalesced(own_engine, gate, rnd,
                                                    (1, 2))
    bad_late.fail(codec.CodecError("no proof in these bytes"))
    good_late.put(rnd.mu[2:3], rnd.sigma[2:3])
    assert good.result(timeout=120).tolist() == [True]
    with pytest.raises(codec.CodecError):
        bad.result(timeout=120)
    own_engine.flush()
    b = own_engine.stats_snapshot()["classes"]["verify"]
    assert b["batches"] - a0["batches"] == 2           # the held one, and theirs
    assert b["failed"] - a0["failed"] == 1
    assert b["completed"] - a0["completed"] == 2
    assert b["batched_requests"] - a0["batched_requests"] == 2


@pytest.mark.parametrize("fault", ["both_fail", "mis_shaped", "put_twice"])
def test_late_proofs_are_their_requests_own(ref, key, own_engine, gate,
                                            fault):
    rnd = make_round(ref, 16, sizes=(3, 7, 2))
    (one, one_late), (two, two_late) = _coalesced(own_engine, gate, rnd,
                                                  (1, 2))
    if fault == "both_fail":        # each its own failure, none the other's
        one_late.fail(KeyError("one"))
        two_late.fail(IndexError("two"))
        with pytest.raises(KeyError):
            one.result(timeout=120)
        with pytest.raises(IndexError):
            two.result(timeout=120)
    elif fault == "mis_shaped":     # two missions' proofs for one mission
        one_late.put(rnd.mu[:2], rnd.sigma[:2])
        two_late.put(rnd.mu[2:3], rnd.sigma[2:3])
        with pytest.raises(ValueError, match="expected mu"):
            one.result(timeout=120)
        assert two.result(timeout=120).tolist() == [True]
    else:                           # the first settlement stands
        one_late.put(rnd.mu[1:2], rnd.sigma[1:2])
        one_late.fail(KeyError("late for it"))
        two_late.fail(KeyError("first"))
        two_late.put(rnd.mu[2:3], rnd.sigma[2:3])
        assert one.result(timeout=120).tolist() == [True]
        with pytest.raises(KeyError, match="first"):
            two.result(timeout=120)
    # the batcher is alive and serves the next round
    assert _request(own_engine, rnd, 0).result(timeout=120).tolist() == [True]


def test_proofs_that_never_come_fail_by_the_requests_timeout(ref, key,
                                                             own_engine):
    rnd = make_round(ref, 17, sizes=(4, 2))
    a = own_engine.stats_snapshot()["classes"]["verify"]
    never = LateProofs()
    lost = _request(own_engine, rnd, 0, never, timeout=0.5)
    assert never.folds_out(120)             # its rows were folded
    with pytest.raises(EngineTimeout, match="no proofs"):
        lost.result(timeout=120)
    never.put(rnd.mu[:1], rnd.sigma[:1])    # too late: the failure stands
    assert isinstance(never.failure(), EngineTimeout)
    assert _request(own_engine, rnd, 1).result(timeout=120).tolist() == [True]
    own_engine.flush()
    b = own_engine.stats_snapshot()["classes"]["verify"]
    assert b["failed"] - a["failed"] == 1 and b["timeouts"] == a["timeouts"]
    assert b["completed"] - a["completed"] == 1
    # a request that is over before its folds: the caller is told so
    gone = LateProofs()
    with pytest.raises(ValueError):
        own_engine.submit_verify_round(
            np.zeros((3, 2), np.uint32), [2], BLOCKS, np.zeros(1, np.int32),
            np.zeros(1, np.uint32), np.zeros(2, np.uint32), proofs=gone)
    assert not gone.folds_out(0.2)


@pytest.mark.parametrize("proofs", ["late", "in_hand"])
def test_late_proofs_and_their_stage_count_one_a_batch(ref, key, own_engine,
                                                       proofs):
    rnd = make_round(ref, 18)
    tee = make_tee(key, own_engine)
    a = own_engine.stats_snapshot()["classes"]["verify"]
    for _ in range(3):
        if proofs == "late":
            assert all(tee.verify_round(rnd.blobs, rnd.owed, rnd.seed))
        else:
            assert in_hand(key, own_engine, rnd.blobs, rnd.owed, rnd.seed) \
                == [True] * len(SIZES)
    own_engine.flush()
    b = own_engine.stats_snapshot()["classes"]["verify"]
    assert b["batches"] - a["batches"] == 3
    assert b["late_proofs"] - a["late_proofs"] == 3 * (proofs == "late")
    stage = b["late"]["proofs"]
    assert stage["n"] - a["late"]["proofs"]["n"] == 3
    assert stage["n"] == sum(n for _, n, _ in stage["buckets"])
    assert stage["s"] == pytest.approx(sum(s for _, _, s in stage["buckets"]))
    assert b["device_calls"] - a["device_calls"] == 3 * 2   # a fold, a close
    metrics = own_engine.stats_metrics()
    assert metrics["cess_engine_verify_late_proofs"] == b["late_proofs"]
    assert metrics["cess_engine_verify_stage_proofs_count"] == stage["n"]
    assert metrics["cess_engine_verify_stage_proofs_seconds"] == stage["s"]
    assert metrics["cess_engine_encode_stage_proofs_count"] == 0


# -- spans -----------------------------------------------------------------
def test_a_round_is_one_span_with_its_stages_inside(ref, key, engine):
    rnd = make_round(ref, 11, sizes=(2, 5))
    tracer = obs.Tracer()
    with obs.armed(tracer):
        assert all(make_tee(key, engine).verify_round(
            rnd.blobs, rnd.owed, rnd.seed))
        engine.flush()
    spans = {s["name"]: s for s in tracer.finished()}
    outer = spans["tee.round"]
    order = ("ids", "challenge", "submit", "decode", "close", "gather")
    for stage in order:
        assert spans[f"tee.round.{stage}"]["parent_id"] == outer["span_id"]
    starts = [spans[f"tee.round.{stage}"]["ts_s"] for stage in order]
    assert starts == sorted(starts)          # the decode after the submit
    assert "engine.verify" in spans and "engine.verify.dispatch" in spans
    # the batch's wait for the proofs: from its folds' enqueue (which
    # ended the TEE's submit stage) until the TEE's close put them
    assert spans["engine.verify.proofs"]["parent_id"] \
        == spans["engine.verify.dispatch"]["parent_id"]
    assert spans["engine.verify.proofs"]["ts_s"] \
        <= spans["tee.round.decode"]["ts_s"]
    # the round's derivation is the program's own stage inside the TEE's,
    # and the caller's side of the request lies where the caller was
    assert spans["podr2.challenge"]["parent_id"] \
        == spans["tee.round.challenge"]["span_id"]
    assert spans["engine.verify.submit"]["parent_id"] \
        == spans["tee.round.submit"]["span_id"]
    assert spans["engine.verify"]["parent_id"] \
        == spans["tee.round.submit"]["span_id"]
    assert spans["engine.verify.result"]["parent_id"] \
        == spans["tee.round.gather"]["span_id"]


# -- the chain path --------------------------------------------------------
def _missions(ref, rnd: Round, blobs, owed):
    zero = wire(np.zeros(podr2.SECTORS, np.uint32), np.zeros(2, np.uint32))
    return [chain_audit.ProveInfo(
        miner=f"miner{m}",
        snapshot=chain_audit.MinerSnapshot(
            miner=f"miner{m}", idle_space=0, service_space=len(hs),
            service_frags=tuple(hs), fillers=()),
        idle_proof=zero, service_proof=blobs[m])
        for m, hs in enumerate(owed)]


class _Node:
    def __init__(self, missions=(), challenge=None):
        self.extrinsics = []
        state = types.SimpleNamespace(
            get=lambda pallet, item, who, default=(): missions)
        self.runtime = types.SimpleNamespace(
            state=state,
            audit=types.SimpleNamespace(challenge=lambda: challenge))

    def submit_extrinsic(self, account, call, *args):
        self.extrinsics.append((account, call, args))


@pytest.mark.parametrize("through", ["engine", "direct"])
def test_on_block_submits_the_extrinsics_it_always_did(ref, key, engine,
                                                       through):
    """One ``audit.submit_verify_result`` a mission, in mission order,
    with the verdicts the per-mission path gave; a mission whose result
    is already queued is not judged again."""
    rnd = make_round(ref, 12)
    blobs, owed, _, want = tampered("uncovered_fragment", rnd)
    missions = _missions(ref, rnd, blobs, owed)
    challenge = types.SimpleNamespace(
        start=40, net=types.SimpleNamespace(randoms=(rnd.seed[:3],
                                                     rnd.seed[3:])))
    node = _Node(missions, challenge)
    tee = make_tee(key, engine if through == "engine" else None)
    tee.on_block(node)
    assert node.extrinsics == [
        ("tee0", "audit.submit_verify_result",
         (f"miner{m}", True, want[m], b"")) for m in range(len(missions))]
    assert tee._submitted == {(f"miner{m}", 40)
                              for m in range(len(missions))}
    tee.on_block(node)                       # nothing new to judge
    assert len(node.extrinsics) == len(missions)
    tee._submitted.discard(("miner3", 40))   # one result was dropped
    tee.on_block(node)
    assert node.extrinsics[len(missions):] == [
        ("tee0", "audit.submit_verify_result", ("miner3", True, False, b""))]
    idle = _Node((), challenge)              # no mission: no work, no call
    tee.on_block(idle)
    tee.on_block(_Node(missions, None))
    assert idle.extrinsics == []
