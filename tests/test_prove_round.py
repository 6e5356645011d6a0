"""A storage miner's audit round over what it holds (PR 38, the
deployment ``miner-deal-cap``): ``MinerAgent.prove_round`` -> the
engine's prove class (or ``podr2.prove_held`` without one) -> a host
gather and a device fold ``podr2.PROVE_CHUNK`` fragments at a time.

The wire bytes are held to the benchmark's plain reference
(``benchmark/reference/prove_round_ref.py``: the published equations in
NumPy uint64, fragment by fragment, nothing of the program in it) on
seeded sets below, at and above a chunk; to themselves however the set
is submitted (through the engine or without one, in another order,
coalesced with another miner of the round); to the verifier (a dropped
fragment and a flipped byte fail it); to the compile counter (a second
round at another size compiles nothing); and to the store: the buffers
the engine gathers from are the miner's own. Small sizes, CPU; the
chunk is shrunk to four fragments so that several chunks stay small.
"""
import hashlib
import importlib
import os
import sys
import threading
import types

import jax
import numpy as np
import pytest

from cess_tpu import codec
from cess_tpu.node.offchain import MinerAgent, Proof, TeeAgent, build_proof
from cess_tpu.ops import podr2
from cess_tpu.serve import AdmissionPolicy, make_engine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BLOCKS = 128                       # 5 challenged a round
NBYTES = BLOCKS * podr2.BLOCK_BYTES
KEY_SEED = 24
CHUNK = 4
SIZES = (1, CHUNK - 1, CHUNK, CHUNK + 1, 3 * CHUNK + 2)
POOL = 3 * CHUNK + 3               # fragments made once a module


@pytest.fixture(scope="module")
def ref():
    """The benchmark's reference package, as benchmark/run.py sees it."""
    sys.path.insert(0, os.path.join(REPO, "benchmark"))
    try:
        return types.SimpleNamespace(
            round=importlib.import_module("reference.prove_round_ref"),
            podr2=importlib.import_module("reference.podr2_ref"))
    finally:
        sys.path.remove(os.path.join(REPO, "benchmark"))


@pytest.fixture(autouse=True)
def small_chunk(monkeypatch):
    monkeypatch.setattr(podr2, "PROVE_CHUNK", CHUNK)


@pytest.fixture(scope="module")
def key():
    return podr2.Podr2Key.generate(KEY_SEED)


@pytest.fixture(scope="module")
def engine(key):
    eng = make_engine(2, 1, podr2_key=key,
                      policy=AdmissionPolicy(max_delay=0.001))
    yield eng
    eng.close()


@pytest.fixture(scope="module")
def held(key):
    """(hashes, store, tags): POOL seeded fragments as a miner holds
    them — ``bytes`` under their hash, one tag array a fragment."""
    rng = np.random.default_rng(38)
    blobs = [rng.integers(0, 256, NBYTES, dtype=np.uint8).tobytes()
             for _ in range(POOL)]
    hashes = [hashlib.sha256(b).digest() for b in blobs]
    tags = np.asarray(podr2.tag_fragments(
        key, podr2.fragment_ids_from_hashes(hashes),
        np.stack([np.frombuffer(b, np.uint8) for b in blobs])))
    return (hashes, dict(zip(hashes, blobs)),
            {h: np.ascontiguousarray(t) for h, t in zip(hashes, tags)})


def miner(held, engine=None, account="m1") -> MinerAgent:
    _, store, tags = held
    return MinerAgent.custodian(dict(store), dict(tags), engine=engine,
                                account=account)


def make_tee(key, engine=None) -> TeeAgent:
    tee = object.__new__(TeeAgent)
    tee.key, tee.blocks, tee.engine = key, BLOCKS, engine
    tee.controller, tee.bls_sk, tee._submitted = "tee0", None, set()
    return tee


def ref_wire(ref, seed, owed, store, tags) -> bytes:
    hs = [h for h in owed if h in store]
    mu, sigma = ref.round.prove(seed, hs, [store[h] for h in hs],
                                [tags[h] for h in hs], BLOCKS)
    return codec.encode(Proof(mu=mu, sigma=sigma))


@pytest.mark.parametrize("how", ["engine", "direct"])
@pytest.mark.parametrize("size", SIZES)
def test_wire_bytes_equal_the_reference(ref, key, engine, held, size, how):
    """Below, at and above a chunk, ragged: the program's wire bytes
    are the reference's, and the reference verifier accepts them."""
    hashes, store, tags = held
    owed = hashes[:size]
    seed = b"round:%d" % size
    m = miner(held, engine if how == "engine" else None)
    got = m.prove_round(seed, owed)
    assert got == ref_wire(ref, seed, owed, store, tags)
    proof = codec.decode(got)
    assert ref.round.accepted(ref.podr2.generate_key(KEY_SEED), seed,
                              BLOCKS, owed, proof.mu, proof.sigma)
    # the free function is the same call
    assert build_proof(seed, owed, store, tags,
                       engine=m.engine) == got


def test_zero_proof_and_limb_width(held):
    """An empty held set is the all-zero proof at the deployment's limb
    width; a fragment not held does not contribute."""
    hashes, store, tags = held
    blob = miner(held).prove_round(b"s", [])
    proof = codec.decode(blob)
    assert not proof.mu.any() and proof.sigma.shape == (podr2.LIMBS,)
    wide = MinerAgent.custodian({}, {}, limbs=3).prove_round(b"s", hashes)
    assert codec.decode(wide).sigma.shape == (3,)
    assert build_proof(b"s", [], {}, {}, limbs=3) == wide
    m = miner(held)
    some = hashes[:CHUNK + 2]
    lost = some[1]
    del m.store[lost]
    assert m.prove_round(b"s", some) \
        == miner(held).prove_round(b"s", [h for h in some if h != lost])


@pytest.mark.parametrize("how", ["engine", "direct"])
def test_same_proof_in_any_order(engine, held, how):
    """Neither the order the store was filled in nor the order the
    owed set names its fragments changes a bit of the proof."""
    hashes, store, tags = held
    owed = hashes[:2 * CHUNK + 1]
    eng = engine if how == "engine" else None
    want = miner(held, eng).prove_round(b"order", owed)
    back = MinerAgent.custodian(
        {h: store[h] for h in reversed(hashes)},
        {h: tags[h] for h in reversed(hashes)}, engine=eng)
    assert back.prove_round(b"order", owed) == want
    assert back.prove_round(b"order", owed[::-1]) == want
    # engine or no engine: the same bytes
    assert miner(held, None if eng else engine).prove_round(
        b"order", owed) == want


def test_two_miners_of_one_round_coalesce(key, held):
    """Two miners answering the same round share a device batch (one
    row each of [miners, chunk, ...], ragged in their chunk counts)
    and get what each gets alone."""
    hashes, _, _ = held
    sets = {"m1": hashes[:CHUNK - 1], "m2": hashes[2:2 * CHUNK + 3]}
    alone = {a: miner(held).prove_round(b"shared", owed)
             for a, owed in sets.items()}
    eng = make_engine(2, 1, podr2_key=key,
                      policy=AdmissionPolicy(max_delay=0.5))
    try:
        got = {}
        threads = [threading.Thread(
            target=lambda a=a, owed=owed: got.__setitem__(
                a, miner(held, eng, a).prove_round(b"shared", owed)))
            for a, owed in sets.items()]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        eng.flush()
        prove = eng.stats_snapshot()["classes"]["prove"]
    finally:
        eng.close()
    assert got == alone
    assert (prove["batches"], prove["batch_occupancy"]) == (1, 2.0)
    # the larger miner's steps: its 11 fragments, four a step
    assert prove["chunks"] == prove["device_calls"] == 3
    assert prove["rows"] == CHUNK - 1 + 2 * CHUNK + 1
    assert prove["padded_rows"] == 2 * CHUNK * 3 - prove["rows"]


@pytest.mark.parametrize("how", ["engine", "direct"])
def test_a_dropped_fragment_fails_the_verifier(ref, key, engine, held, how):
    hashes, _, _ = held
    owed = hashes[:2 * CHUNK + 1]
    eng = engine if how == "engine" else None
    m = miner(held, eng)
    tee = make_tee(key, eng)
    seed = b"drop"
    honest = m.prove_round(seed, owed)
    del m.store[owed[CHUNK]]
    short = m.prove_round(seed, owed)
    assert tee.verify_round([honest, short], [owed, owed], seed) \
        == [True, False]
    proof = codec.decode(short)
    assert not ref.round.accepted(ref.podr2.generate_key(KEY_SEED), seed,
                                  BLOCKS, owed, proof.mu, proof.sigma)


@pytest.mark.parametrize("how", ["engine", "direct"])
def test_a_flipped_byte_in_a_challenged_block_is_rejected(key, engine,
                                                          held, how):
    """Every challenged block of every owed fragment is read from the
    held bytes in its round: one bit of one of them fails the round,
    in the first chunk and in the last."""
    hashes, _, _ = held
    owed = hashes[:3 * CHUNK + 2]
    eng = engine if how == "engine" else None
    tee = make_tee(key, eng)
    seed = b"flip"
    idx = np.asarray(podr2.gen_challenge(seed, BLOCKS)[0])
    for victim, block in ((owed[0], idx[0]), (owed[-1], idx[-1])):
        m = miner(held, eng)
        bad = bytearray(m.store[victim])
        bad[int(block) * podr2.BLOCK_BYTES + 7] ^= 0x10
        m.store[victim] = bytes(bad)
        assert tee.verify_round([m.prove_round(seed, owed)], [owed],
                                seed) == [False]
    assert tee.verify_round([miner(held, eng).prove_round(seed, owed)],
                            [owed], seed) == [True]


@pytest.mark.parametrize("how", ["engine", "direct"])
def test_a_second_round_at_another_size_compiles_nothing(
        key, held, compiles, how):
    """Past a chunk the chunk is the only shape: custody that grows (or
    shrinks) and a new seed compile nothing and build no program."""
    hashes, _, _ = held
    eng = make_engine(2, 1, podr2_key=key,
                      policy=AdmissionPolicy(max_delay=0.001)) \
        if how == "engine" else None
    try:
        m = miner(held, eng)
        m.prove_round(b"warm", hashes[:CHUNK + 1])      # two steps
        built = eng.stats_snapshot()["programs_built"] if eng else 0
        coeffs = podr2.stage_counters()["podr2.coeffs"]["programs"]
        before = compiles()
        for n, seed in ((3 * CHUNK + 1, b"grown"), (2 * CHUNK, b"even"),
                        (POOL, b"all")):
            m.prove_round(seed, hashes[:n])
        assert compiles() == before
        assert podr2.stage_counters()["podr2.coeffs"]["programs"] == coeffs
        if eng:
            assert eng.stats_snapshot()["programs_built"] == built == 2
    finally:
        if eng:
            eng.close()


def test_chunk_plan_pads_below_a_chunk_and_steps_past_it():
    """(fragments a device step, steps): a held set below a chunk pads
    to its power of two and runs in one step (one program a bucket);
    past a chunk every step is a whole chunk (one program more)."""
    assert [podr2.chunk_plan(rows) for rows in (0, 1, 2, 3, CHUNK)] == \
        [(1, 1), (1, 1), (2, 1), (4, 1), (CHUNK, 1)]
    assert [podr2.chunk_plan(rows)
            for rows in (CHUNK + 1, 2 * CHUNK, 3 * CHUNK + 2)] == \
        [(CHUNK, 2), (CHUNK, 2), (CHUNK, 4)]


def test_round_coeffs_are_aggregate_coeffs_in_fixed_pieces(monkeypatch):
    """r as host words, from calls of one shape past COEFF_ROWS."""
    ids = np.random.default_rng(5).integers(
        0, 2 ** 32, (21, 2), dtype=np.uint64).astype(np.uint32)
    want = np.asarray(podr2.aggregate_coeffs(b"r", ids))
    assert np.array_equal(podr2.round_coeffs(b"r", ids), want)
    monkeypatch.setattr(podr2, "COEFF_ROWS", 8)
    got = podr2.round_coeffs(b"r", ids)
    assert isinstance(got, np.ndarray) and np.array_equal(got, want)
    assert np.array_equal(podr2.round_coeffs(b"r", ids[:1]), want[:1])


def test_the_store_is_not_copied(key, held, monkeypatch):
    """What the engine gathers from are views of the miner's own
    ``bytes`` and its own tag arrays, and nothing stacks the set on the
    batcher's side either."""
    hashes, _, _ = held
    owed = hashes[:2 * CHUNK + 1]
    eng = make_engine(2, 1, podr2_key=key,
                      policy=AdmissionPolicy(max_delay=0.001))
    seen = []
    real = eng._op_prove

    def no_stack(*a, **k):
        raise AssertionError("the held set was stacked")

    def spy(batch, degraded=False, lane=None):
        seen.extend((r.arrays["fragments"], r.arrays["tags"])
                    for r in batch)
        with monkeypatch.context() as mp:
            mp.setattr(np, "stack", no_stack)
            mp.setattr(np, "concatenate", no_stack)
            return real(batch, degraded, lane)
    monkeypatch.setattr(eng, "_op_prove", spy)
    try:
        m = miner(held, eng)
        assert m.prove_round(b"views", owed) \
            == miner(held).prove_round(b"views", owed)
    finally:
        eng.close()
    (frags, tags), = seen
    assert isinstance(frags, podr2.HeldRows) and len(frags) == len(owed)
    for h, row, tag in zip(owed, frags, tags):
        assert np.shares_memory(row, np.frombuffer(m.store[h], np.uint8))
        assert not row.flags.owndata and tag is m.tags[h]


def test_counters_and_spans_of_a_round(key, held, tmp_path):
    """``cess:miner.round`` and its five parts are in a profiler trace,
    and the prove class counts its steps, gathered bytes and seconds;
    a chunked batch still counts each stage once."""
    hashes, _, _ = held
    owed = hashes[:2 * CHUNK + 1]
    eng = make_engine(2, 1, podr2_key=key,
                      policy=AdmissionPolicy(max_delay=0.001))
    try:
        m = miner(held, eng)
        m.prove_round(b"warm", owed)
        eng.flush()
        before = eng.stats_snapshot()["classes"]["prove"]
        jax.profiler.start_trace(str(tmp_path))
        try:
            m.prove_round(b"traced", owed)
        finally:
            jax.profiler.stop_trace()
        eng.flush()
        after = eng.stats_snapshot()["classes"]["prove"]
        flat = eng.stats_metrics()
    finally:
        eng.close()
    c = len(podr2.gen_challenge(b"traced", BLOCKS)[0])
    assert after["chunks"] - before["chunks"] == 3
    assert after["device_calls"] - before["device_calls"] == 3
    assert after["gathered_bytes"] - before["gathered_bytes"] \
        == len(owed) * c * (podr2.BLOCK_BYTES + podr2.LIMBS * 4)
    assert after["gather_seconds"] > before["gather_seconds"]
    assert after["gather_seconds"] == after["stages"]["assemble"]["s"]
    assert {s["n"] for s in after["stages"].values()} == {after["batches"]}
    for name in ("chunks", "gathered_bytes", "gather_seconds"):
        assert f"cess_engine_prove_{name}" in flat
    path = next(p for p in tmp_path.rglob("*.xplane.pb"))
    names = set()
    data = jax.profiler.ProfileData.from_file(str(path))
    for plane in data.planes:
        for line in plane.lines:
            names.update(e.name for e in line.events
                         if e.name.startswith("cess:miner."))
    assert names == {"cess:miner.round"} | {
        f"cess:miner.round.{part}"
        for part in ("ids", "challenge", "coeffs", "submit", "encode")}


def test_submit_proof_sends_what_it_always_did(ref, held):
    """The chain path: ``_submit_proof`` answers the frozen snapshot
    through ``prove_round``, service and idle alike, and submits one
    ``audit.submit_proof`` with both proofs."""
    hashes, store, tags = held
    sent = []
    node = types.SimpleNamespace(
        submit_extrinsic=lambda who, call, *args: sent.append(
            (who, call, args)))
    m = miner(held, account="m7")
    fillers = hashes[-2:]
    m.filler_store = {h: store[h] for h in fillers}
    m.filler_tags = {h: tags[h] for h in fillers}
    ch = types.SimpleNamespace(
        start=9, net=types.SimpleNamespace(randoms=(b"ab", b"cd")),
        miners=[types.SimpleNamespace(miner="m0", service_frags=(),
                                      fillers=()),
                types.SimpleNamespace(miner="m7",
                                      service_frags=tuple(hashes[:CHUNK + 2]),
                                      fillers=tuple(fillers))])
    m._submit_proof(node, ch)
    assert sent == [("m7", "audit.submit_proof", (
        ref_wire(ref, b"abcd", fillers, store, tags),
        ref_wire(ref, b"abcd", hashes[:CHUNK + 2], store, tags)))]


def test_on_block_in_a_sim_still_passes_its_audits():
    """``MinerAgent.on_block`` in a simulated network: honest miners'
    rounds are accepted, service and idle, and the miners that stored
    corrupt bytes fail their service audit (the audit-soundness
    invariant is the scenario's final check)."""
    from cess_tpu.sim import SCENARIOS, run_scenario

    report = run_scenario(SCENARIOS["adversarial_audit"], b"prove-round",
                          n_nodes=12)
    rt = report.world.nodes[0].runtime
    adversarial = {f"m{j}" for j in report.world.storage.adversarial_miners}
    verdicts = [dict(e.data)
                for e in rt.state.events_of("audit", "VerifyResult")]
    honest = [d for d in verdicts if d["miner"] not in adversarial]
    assert honest and all(d["service"] and d["idle"] for d in honest)
    assert any(not d["service"] for d in verdicts
               if d["miner"] in adversarial)
