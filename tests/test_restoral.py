"""A restoral through the miner's own entry point (PR 40, the deployment
``archival-restoral``): ``MinerAgent.restore_fragment`` -> in mode
``symbols`` a chain of k helpers, each folding its row into the aggregate
it was handed (``repair_symbol``, a request of the engine's repair class
on a regenerating codec), in mode ``fragments`` their k whole rows and one
reconstruct.

Every hop's aggregate and the stored fragment are held to the benchmark's
plain reference (``benchmark/reference/symbol_chain_ref.py``: the repair
row by Gauss-Jordan, the chain in NumPy table arithmetic, nothing of the
program in it) for all fourteen single-loss patterns at RS(10,4); a
fallback is counted, ends in a correct whole-fragment repair and never
stores bytes that fail their hash; the ingress is exact in both modes; an
aggregate is a host array between any two hops; the spans nest, the
counters show in the agent's and the engine's snapshots; a warmed engine
compiles nothing for a window of both modes. ``try_repair`` against the
entry point on a real runtime is in tests/test_network.py (its fixture is
there). Small sizes, CPU.
"""
import hashlib
import importlib
import os
import sys
import types

import jax
import numpy as np
import pytest

from cess_tpu import obs
from cess_tpu.models.pipeline import PipelineConfig
from cess_tpu.node.offchain import MinerAgent
from cess_tpu.ops import regen
from cess_tpu.ops.rs_ref import ReferenceCodec
from cess_tpu.resilience import faults
from cess_tpu.resilience.faults import FaultPlan, FaultSpec
from cess_tpu.serve import make_engine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
K, M, N = 10, 4, 4096
ROWS = K + M
CALLS = ("file_bank.claim_restoral_order",
         "file_bank.restoral_order_complete")


@pytest.fixture(scope="module")
def ref():
    """The benchmark's reference package, as benchmark/run.py sees it."""
    sys.path.insert(0, os.path.join(REPO, "benchmark"))
    try:
        return types.SimpleNamespace(
            chain=importlib.import_module("reference.symbol_chain_ref"),
            rs=importlib.import_module("reference.rs_ref"))
    finally:
        sys.path.remove(os.path.join(REPO, "benchmark"))


@pytest.fixture(scope="module")
def engine():
    eng = make_engine(K, M, rs_backend="regen")
    yield eng
    eng.close()


@pytest.fixture(scope="module")
def stripe():
    """(coded [14, n], hashes): one seeded segment under the oracle."""
    rng = np.random.default_rng(40)
    coded = ReferenceCodec(K, M).encode(
        rng.integers(0, 256, (K, N), dtype=np.uint8))
    return coded, tuple(hashlib.sha256(r).digest() for r in coded)


class Recorder:
    def __init__(self):
        self.extrinsics = []

    def submit_extrinsic(self, account, call, *args):
        self.extrinsics.append((account, call, args))


def tier(stripe, engine, mode):
    """(node, holders, rebuilder): fourteen holders of one row each and
    a rebuilder that holds nothing, all on one engine."""
    coded, hashes = stripe
    node = Recorder()
    pipe = types.SimpleNamespace(
        config=PipelineConfig(k=K, m=M, segment_size=K * N))
    holders = [MinerAgent(node, f"h{j}", [], pipe, engine=engine)
               for j in range(ROWS)]
    for j, holder in enumerate(holders):
        holder.store[hashes[j]] = coded[j].tobytes()
    rebuilder = MinerAgent(node, "rebuilder", [], pipe, engine=engine)
    rebuilder.set_repair_mode(mode)
    return node, holders, rebuilder


def peers_of(holders, row):
    return [h for j, h in enumerate(holders) if j != row]


def listen(holders, heard):
    """Every helper's answer, as it is handed on."""
    for holder in holders:
        def hop(frag_hash, coeff, acc=None, real=holder.repair_symbol):
            out = real(frag_hash, coeff, acc)
            heard.append((acc, out))
            return out
        holder.repair_symbol = hop


@pytest.mark.parametrize("with_engine", [True, False],
                         ids=["engine", "host-fold"])
@pytest.mark.parametrize("row", range(ROWS))
def test_every_hop_is_the_references(ref, stripe, engine, row, with_engine):
    """All fourteen single-loss patterns: each of the ten aggregates the
    helpers hand on, and the fragment the rebuilder stores, equal the
    plain reference's; every aggregate is a host array on both sides of
    a hop; the ingress is one fragment."""
    coded, hashes = stripe
    node, holders, reb = tier(stripe, engine if with_engine else None,
                              "symbols")
    heard = []
    listen(holders, heard)
    assert reb.restore_fragment(hashes, row, peers_of(holders, row))
    present = tuple(j for j in range(ROWS) if j != row)[:K]
    wanted = ref.chain.chain(K, M, present, row, coded[list(present)])
    assert len(heard) == K
    for (came_in, went_out), want in zip(heard, wanted):
        assert type(went_out) is np.ndarray and went_out.dtype == np.uint8
        assert came_in is None or type(came_in) is np.ndarray
        assert not isinstance(went_out, jax.Array)
        assert np.array_equal(went_out, want)
    assert heard[0][0] is None and all(
        nxt[0] is prev[1] for prev, nxt in zip(heard, heard[1:]))
    assert reb.store[hashes[row]] == coded[row].tobytes() \
        == wanted[-1].tobytes()
    assert np.array_equal(
        wanted[-1], ref.rs.ReferenceCodec(K, M).reconstruct(
            coded[list(present)], present, (row,))[0])
    c = reb.counters()
    assert (c["repair_ingress_bytes"], c["repair_recovered_bytes"],
            c["repair_symbol_repairs"], c["repair_whole_repairs"],
            c["repair_fallbacks"], c["repairs"]) == (N, N, 1, 0, 0, 1)
    assert node.extrinsics == [("rebuilder", call, (hashes[row],))
                               for call in CALLS]


@pytest.mark.parametrize("row", [0, 6, 13])
def test_whole_fragments_through_the_entry_point(stripe, engine, row):
    """Mode ``fragments``: the k lowest holders' rows go to the engine
    as views of their bytes, the ingress is k fragments, and the closed
    form serves the matrix."""
    coded, hashes = stripe
    node, holders, reb = tier(stripe, engine, "fragments")
    seen = []
    real = engine.reconstruct

    def reconstruct(survivors, present, missing, **kw):
        seen.append((survivors, present, missing))
        return real(survivors, present, missing, **kw)
    reb.engine = types.SimpleNamespace(codec=engine.codec,
                                       reconstruct=reconstruct)
    assert reb.restore_fragment(hashes, row, peers_of(holders, row))
    (survivors, present, missing), = seen
    assert present == tuple(j for j in range(ROWS) if j != row)[:K]
    assert missing == (row,)
    for j, surv in zip(present, survivors):
        assert np.shares_memory(
            surv, np.frombuffer(holders[j].store[hashes[j]], np.uint8))
    assert reb.store[hashes[row]] == coded[row].tobytes()
    c = reb.counters()
    assert (c["repair_ingress_bytes"], c["repair_recovered_bytes"],
            c["repair_symbol_repairs"], c["repair_whole_repairs"],
            c["repair_fallbacks"]) == (K * N, N, 0, 1, 0)
    assert [call for _, call, _ in node.extrinsics] == list(CALLS)


def corrupts():
    """The fifth aggregate handed on arrives with a bit flipped."""
    return faults.armed(FaultPlan({"offchain.symbol_bytes": {
        4: FaultSpec("corrupt", xor=0x20)}}))


@pytest.mark.parametrize("fault,reason,came_in", [
    ("refuses", "broken-chain", K * N),
    ("corrupts", "bad-hash", (K + 1) * N)])
def test_a_broken_chain_falls_back_and_stores_only_what_hashes(
        stripe, engine, fault, reason, came_in):
    """A helper that refuses, or an aggregate corrupted on its way: the
    fallback is counted and noted, the repair ends as a whole-fragment
    one, and what is stored hashes to its id."""
    from cess_tpu.obs import flight

    coded, hashes = stripe
    node, holders, reb = tier(stripe, engine, "symbols")
    rec = flight.FlightRecorder(b"restoral")
    with flight.armed(rec):
        if fault == "refuses":
            holders[5].repair_symbol = lambda *a, **kw: None
            ok = reb.restore_fragment(hashes, 1, peers_of(holders, 1))
        else:
            with corrupts():
                ok = reb.restore_fragment(hashes, 1, peers_of(holders, 1))
    assert ok
    assert hashlib.sha256(reb.store[hashes[1]]).digest() == hashes[1]
    c = reb.counters()
    assert (c["repair_fallbacks"], c["repair_whole_repairs"],
            c["repair_symbol_repairs"]) == (1, 1, 0)
    assert c["repair_ingress_bytes"] == came_in
    assert c["stage_count"]["miner.repair.chain"] == 1
    assert c["stage_count"]["miner.repair.fragments"] == 1
    assert c["stage_count"]["miner.repair.hash"] == 2
    notes = [n["detail"] for n in rec.journal_tail("repair")
             if n["kind"] == "fallback"]
    assert [n["reason"] for n in notes] == [reason]


def test_bytes_that_fail_their_hash_are_never_stored(stripe, engine):
    """A survivor whose bytes are not what its id says: both modes end
    without a store and without an extrinsic, and the fallback of the
    chain is counted."""
    coded, hashes = stripe
    for mode in ("symbols", "fragments"):
        node, holders, reb = tier(stripe, engine, mode)
        holders[2].store[hashes[2]] = bytes(N)
        assert not reb.restore_fragment(hashes, 0, peers_of(holders, 0))
        assert hashes[0] not in reb.store and node.extrinsics == []
        c = reb.counters()
        assert c["repairs"] == c["repair_recovered_bytes"] == 0
        assert c["repair_fallbacks"] == (mode == "symbols")
        assert "miner.repair.store" not in c["stage_count"]
    # too few holders answer: nothing is asked of anyone
    node, holders, reb = tier(stripe, engine, "symbols")
    assert not reb.restore_fragment(hashes, 0, holders[1:K])
    assert reb.counters()["repair_ingress_bytes"] == 0


def test_spans_nest_and_counters_show(stripe, tmp_path):
    """``cess:miner.repair`` and its parts are in a profiler trace, the
    helpers' hops with the engine's wait inside them; the agent's and
    the engine's snapshots and flat metrics carry the new counters."""
    coded, hashes = stripe
    eng = make_engine(K, M, rs_backend="regen")
    try:
        node, holders, reb = tier(stripe, eng, "symbols")
        reb.warm_restoral()
        eng.flush()
        before = eng.stats_snapshot()["classes"]["repair"]
        tracer = obs.Tracer()
        jax.profiler.start_trace(str(tmp_path))
        try:
            with obs.armed(tracer):
                with tracer.start("test.restoral"):
                    assert reb.restore_fragment(hashes, 4,
                                                peers_of(holders, 4))
                    reb.store.pop(hashes[4])
                    reb.set_repair_mode("fragments")
                    assert reb.restore_fragment(hashes, 4,
                                                peers_of(holders, 4))
        finally:
            jax.profiler.stop_trace()
        eng.flush()
        after = eng.stats_snapshot()["classes"]["repair"]
        flat = eng.stats_metrics()
    finally:
        eng.close()
    assert after["symbol_folds"] - before["symbol_folds"] == K
    assert after["batches"] - before["batches"] == K + 1
    assert after["linear_puts"] - before["linear_puts"] == K + 1
    assert after["patterns_new"] == before["patterns_new"]
    assert flat["cess_engine_repair_symbol_folds"] == after["symbol_folds"]
    c = reb.counters()
    assert c["stage_count"] == {
        "miner.repair": 2, "miner.repair.holders": 2,
        "miner.repair.chain": 1, "miner.repair.fragments": 1,
        "miner.repair.hash": 2, "miner.repair.store": 2,
        "miner.repair.report": 2}
    parts = sum(s for name, s in c["stage_seconds"].items()
                if name != "miner.repair")
    assert 0 < parts <= c["stage_seconds"]["miner.repair"]
    assert sum(h.counters()["stage_count"].get("miner.symbol.hop", 0)
               for h in holders) == K
    flat = reb.metrics()
    assert flat["cess_miner_repair_ingress_bytes_total"] == (K + 1) * N
    assert flat["cess_miner_repairs_total"] == 2
    assert flat["cess_miner_stage_repair_chain_count"] == 1
    assert flat["cess_miner_stage_repair_seconds"] \
        == c["stage_seconds"]["miner.repair"]
    # the tracer's spans: every stage closed, each under its parent
    spans = {s["span_id"]: s for s in tracer.finished()}
    by_name = {}
    for s in spans.values():
        by_name.setdefault(s["name"], []).append(s)
    assert len(by_name["miner.repair"]) == 2
    assert len(by_name["miner.symbol.hop"]) == K

    def parent(s):
        return spans[s["parent_id"]]["name"]
    for name in ("holders", "hash", "store", "report"):
        assert [parent(s) for s in by_name[f"miner.repair.{name}"]] \
            == ["miner.repair"] * 2
    assert parent(by_name["miner.repair.chain"][0]) == "miner.repair"
    assert parent(by_name["miner.repair.fragments"][0]) == "miner.repair"
    assert {parent(s) for s in by_name["miner.symbol.hop"]} \
        == {"miner.repair.chain"}
    waits = [parent(s) for s in by_name["engine.repair.result"]]
    assert sorted(waits) == ["miner.repair.fragments"] \
        + ["miner.symbol.hop"] * K
    # the profiler's trace: the same names, on the device's clock
    path = next(p for p in tmp_path.rglob("*.xplane.pb"))
    names = {}
    data = jax.profiler.ProfileData.from_file(str(path))
    for plane in data.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("cess:miner."):
                    names[e.name] = names.get(e.name, 0) + 1
    assert names == {"cess:miner.repair": 2, "cess:miner.repair.holders": 2,
                     "cess:miner.repair.chain": 1,
                     "cess:miner.repair.fragments": 1,
                     "cess:miner.repair.hash": 2,
                     "cess:miner.repair.store": 2,
                     "cess:miner.repair.report": 2,
                     "cess:miner.symbol.hop": K}


def test_a_warmed_regen_engine_compiles_nothing_for_either_mode(
        stripe, compiles):
    """``warm_restoral`` on a regenerating engine: the repair shape, the
    fold, the flatten and every coefficient of the fourteen patterns.
    A window of both modes over every pattern then compiles nothing,
    builds no program and no matrix."""
    coded, hashes = stripe
    eng = make_engine(K, M, rs_backend="regen")
    try:
        node, holders, reb = tier(stripe, eng, "symbols")
        reb.warm_restoral()
        eng.flush()
        before = eng.stats_snapshot()
        c0 = compiles()
        for mode in ("symbols", "fragments"):
            reb.set_repair_mode(mode)
            for row in range(ROWS):
                assert reb.restore_fragment(hashes, row,
                                            peers_of(holders, row))
                assert reb.store.pop(hashes[row]) == coded[row].tobytes()
        eng.flush()
        after = eng.stats_snapshot()
        assert compiles() == c0
        assert after["programs_built"] == before["programs_built"]
        assert after["classes"]["repair"]["patterns_new"] \
            == before["classes"]["repair"]["patterns_new"]
        assert len(eng.codec._cache) <= type(eng.codec).MATRICES
    finally:
        eng.close()
    made = reb.counters()
    assert made["repair_ingress_bytes"] == ROWS * N + ROWS * K * N
    assert made["repair_fallbacks"] == 0


def test_the_engine_takes_a_fold_as_its_two_rows(engine):
    """``submit_repair_symbol`` with the request as (accumulator,
    fragment) rows: the same answer as the stacked pair, the rows put
    from where they lie, and a wrong count refused."""
    rng = np.random.default_rng(7)
    acc, frag = rng.integers(0, 256, (2, N), dtype=np.uint8)
    want = regen.fold_symbol_host(acc, frag, 0x53)
    frag.flags.writeable = False            # a view of held bytes
    before = engine.stats_snapshot()["classes"]["repair"]
    out = engine.repair_symbol([acc, frag], 0x53)
    assert type(out) is np.ndarray and out.shape == (1, N)
    assert np.array_equal(out[0], want)
    assert np.array_equal(
        engine.repair_symbol(np.stack([acc, frag]), 0x53)[0], want)
    engine.flush()
    after = engine.stats_snapshot()["classes"]["repair"]
    assert after["symbol_folds"] - before["symbol_folds"] == 2
    assert after["linear_puts"] - before["linear_puts"] == 2
    with pytest.raises(ValueError, match="2 rows"):
        engine.submit_repair_symbol([acc, frag, acc], 0x53)
