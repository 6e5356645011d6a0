"""One repair program per SHAPE, the erasure pattern an argument.

The archival tier (RS(10,4), benchmark configuration ``archival-wide``)
repairs a stripe from whichever ten helpers answer: 4,004 single-loss
patterns, so nothing on the repair path may be kept, compiled or built
per pattern. Pinned here on the CPU mesh at small n:

- ``engine.reconstruct`` equals ``ReferenceCodec.reconstruct`` byte for
  byte at (10,4), (4,8) and (2,1) for 1-4 lost rows with the helper set
  drawn at random from the survivors;
- after ``warm_repair`` of four shapes, 300+ patterns never seen before
  build no program and compile nothing, and the codec's and the engine's
  caches stay under their stated bounds;
- a pattern that cannot be served (a repeated or out-of-range row, fewer
  than k helpers) is refused, and leaves nothing behind;
- a miner's ``try_repair`` at (10,4) with three holders silent repairs
  from a helper set that is not the k lowest survivors.
"""
import types

import numpy as np
import pytest

from cess_tpu.crypto.hashing import fragment_hash
from cess_tpu.models.pipeline import PipelineConfig, StoragePipeline
from cess_tpu.node.offchain import MinerAgent
from cess_tpu.ops.rs import TPUCodec
from cess_tpu.ops.rs_ref import ReferenceCodec
from cess_tpu.serve import AdmissionPolicy, make_engine

N = 256


def _engine(k, m):
    return make_engine(k, m, rs_backend="jax",
                       policy=AdmissionPolicy(max_delay=0.001))


def _draw(rng, k, m, e):
    """e lost rows uniform of k+m, k helpers uniform of the survivors,
    both ascending (traffic kind repair_helpers draws the same way)."""
    lost = tuple(sorted(rng.choice(k + m, e, replace=False).tolist()))
    surv = [j for j in range(k + m) if j not in lost]
    helpers = tuple(sorted(rng.choice(surv, k, replace=False).tolist()))
    return helpers, lost


@pytest.mark.parametrize("k,m,e", [
    (10, 4, 1), (10, 4, 2), (10, 4, 3), (10, 4, 4),
    (4, 8, 1), (4, 8, 2), (4, 8, 3), (4, 8, 4),
    (2, 1, 1)])
def test_reconstruct_equals_reference_for_random_helpers(k, m, e):
    rng = np.random.default_rng(1000 * k + 10 * m + e)
    ref = ReferenceCodec(k, m)
    coded = ref.encode(rng.integers(0, 256, (k, N), dtype=np.uint8))
    eng = _engine(k, m)
    lost_kinds, helper_kinds = set(), set()
    try:
        for _ in range(12):
            helpers, lost = _draw(rng, k, m, e)
            surv = coded[list(helpers)]
            got = np.asarray(eng.reconstruct(surv, helpers, lost))
            want = ref.reconstruct(surv, helpers, lost)
            assert np.array_equal(got, want), (helpers, lost)
            assert np.array_equal(got, coded[list(lost)])
            lost_kinds |= {j < k for j in lost}
            helper_kinds |= {j < k for j in helpers}
    finally:
        eng.close()
    if (k, m) != (2, 1):
        # data rows and parity rows both among the lost and the helpers
        assert lost_kinds == helper_kinds == {True, False}


@pytest.mark.parametrize("form", ["array", "rows"])
def test_unseen_patterns_build_no_program_and_compile_nothing(form,
                                                              compiles):
    """``form``: the survivors as ``[q, n]``, or as the list of their q
    host rows (a miner's fetched fragments, PR 32): either way they go
    up as linear rows, through the one program the shape was warmed
    with."""
    from cess_tpu.ops import rs

    k, m = 10, 4
    rng = np.random.default_rng(31)
    coded = ReferenceCodec(k, m).encode(
        rng.integers(0, 256, (k, N), dtype=np.uint8))
    eng = _engine(k, m)
    try:
        codec = eng.codec
        eng.warm_repair([(tuple(range(e, e + k)), tuple(range(e)))
                         for e in (1, 2, 3, 4)], N, buckets=(1,))
        eng.flush()
        warmed = eng.stats_snapshot()
        # four shapes: a repair program and a flatten each
        assert warmed["programs_built"] == len(eng.programs) == 8
        compiled = compiles()
        stackers = rs._apply_rows._cache_size()
        seen = set()
        while len(seen) < 300:
            helpers, lost = _draw(rng, k, m, int(rng.integers(1, 5)))
            if (helpers, lost) in seen or helpers[0] == len(lost):
                continue            # a new pattern, none of the warmed
            seen.add((helpers, lost))
            surv = coded[list(helpers)]
            got = np.asarray(eng.reconstruct(
                list(surv) if form == "rows" else surv, helpers, lost))
            assert np.array_equal(got, coded[list(lost)])
        eng.flush()
        snap = eng.stats_snapshot()
        assert snap["programs_built"] == warmed["programs_built"]
        # every one of them ran a warmed shape's program: the one that
        # stacks the rows and applies the matrix, one a shape
        assert compiles() == compiled
        assert rs._apply_rows._cache_size() == stackers
        assert snap["classes"]["repair"]["linear_puts"] \
            == snap["classes"]["repair"]["batches"] == 300
        # the bounds: 8 programs and the codec's newest MATRICES
        # matrices; the codec keeps no executable of its own
        assert len(eng.programs) == 8
        assert len(codec._cache) <= TPUCodec.MATRICES == 64
        repair = snap["classes"]["repair"]
        # make_codec hands every (10,4) engine of the process one codec:
        # at most MATRICES of the 300 can have been held already
        assert 300 - TPUCodec.MATRICES <= repair["patterns_new"] <= 300
        assert repair["matrix_build_s"] > 0
        assert repair["failed"] == 0
        assert eng.stats_metrics()["cess_engine_repair_patterns_new"] \
            == repair["patterns_new"]
    finally:
        eng.close()


@pytest.mark.parametrize("present,missing,match", [
    ((0, 1, 2, 3, 4, 5, 6, 7, 8, 8), (9,), "duplicate"),
    ((0, 1, 2, 3, 4, 5, 6, 7, 8, 14), (9,), "out of range"),
    ((0, 1, 2, 3, 4, 5, 6, 7, 8, 10), (14,), "out of range"),
    ((0, 1, 2, 3, 4, 5, 6, 7, 8), (9,), "need exactly k=10"),
])
def test_unservable_pattern_is_refused(present, missing, match):
    codec = TPUCodec(10, 4)
    surv = np.zeros((len(present), N), np.uint8)
    with pytest.raises(ValueError, match=match):
        codec.reconstruct(surv, present, missing)
    assert not codec._cache
    eng = _engine(10, 4)
    try:
        with pytest.raises(ValueError, match=match):
            eng.reconstruct(surv, present, missing)
        # still serving
        good = tuple(range(1, 11))
        coded = ReferenceCodec(10, 4).encode(
            np.arange(10 * N, dtype=np.uint8).reshape(10, N))
        got = np.asarray(eng.reconstruct(coded[list(good)], good, (0,)))
        assert np.array_equal(got[0], coded[0])
    finally:
        eng.close()


def _restoral_world(eng, k, m, lost_row, silent):
    """One (k, m) stripe on k + m peer miners, ``lost_row`` lost and
    the ``silent`` rows' holders empty-handed, a restoral order open,
    and a rescuer on ``eng``: (rescuer, peers, hashes, blobs, cfg,
    extrinsics sent)."""
    cfg = PipelineConfig(k=k, m=m, segment_size=k * 1024)
    pipe = StoragePipeline(cfg)
    rng = np.random.default_rng(5)
    coded = ReferenceCodec(k, m).encode(
        rng.integers(0, 256, (k, cfg.fragment_size), dtype=np.uint8))
    blobs = [row.tobytes() for row in coded]
    hashes = [fragment_hash(b) for b in blobs]
    seg = types.SimpleNamespace(fragment_hashes=hashes)
    bank = types.SimpleNamespace(
        restoral_order=lambda h: types.SimpleNamespace(file_hash=b"f"),
        file=lambda fh: types.SimpleNamespace(segments=[seg]))
    sent = []
    node = types.SimpleNamespace(
        runtime=types.SimpleNamespace(file_bank=bank),
        submit_extrinsic=lambda *a: sent.append(a[1]))
    peers = []
    for j in range(k + m):
        peer = MinerAgent(node, f"h{j}", [], pipe)
        if j != lost_row and j not in silent:
            peer.store[hashes[j]] = blobs[j]
        peers.append(peer)
    rescuer = MinerAgent(node, "rescuer", [], pipe, engine=eng)
    return rescuer, peers, hashes, blobs, cfg, sent


def test_try_repair_at_10p4_with_three_holders_silent():
    """The ten helpers are whichever peers hold their row: with rows 1,
    4 and 7 silent the set is not the k lowest survivors, and the warmed
    shape's program serves it (nothing is built under the claim)."""
    k, m = 10, 4
    lost_row = 2
    eng = _engine(k, m)
    try:
        rescuer, peers, hashes, blobs, cfg, sent = _restoral_world(
            eng, k, m, lost_row, silent=(1, 4, 7))
        rescuer.warm_restoral()
        built = eng.stats_snapshot()["programs_built"]
        assert rescuer.try_repair(hashes[lost_row], peers)
        assert rescuer.store[hashes[lost_row]] == blobs[lost_row]
        assert rescuer.repair_ingress_bytes == k * cfg.fragment_size
        assert sent == ["file_bank.claim_restoral_order",
                        "file_bank.restoral_order_complete"]
        eng.flush()
        snap = eng.stats_snapshot()
        assert snap["programs_built"] == built
        # helpers (0, 3, 5, 6, 8, ..., 13): not a set warm_restoral
        # named (0 only if another test of this process left the one
        # (10,4) codec make_codec hands out holding it)
        assert snap["classes"]["repair"]["patterns_new"] <= 1
    finally:
        eng.close()


@pytest.mark.parametrize("k,m", [(2, 1), (10, 4)])
def test_repair_via_fragments_hands_the_holders_rows_over(k, m, monkeypatch,
                                                          compiles):
    """``_repair_via_fragments`` reaches the engine with the k
    fragments as they lie in the holders' stores (read-only views of
    their ``bytes``: no stacked array in between), they go up as linear
    rows through the warmed program, and the repaired fragment
    re-hashes to its on-chain identity."""
    lost_row = 1
    eng = _engine(k, m)
    try:
        rescuer, peers, hashes, blobs, cfg, _ = _restoral_world(
            eng, k, m, lost_row, silent=())
        rescuer.warm_restoral()
        handed = []
        submit = eng.submit_reconstruct

        def submit_reconstruct(survivors, present, missing=None, **kw):
            handed.append((survivors, present, missing))
            return submit(survivors, present, missing, **kw)

        monkeypatch.setattr(eng, "submit_reconstruct", submit_reconstruct)

        def no_stack(*a, **kw):
            raise AssertionError("np.stack between the stores and the "
                                 "engine")

        built = eng.stats_snapshot()["programs_built"]
        compiled = compiles()
        with monkeypatch.context() as mp:
            mp.setattr(np, "stack", no_stack)
            assert rescuer.try_repair(hashes[lost_row], peers)
        (survivors, present, missing), = handed
        assert missing == (lost_row,) and len(survivors) == k
        for row, j in zip(survivors, present):
            assert row.ndim == 1 and not row.flags.writeable
            assert row.base is peers[j].store[hashes[j]]     # a view
        assert fragment_hash(rescuer.store[hashes[lost_row]]) \
            == hashes[lost_row]
        assert rescuer.store[hashes[lost_row]] == blobs[lost_row]
        eng.flush()
        snap = eng.stats_snapshot()
        assert snap["programs_built"] == built and compiles() == compiled
        assert snap["classes"]["repair"]["linear_puts"] \
            == snap["classes"]["repair"]["batches"] == 1
    finally:
        eng.close()
