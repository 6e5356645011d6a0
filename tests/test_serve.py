"""Device submission engine (cess_tpu/serve): batch coalescing
determinism, bucket padding, priority, backpressure/timeout contracts,
and the stats surface through node/metrics.py + RPC.

The hard invariant throughout: engine-mediated results are
BIT-IDENTICAL to the direct ErasureCodec / AuditBackend calls —
the engine decides WHEN and HOW BATCHED device work runs, never what
it computes (protocol determinism, like the codec gate itself).
"""
import collections
import contextlib
import sys
import threading
import types

import numpy as np
import pytest

from cess_tpu.ops import podr2, rs
from cess_tpu.serve import (AdaptiveBatchPolicy, AdmissionPolicy,
                            EngineClosed, EngineSaturated, EngineTimeout,
                            make_engine)
from cess_tpu.serve.engine import SubmissionEngine

K, M = 2, 1
FRAG = 1024               # bytes per fragment -> 2 PoDR2 blocks


@pytest.fixture(scope="module")
def pkey():
    return podr2.Podr2Key.generate(21)


@pytest.fixture()
def engine(pkey):
    eng = make_engine(K, M, podr2_key=pkey,
                      policy=AdmissionPolicy(max_delay=0.005))
    yield eng
    eng.close()


def rnd(shape, seed=0, dtype=np.uint8):
    rng = np.random.default_rng(seed)
    return rng.integers(0, np.iinfo(dtype).max, shape, dtype=dtype)


# -- determinism: engine == direct, per op class ---------------------------

def test_encode_bit_identical_and_padded(engine):
    codec = rs.make_codec(K, M, backend="cpu")
    for b, seed in ((1, 1), (3, 2), (5, 3)):       # odd sizes force pads
        data = rnd((b, K, 256), seed)
        assert np.array_equal(engine.encode(data), codec.encode(data))
    # 2-D submit round-trips without a batch axis
    one = rnd((K, 256), 9)
    out = engine.encode(one)
    assert out.shape == (K + M, 256)
    assert np.array_equal(out, codec.encode(one[None])[0])
    st = engine.stats_snapshot()["classes"]["encode"]
    assert st["pad_waste"] > 0          # 3- and 5-row batches padded


def test_reconstruct_and_decode_match_direct(engine):
    codec = rs.make_codec(K, M, backend="cpu")
    data = rnd((4, K, 512), 5)
    coded = codec.encode(data)
    # drop row 0: survivors are rows (1, 2)
    surv = coded[:, [1, 2]]
    rec = engine.reconstruct(surv, (1, 2), (0,))
    assert np.array_equal(rec, codec.reconstruct(surv, (1, 2), (0,)))
    assert np.array_equal(rec[:, 0], coded[:, 0])
    dec = engine.decode_data(surv, (1, 2))
    assert np.array_equal(dec, data)


def test_tag_prove_verify_bit_identical(engine, pkey):
    frags = rnd((5, FRAG), 7)
    hashes = [bytes([i]) * 32 for i in range(5)]
    ids = np.stack([podr2.fragment_id_from_hash(h) for h in hashes])
    tags = engine.tag_fragments(ids, frags)
    direct = np.asarray(podr2.tag_fragments(pkey, ids, frags))
    assert np.array_equal(tags, direct)
    blocks = tags.shape[1]
    idx, nu = podr2.gen_challenge(b"round-1", blocks)
    r = np.asarray(podr2.aggregate_coeffs(b"round-1", ids))
    mu, sigma = engine.prove_aggregate(frags, tags, idx, nu, r)
    dmu, dsigma = podr2.prove_aggregate(frags, tags, idx, nu, r)
    assert np.array_equal(mu, np.asarray(dmu))
    assert np.array_equal(sigma, np.asarray(dsigma))
    assert engine.verify_aggregate(ids, blocks, idx, nu, r, mu, sigma)
    # per-fragment checks coalesce along F and agree with the direct op
    mu_b, sigma_b = podr2.prove_batch(frags, tags, idx, nu)
    ok = engine.verify_batch(ids, blocks, idx, nu, np.asarray(mu_b),
                             np.asarray(sigma_b))
    dok = np.asarray(podr2.verify_batch(pkey, ids, blocks, idx, nu,
                                        mu_b, sigma_b))
    assert np.array_equal(ok, dok) and ok.all()


def test_verify_aggregate_coalesces_ragged_missions(pkey):
    """Missions with DIFFERENT owed-set sizes coalesce into one
    F-padded vmap batch; verdicts match the direct per-mission calls,
    including a tampered proof rejected inside the same batch."""
    eng = make_engine(K, M, podr2_key=pkey,
                      policy=AdmissionPolicy(max_delay=0.25))
    try:
        blocks = FRAG // podr2.BLOCK_BYTES
        idx, nu = podr2.gen_challenge(b"round-2", blocks)
        missions = []
        for i, f in enumerate((2, 3, 5)):        # ragged owed sets
            frags = rnd((f, FRAG), 30 + i)
            hashes = [bytes([40 + i, j]) * 16 for j in range(f)]
            ids = np.stack([podr2.fragment_id_from_hash(h)
                            for h in hashes])
            tags = np.asarray(podr2.tag_fragments(pkey, ids, frags))
            r = np.asarray(podr2.aggregate_coeffs(b"round-2", ids))
            mu, sigma = podr2.prove_aggregate(frags, tags, idx, nu, r)
            mu, sigma = np.asarray(mu), np.asarray(sigma)
            if i == 1:                           # tamper one mission
                sigma = (sigma + 1) % (2 ** 31 - 1)
            missions.append((ids, r, mu, sigma))
        # submit back-to-back (inputs prepared above, so all three
        # land in the queue within the coalescing window)
        futs = [eng.submit_verify_aggregate(ids, blocks, idx, nu, r,
                                            mu, sigma)
                for ids, r, mu, sigma in missions]
        want = [bool(np.asarray(podr2.verify_aggregate(
            pkey, ids, blocks, idx, nu, r, mu, sigma)))
            for ids, r, mu, sigma in missions]
        got = [bool(f.result(timeout=30)) for f in futs]
        assert got == want == [True, False, True]
        st = eng.stats_snapshot()["classes"]["verify"]
        assert st["batch_occupancy"] > 1        # they really coalesced
    finally:
        eng.close()


# -- zero-copy device handoff ----------------------------------------------

def test_engine_zero_copy_device_arrays(pkey):
    """jax.Array in -> jax.Array out (no forced np.asarray anywhere on
    the device submitter's path), values bit-identical to direct; host
    (numpy) submitters keep getting numpy back."""
    import jax
    import jax.numpy as jnp

    eng = make_engine(K, M, rs_backend="jax", podr2_key=pkey,
                      policy=AdmissionPolicy(max_delay=0.005))
    try:
        codec = rs.make_codec(K, M, backend="cpu")
        host = rnd((2, K, 256), 1)
        dev = jnp.asarray(host)
        out = eng.encode(dev)
        assert isinstance(out, jax.Array)
        assert np.array_equal(np.asarray(out), codec.encode(host))
        np_out = eng.encode(host)
        assert isinstance(np_out, np.ndarray)
        assert np.array_equal(np_out, np.asarray(out))
        # tag + verify classes round-trip on device too
        frags = jnp.asarray(rnd((3, FRAG), 2))
        ids = jnp.asarray(rnd((3, 2), 3, dtype=np.uint32))
        tags = eng.tag_fragments(ids, frags)
        assert isinstance(tags, jax.Array)
        direct = np.asarray(podr2.tag_fragments(pkey, ids, frags))
        assert np.array_equal(np.asarray(tags), direct)
        blocks = tags.shape[1]
        idx, nu = podr2.gen_challenge(b"round-zc", blocks)
        mu_b, sigma_b = podr2.prove_batch(frags, tags, idx, nu)
        ok = eng.verify_batch(jnp.asarray(ids), blocks, idx, nu,
                              jnp.asarray(mu_b), jnp.asarray(sigma_b))
        assert isinstance(ok, jax.Array) and np.asarray(ok).all()
    finally:
        eng.close()


def test_mixed_host_device_batch_coalesces(pkey):
    """A device submitter and a host submitter coalesce into ONE
    batch; each gets its own domain back and both match direct."""
    import jax
    import jax.numpy as jnp

    codec = rs.make_codec(K, M, backend="cpu")
    eng = make_engine(K, M, rs_backend="jax",
                      policy=AdmissionPolicy(max_delay=0.25))
    try:
        host = rnd((2, K, 128), 4)
        dev = jnp.asarray(rnd((3, K, 128), 5))
        f_host = eng.submit_encode(host)
        f_dev = eng.submit_encode(dev)
        out_host = f_host.result(timeout=30)
        out_dev = f_dev.result(timeout=30)
        assert isinstance(out_host, np.ndarray)
        assert isinstance(out_dev, jax.Array)
        assert np.array_equal(out_host, codec.encode(host))
        assert np.array_equal(np.asarray(out_dev),
                              codec.encode(np.asarray(dev)))
        st = eng.stats_snapshot()["classes"]["encode"]
        assert st["batches"] == 1 and st["batch_occupancy"] == 2
    finally:
        eng.close()


# -- linear fetch: a host batch's byte result leaves the device as 1-D
# rows and is put back together on the host (PERF.md, PR 28) ---------------

@pytest.mark.parametrize("k,m,missing,claims,squeezed", [
    (2, 1, (0,), 1, False),         # rows x r = 1 x 1: a view of the row
    (2, 1, (0,), 2, False),         # 2 x 1: two claims coalesced
    (4, 8, (0, 5), 1, False),       # 1 x 2: a two-row repair at RS(4,8)
    (2, 1, (1,), 1, True),          # the squeezed [k, n] form
])
def test_reconstruct_fetched_as_linear_rows(k, m, missing, claims, squeezed):
    """The repaired rows equal the reference codec's byte for byte, in
    the shape, dtype and C-contiguity a host caller always got, and
    every batch's result left the device as linear rows."""
    from cess_tpu.ops.rs_ref import ReferenceCodec

    ref = ReferenceCodec(k, m)
    present = tuple(i for i in range(k + m) if i not in missing)[:k]
    n = 384
    coded = ref.encode(rnd((claims, k, n), 31))
    eng = make_engine(k, m, rs_backend="jax",
                      policy=AdmissionPolicy(max_delay=0.25))
    try:
        survs = [coded[c][list(present)] if squeezed
                 else coded[c:c + 1, list(present)] for c in range(claims)]
        futs = [eng.submit_reconstruct(s, present, missing) for s in survs]
        outs = [f.result(timeout=60) for f in futs]
        for c, out in enumerate(outs):
            want = ref.reconstruct(coded[c:c + 1, list(present)], present,
                                   missing)
            assert np.array_equal(want[0], coded[c, list(missing)])
            if squeezed:
                want = want[0]
            assert isinstance(out, np.ndarray) and out.dtype == np.uint8
            assert out.shape == want.shape == \
                ((len(missing), n) if squeezed else (1, len(missing), n))
            assert out.flags.c_contiguous
            assert out.tobytes() == want.tobytes()
        st = eng.stats_snapshot()["classes"]["repair"]
        assert st["batches"] == 1 and st["batch_occupancy"] == claims
        assert st["linear_fetches"] == st["batches"]
        assert eng.stats.metrics()[
            "cess_engine_repair_linear_fetches"] == 1
    finally:
        eng.close()


@pytest.mark.parametrize("cls", ["encode", "tag"])
def test_linear_fetch_only_for_host_byte_results(cls, pkey):
    """A device submitter's slice never touches the host (jax.Array in,
    jax.Array out) and a uint32 tag batch is fetched whole, as before:
    neither counts a linear fetch nor runs the flatten."""
    import jax
    import jax.numpy as jnp

    from cess_tpu.serve import engine as engine_mod

    eng = make_engine(K, M, rs_backend="jax", podr2_key=pkey,
                      policy=AdmissionPolicy(max_delay=0.005))
    try:
        flattens = engine_mod._linear_rows._cache_size()
        if cls == "encode":
            host = rnd((2, K, 256), 41)
            out = eng.encode(jnp.asarray(host))
            assert isinstance(out, jax.Array)
            assert np.array_equal(
                np.asarray(out),
                rs.make_codec(K, M, backend="cpu").encode(host))
        else:
            frags = rnd((3, FRAG), 42)
            ids = rnd((3, 2), 43, dtype=np.uint32)
            out = eng.tag_fragments(ids, frags)
            assert isinstance(out, np.ndarray) and out.dtype == np.uint32
            assert np.array_equal(
                out, np.asarray(podr2.tag_fragments(pkey, ids, frags)))
        st = eng.stats_snapshot()["classes"][cls]
        assert st["batches"] == 1 and st["linear_fetches"] == 0
        assert engine_mod._linear_rows._cache_size() == flattens
    finally:
        eng.close()


def test_warm_repair_warms_the_linear_fetch():
    """After warm_repair a first claim (alone, or two coalesced: the
    default buckets) builds no program: neither an engine cache entry
    nor a compile of the flatten."""
    from cess_tpu.serve import engine as engine_mod

    eng = make_engine(K, M, rs_backend="jax",
                      policy=AdmissionPolicy(max_delay=0.25))
    try:
        n = 640                         # a width no other test flattens
        eng.warm_repair([((1, 2), (0,))], n)
        built = eng.stats_snapshot()["programs_built"]
        flattens = engine_mod._linear_rows._cache_size()
        assert {("linear_rows", 1, 1, n), ("linear_rows", 2, 1, n)} \
            <= set(eng.programs._programs)
        coded = rs.make_codec(K, M, backend="cpu").encode(
            rnd((2, K, n), 51))
        rec = eng.reconstruct(coded[:1, [1, 2]], (1, 2), (0,))
        assert np.array_equal(rec[:, 0], coded[:1, 0])
        futs = [eng.submit_reconstruct(coded[c:c + 1, [1, 2]], (1, 2),
                                       (0,)) for c in range(2)]
        for c, f in enumerate(futs):
            assert np.array_equal(f.result(timeout=60)[0, 0], coded[c, 0])
        st = eng.stats_snapshot()
        assert st["classes"]["repair"]["linear_fetches"] == \
            st["classes"]["repair"]["batches"] == 2
        assert st["programs_built"] == built
        assert engine_mod._linear_rows._cache_size() == flattens
    finally:
        eng.close()


# -- linear put: a host batch's survivors go up as linear rows, put from
# the callers' own memory and stacked on the device (PERF.md, PR 32) -------

def _repair_kind(kind, k, m, n, seed):
    """One request of ``kind`` at RS(k, m): (engine method name,
    ``[1, q, n]`` host payload, the call's other arguments, the bytes
    the reference gives for it)."""
    from cess_tpu.ops import regen
    from cess_tpu.ops.rs_ref import ReferenceCodec

    if kind == "repair_symbol":
        pairs = rnd((1, 2, n), seed)
        return pairs, (29,), regen.fold_symbol_pairs(pairs, 29)
    ref = ReferenceCodec(k, m)
    coded = ref.encode(rnd((1, k, n), seed))
    present = tuple(range(1, k + 1))            # row 0 lost
    surv = np.ascontiguousarray(coded[:, list(present)])
    if kind == "decode_data":
        return surv, (present,), ref.decode_data(surv, present)
    return surv, (present, (0,)), ref.reconstruct(surv, present, (0,))


@contextlib.contextmanager
def _no_host_copy_on_the_way_up(eng, monkeypatch, n):
    """np.concatenate / np.stack of a row's ``n`` bytes or more raise
    while ``eng._op_repair`` runs (a pattern's matrix, a few hundred
    bytes, is built with them), up to where it hands its result to
    _split_rows (the way down puts a multi-row result back together
    with np.stack, PR 28)."""
    guard = threading.local()

    def forbidding(name, real):
        def copy(arrays, *args, **kwargs):
            if getattr(guard, "on", False) \
                    and sum(np.asarray(a).nbytes for a in arrays) >= n:
                raise AssertionError(f"np.{name} on a repair's way up")
            return real(arrays, *args, **kwargs)
        return copy

    for name in ("concatenate", "stack"):
        monkeypatch.setattr(np, name, forbidding(name, getattr(np, name)))
    op, split = eng._op_repair, eng._split_rows

    def op_repair(*args, **kwargs):
        guard.on = True
        try:
            return op(*args, **kwargs)
        finally:
            guard.on = False

    def split_rows(*args, **kwargs):
        guard.on = False
        return split(*args, **kwargs)

    monkeypatch.setattr(eng, "_op_repair", op_repair)
    monkeypatch.setattr(eng, "_split_rows", split_rows)
    yield
    monkeypatch.undo()


@pytest.mark.parametrize("claims", [1, 2, 3])
@pytest.mark.parametrize("k,m", [(2, 1), (10, 4)])
@pytest.mark.parametrize("kind", ["reconstruct", "decode_data",
                                  "repair_symbol"])
def test_host_repairs_go_up_as_linear_rows(kind, k, m, claims, monkeypatch):
    """Every kind of the repair class, alone and coalesced (three
    claims pad to a bucket of four): bytes equal to the reference's,
    ``linear_puts == batches``, and no host copy of the survivors
    between the submit and the device."""
    n = 320
    eng = make_engine(k, m, rs_backend="regen",
                      policy=AdmissionPolicy(max_delay=0.25))
    try:
        cases = [_repair_kind(kind, k, m, n, 80 + c) for c in range(claims)]
        with _no_host_copy_on_the_way_up(eng, monkeypatch, n):
            futs = [getattr(eng, "submit_" + kind)(payload, *args)
                    for payload, args, _ in cases]
            outs = [f.result(timeout=60) for f in futs]
        for out, (_, _, want) in zip(outs, cases):
            assert isinstance(out, np.ndarray) and out.dtype == np.uint8
            assert out.shape == want.shape
            assert out.tobytes() == np.asarray(want).tobytes()
        st = eng.stats_snapshot()["classes"]["repair"]
        assert st["batches"] == 1 and st["batch_occupancy"] == claims
        assert (st["pad_waste"] > 0) == (claims == 3)
        assert st["linear_puts"] == st["batches"] == st["linear_fetches"]
        assert eng.stats_metrics()["cess_engine_repair_linear_puts"] == 1
    finally:
        eng.close()


def test_warmed_bucket_pads_with_device_zeros():
    """Three claims in a warmed bucket of four: the fourth request's
    rows are zeros made on the device, and the rows program is the one
    the warm-up compiled (one a shape, whatever the number of claims).
    Since PR 38 a warmed bucket is warmed for every count of claims
    that pads to it, so the way down builds nothing either: the slice
    off the pad and the flatten of three rows were loaded with the
    bucket of four. A host engine's warm_repair has nothing to run."""
    from cess_tpu.ops.rs_ref import ReferenceCodec

    k, m, n = 2, 1, 704                 # a width no other test compiles
    eng = make_engine(k, m, rs_backend="jax",
                      policy=AdmissionPolicy(max_delay=0.25))
    try:
        eng.warm_repair([((1, 2), (0,))], n, buckets=(4,))
        stackers = rs._apply_rows._cache_size()
        built = eng.stats_snapshot()["programs_built"]
        ref = ReferenceCodec(k, m)
        coded = ref.encode(rnd((3, k, n), 61))
        futs = [eng.submit_reconstruct(coded[c, [0, 2]], (0, 2), (1,))
                for c in range(3)]          # a pattern never named
        for c, f in enumerate(futs):
            assert np.array_equal(f.result(timeout=60)[0], coded[c, 1])
        st = eng.stats_snapshot()
        assert st["classes"]["repair"]["batches"] == 1
        assert st["classes"]["repair"]["pad_waste"] == 0.25
        assert st["classes"]["repair"]["linear_puts"] == 1
        assert rs._apply_rows._cache_size() == stackers
        # the flatten of the three rows left after the slice was
        # warmed with the bucket
        assert st["programs_built"] == built
    finally:
        eng.close()
    host = make_engine(k, m, rs_backend="cpu",
                       policy=AdmissionPolicy(max_delay=0.001))
    try:
        host.warm_repair([((1, 2), (0,))], n)
        rec = host.reconstruct(list(coded[0, [1, 2]]), (1, 2), (0,))
        assert np.array_equal(rec[0], coded[0, 0])
        assert host.stats_snapshot()["classes"]["repair"][
            "linear_puts"] == 0
    finally:
        host.close()


@pytest.mark.parametrize("kind", ["reconstruct", "decode_data"])
def test_request_given_as_rows_equals_the_stacked_request(kind, monkeypatch):
    """One request as a list of its q 1-D rows: the answer ``[q, n]``
    would get, with the rows never stacked on the host; a read-only
    view of ``bytes`` (a miner's store) is taken as it is."""
    k, m, n = 10, 4, 192
    surv, args, want = _repair_kind(kind, k, m, n, 91)
    rows = [np.frombuffer(surv[0, j].tobytes(), np.uint8) for j in range(k)]
    eng = make_engine(k, m, rs_backend="jax",
                      policy=AdmissionPolicy(max_delay=0.001))
    try:
        stacked = getattr(eng, kind)(surv[0], *args)
        with _no_host_copy_on_the_way_up(eng, monkeypatch, n):
            for seq in (rows, tuple(rows)):
                got = getattr(eng, kind)(seq, *args)
                assert isinstance(got, np.ndarray)
                assert got.shape == stacked.shape == want.shape[1:]
                assert got.tobytes() == stacked.tobytes() \
                    == np.asarray(want)[0].tobytes()
        st = eng.stats_snapshot()["classes"]["repair"]
        assert st["linear_puts"] == st["batches"] == 3
        for bad in (rows[:-1], rows[:-1] + [rows[-1][:-1]]):
            with pytest.raises(ValueError, match="rows"):
                getattr(eng, kind)(bad, *args)
    finally:
        eng.close()


def test_device_resident_repair_keeps_its_path():
    """A device submitter's survivors are already where they must be:
    jax.Array out, no linear put; coalesced with a host claim the batch
    takes the same on-device path and both get their own bytes."""
    import jax
    import jax.numpy as jnp

    k, m, n = 2, 1, 448
    eng = make_engine(k, m, rs_backend="jax",
                      policy=AdmissionPolicy(max_delay=0.25))
    try:
        (s0, args, w0), (s1, _, w1) = (
            _repair_kind("reconstruct", k, m, n, 95 + c) for c in range(2))
        out = eng.reconstruct(jnp.asarray(s0), *args)
        assert isinstance(out, jax.Array)
        assert np.asarray(out).tobytes() == w0.tobytes()
        st = eng.stats_snapshot()["classes"]["repair"]
        assert st["batches"] == 1 and st["linear_puts"] == 0
        f_dev = eng.submit_reconstruct(jnp.asarray(s0), *args)
        f_host = eng.submit_reconstruct(list(s1[0]), *args)   # as rows
        assert isinstance(f_dev.result(timeout=60), jax.Array)
        assert np.asarray(f_dev.result()).tobytes() == w0.tobytes()
        got = f_host.result(timeout=60)
        assert isinstance(got, np.ndarray)
        assert got.tobytes() == w1.tobytes()
        st = eng.stats_snapshot()["classes"]["repair"]
        assert st["batches"] == 2 and st["batch_occupancy"] == 1.5
        assert st["linear_puts"] == 0
    finally:
        eng.close()


@pytest.mark.parametrize("as_rows", [False, True])
def test_degraded_repair_keeps_a_host_array(as_rows):
    """Every device dispatch fails: the batch is served by the CPU
    reference codec from a host array (built with one np.stack when
    the request came as rows): the same bytes, no linear put."""
    from cess_tpu.resilience import ResilienceConfig, faults
    from cess_tpu.resilience.faults import FaultPlan

    k, m, n = 10, 4, 192
    res = ResilienceConfig()
    eng = make_engine(k, m, rs_backend="jax", resilience=res,
                      policy=AdmissionPolicy(max_delay=0.001))
    plan = FaultPlan.seeded(b"rows", {"engine.dispatch": (1.0, "raise")},
                            horizon=64)
    try:
        surv, args, want = _repair_kind("reconstruct", k, m, n, 97)
        with faults.armed(plan):
            got = eng.reconstruct(list(surv[0]) if as_rows else surv,
                                  *args, timeout=60)
        assert plan.fired_log()
        assert got.tobytes() == want.tobytes()
        assert got.shape == (want.shape[1:] if as_rows else want.shape)
        snap = res.stats.snapshot()
        assert snap["fallback_batches"].get("repair", 0) == 1
        st = eng.stats_snapshot()["classes"]["repair"]
        assert st["completed"] == 1 and st["linear_puts"] == 0
    finally:
        eng.close()


def test_pipeline_engine_path_returns_device_arrays(pkey):
    """StoragePipeline -> engine -> device is one handoff: the engine
    path hands back jax.Array results identical to the direct path."""
    import jax
    import jax.numpy as jnp

    from cess_tpu.models.pipeline import PipelineConfig, StoragePipeline

    cfg = PipelineConfig(k=K, m=M, segment_size=K * FRAG)
    eng = make_engine(K, M, rs_backend="jax", podr2_key=pkey,
                      policy=AdmissionPolicy(max_delay=0.005))
    try:
        piped = StoragePipeline(cfg, podr2_key=pkey, engine=eng)
        direct = StoragePipeline(cfg, podr2_key=pkey)
        # host segments, shared by both pipelines
        segs = rnd((2, K * FRAG), 6)
        ids = jnp.asarray(rnd((2, K + M, 2), 7, dtype=np.uint32))
        out = piped.forward(segs, ids)
        assert isinstance(out["fragments"], jax.Array)
        assert isinstance(out["tags"], jax.Array)
        ref = direct.forward(segs, ids)
        assert np.array_equal(np.asarray(out["fragments"]),
                              np.asarray(ref["fragments"]))
        assert np.array_equal(np.asarray(out["tags"]),
                              np.asarray(ref["tags"]))
    finally:
        eng.close()


# -- pipeline + offchain wiring --------------------------------------------

def test_pipeline_engine_matches_direct(pkey):
    from cess_tpu.models.pipeline import PipelineConfig, StoragePipeline

    cfg = PipelineConfig(k=K, m=M, segment_size=K * FRAG)
    direct = StoragePipeline(cfg, podr2_key=pkey)
    eng = make_engine(K, M, podr2_key=pkey,
                      policy=AdmissionPolicy(max_delay=0.005))
    try:
        piped = StoragePipeline(cfg, podr2_key=pkey, engine=eng)
        segs = rnd((3, K * FRAG), 11)
        a = np.asarray(direct.encode_step(segs))
        b = np.asarray(piped.encode_step(segs))
        assert np.array_equal(a, b)
        ids = rnd((3, K + M, 2), 12, dtype=np.uint32)
        ta = np.asarray(direct.tag_step(a, ids))
        tb = np.asarray(piped.tag_step(b, ids))
        assert np.array_equal(ta, tb)
    finally:
        eng.close()
    # a mismatched audit key is refused loudly (silent tag divergence)
    other = podr2.Podr2Key.generate(99)
    eng2 = make_engine(K, M, podr2_key=other,
                       policy=AdmissionPolicy(max_delay=0.005))
    try:
        with pytest.raises(ValueError, match="key"):
            StoragePipeline(cfg, podr2_key=pkey, engine=eng2)
    finally:
        eng2.close()


def test_build_proof_engine_path_identical(engine, pkey):
    from cess_tpu.node.offchain import build_proof

    frags = rnd((4, FRAG), 17)
    hashes = [bytes([60 + i]) * 32 for i in range(4)]
    ids = np.stack([podr2.fragment_id_from_hash(h) for h in hashes])
    tags = np.asarray(podr2.tag_fragments(pkey, ids, frags))
    store = {h: frags[i].tobytes() for i, h in enumerate(hashes)}
    tagmap = {h: tags[i] for i, h in enumerate(hashes)}
    direct = build_proof(b"round-3", hashes, store, tagmap,
                         limbs=pkey.limbs)
    via_engine = build_proof(b"round-3", hashes, store, tagmap,
                             limbs=pkey.limbs, engine=engine)
    assert direct == via_engine       # identical wire bytes


def test_tee_agent_verify_engine_path(engine, pkey):
    """TeeAgent._verify routes through the engine's verify class when
    one is configured, with verdicts identical to the direct path —
    including malformed-blob rejection (never an exception)."""
    from cess_tpu.node.chain_spec import dev_spec
    from cess_tpu.node.network import Node
    from cess_tpu.node.offchain import TeeAgent, build_proof

    node = Node(dev_spec(), "tee-host", {})
    blocks = FRAG // podr2.BLOCK_BYTES
    direct_tee = TeeAgent(node, "alice", pkey, blocks)
    engine_tee = TeeAgent(node, "alice", pkey, blocks, engine=engine)
    frags = rnd((3, FRAG), 55)
    hashes = [bytes([70 + i]) * 32 for i in range(3)]
    ids = np.stack([podr2.fragment_id_from_hash(h) for h in hashes])
    tags = np.asarray(podr2.tag_fragments(pkey, ids, frags))
    store = {h: frags[i].tobytes() for i, h in enumerate(hashes)}
    tagmap = {h: tags[i] for i, h in enumerate(hashes)}
    seed = b"round-5"
    blob = build_proof(seed, hashes, store, tagmap, limbs=pkey.limbs)
    idx, nu = podr2.gen_challenge(seed, blocks)
    for owed in (hashes, hashes[:2]):       # honest + wrong owed set
        assert engine_tee._verify(blob, owed, seed, idx, nu) \
            == direct_tee._verify(blob, owed, seed, idx, nu)
    assert engine_tee._verify(blob, hashes, seed, idx, nu) is True
    assert engine_tee._verify(b"garbage", hashes, seed, idx, nu) is False
    # a mismatched engine audit key is refused at construction
    other = make_engine(K, M, podr2_key=podr2.Podr2Key.generate(98),
                        policy=AdmissionPolicy(max_delay=0.005))
    try:
        with pytest.raises(ValueError, match="key"):
            TeeAgent(node, "alice", pkey, blocks, engine=other)
    finally:
        other.close()


# -- contention: coalescing + priority --------------------------------------

def test_concurrent_submitters_coalesce(pkey):
    """>= 8 concurrent submitters (the acceptance-criteria contention
    shape): their requests coalesce into shared device batches (mean
    occupancy > 1) and every result is bit-identical to direct."""
    codec = rs.make_codec(K, M, backend="cpu")
    eng = make_engine(K, M, podr2_key=pkey,
                      policy=AdmissionPolicy(max_delay=0.3))
    n_threads = 8
    datas = [rnd((2, K, 256), 100 + i) for i in range(n_threads)]
    outs: list = [None] * n_threads
    barrier = threading.Barrier(n_threads)

    def submit(i):
        barrier.wait()
        outs[i] = eng.encode(datas[i], timeout=30)

    try:
        threads = [threading.Thread(target=submit, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        for i in range(n_threads):
            assert np.array_equal(outs[i], codec.encode(datas[i])), i
        st = eng.stats_snapshot()["classes"]["encode"]
        assert st["submitted"] == st["completed"] == n_threads
        assert st["batch_occupancy"] > 1, st
    finally:
        eng.close()


def test_verify_preempts_queued_encode(pkey):
    """Per-class priority: once a drain triggers, the verify class
    goes to the device before bulk encode that queued EARLIER —
    challenge verification preempts upload work (policy.py)."""
    import time

    eng = make_engine(K, M, podr2_key=pkey,
                      policy=AdmissionPolicy(max_delay=0.4))
    order: list[str] = []
    real_encode, real_verify = eng._op_encode, eng._op_verify_batch
    eng._op_encode = lambda b, d=False: (order.append("encode"),
                                         real_encode(b, d))[1]
    eng._op_verify_batch = lambda b, d=False: (order.append("verify"),
                                               real_verify(b, d))[1]
    try:
        f_enc = eng.submit_encode(rnd((1, K, 256), 1))
        time.sleep(0.05)          # verify arrives LATER...
        blocks = FRAG // podr2.BLOCK_BYTES
        idx, nu = podr2.gen_challenge(b"round-4", blocks)
        f_ver = eng.submit_verify_batch(
            np.zeros((1, 2), np.uint32), blocks, idx, nu,
            np.zeros((1, podr2.SECTORS), np.uint32),
            np.zeros((1, podr2.LIMBS), np.uint32))
        f_ver.result(timeout=30)
        f_enc.result(timeout=30)
        assert order == ["verify", "encode"]     # ...but runs FIRST
    finally:
        eng.close()


# -- backpressure / timeout / shutdown contracts ----------------------------

def test_saturation_is_explicit(pkey):
    eng = make_engine(K, M, policy=AdmissionPolicy(
        queue_cap=2, max_delay=30.0))
    try:
        data = rnd((1, K, 64), 3)
        eng.submit_encode(data)
        eng.submit_encode(data)
        with pytest.raises(EngineSaturated):
            eng.submit_encode(data)
        st = eng.stats_snapshot()["classes"]["encode"]
        assert st["saturated"] == 1 and st["queue_depth"] == 2
    finally:
        eng.close()


def test_deadline_expiry_cancels(pkey):
    eng = make_engine(K, M, policy=AdmissionPolicy(max_delay=30.0))
    try:
        fut = eng.submit_encode(rnd((1, K, 64), 4), timeout=0.05)
        with pytest.raises(EngineTimeout):
            fut.result(timeout=10)
        st = eng.stats_snapshot()["classes"]["encode"]
        assert st["timeouts"] == 1 and st["completed"] == 0
    finally:
        eng.close()


def test_deadline_expiry_crosses_classes(pkey):
    """An expired request in a LOW-priority class cancels promptly
    even while a higher-priority class holds queued (untriggered)
    work — expiry is a queue sweep, not a drain side-effect."""
    eng = make_engine(K, M, podr2_key=pkey,
                      policy=AdmissionPolicy(max_delay=30.0))
    try:
        blocks = FRAG // podr2.BLOCK_BYTES
        idx, nu = podr2.gen_challenge(b"round-6", blocks)
        f_ver = eng.submit_verify_batch(        # higher class, queued
            np.zeros((1, 2), np.uint32), blocks, idx, nu,
            np.zeros((1, podr2.SECTORS), np.uint32),
            np.zeros((1, podr2.LIMBS), np.uint32))
        f_enc = eng.submit_encode(rnd((1, K, 64), 7), timeout=0.05)
        with pytest.raises(EngineTimeout):
            f_enc.result(timeout=10)
        st = eng.stats_snapshot()["classes"]
        assert st["encode"]["timeouts"] == 1
        # the verify request was NOT force-drained by the dead encode
        # (no spurious occupancy-1 batches); it completes on close
        eng.close()
        assert f_ver.result(timeout=10).shape == (1,)
    finally:
        eng.close()


def test_stacked_ops_cap_pad_spread(pkey):
    """One huge prove request must not drag tiny same-round peers
    into its row bucket: requests whose buckets differ more than
    PAD_SPREAD split into separate batches."""
    eng = make_engine(K, M, podr2_key=pkey,
                      policy=AdmissionPolicy(max_delay=0.25,
                                             max_batch_rows=512))
    try:
        blocks = FRAG // podr2.BLOCK_BYTES
        idx, nu = podr2.gen_challenge(b"round-7", blocks)
        sets = []
        for i, f in enumerate((64, 1, 1)):       # 64-row + two tiny
            frags = rnd((f, FRAG), 80 + i)
            ids = np.stack([podr2.fragment_id_from_hash(
                bytes([90 + i, j % 256]) * 16) for j in range(f)])
            tags = np.asarray(podr2.tag_fragments(pkey, ids, frags))
            r = np.asarray(podr2.aggregate_coeffs(b"round-7", ids))
            sets.append((frags, tags, r))
        futs = [eng.submit_prove_aggregate(f, t, idx, nu, r)
                for f, t, r in sets]
        for (f, t, r), fut in zip(sets, futs):
            mu, sigma = fut.result(timeout=60)
            dmu, dsigma = podr2.prove_aggregate(f, t, idx, nu, r)
            assert np.array_equal(mu, np.asarray(dmu))
            assert np.array_equal(sigma, np.asarray(dsigma))
        st = eng.stats_snapshot()["classes"]["prove"]
        assert st["batches"] == 2        # big solo, two tiny together
    finally:
        eng.close()


def test_closed_engine_refuses(pkey):
    eng = make_engine(K, M)
    eng.close()
    with pytest.raises(EngineClosed):
        eng.submit_encode(rnd((1, K, 64), 5))


def test_close_drains_pending(pkey):
    """close() is graceful: already-queued work completes."""
    codec = rs.make_codec(K, M, backend="cpu")
    eng = make_engine(K, M, policy=AdmissionPolicy(max_delay=30.0))
    data = rnd((2, K, 64), 6)
    fut = eng.submit_encode(data)
    eng.close()
    assert np.array_equal(fut.result(timeout=10), codec.encode(data))


def test_flush_waits_for_quiescence(pkey):
    """flush() returns only once every queued request has resolved
    (including in-flight batches), and respects its own timeout."""
    import time

    codec = rs.make_codec(K, M, backend="cpu")
    eng = make_engine(K, M, policy=AdmissionPolicy(max_delay=10.0))
    real = eng._op_encode
    eng._op_encode = lambda b, d=False: (time.sleep(0.3), real(b, d))[1]
    try:
        datas = [rnd((1, K, 64), s) for s in (1, 2)]
        futs = [eng.submit_encode(d) for d in datas]
        assert eng.flush(timeout=0.01) is False     # still working
        assert eng.flush(timeout=30) is True
        for f, d in zip(futs, datas):
            assert f.done()
            assert np.array_equal(f.result(), codec.encode(d))
    finally:
        eng.close()


def test_close_timeout_rejects_still_queued(pkey):
    """A close() whose drain outlives its timeout rejects every
    still-queued future with EngineClosed — no caller hangs forever
    on a future that will never fire."""
    import time

    eng = make_engine(K, M, policy=AdmissionPolicy(max_delay=30.0))
    real = eng._op_encode
    eng._op_encode = lambda b, d=False: (time.sleep(1.5), real(b, d))[1]
    # different shapes -> two batches: the first goes in flight (and
    # sleeps), the second is still queued when close() gives up
    f1 = eng.submit_encode(rnd((1, K, 64), 1))
    f2 = eng.submit_encode(rnd((1, K, 128), 2))
    time.sleep(0.3)                     # let batch 1 enter the runner
    eng.close(timeout=0.1)
    with pytest.raises(EngineClosed):
        f2.result(timeout=10)
    # the in-flight batch still resolves (process is alive)
    assert f1.result(timeout=10).shape == (1, K + M, 64)


def test_miner_agent_rejects_mismatched_engine_geometry(pkey):
    from cess_tpu.models.pipeline import PipelineConfig, StoragePipeline
    from cess_tpu.node.chain_spec import dev_spec
    from cess_tpu.node.network import Node
    from cess_tpu.node.offchain import MinerAgent

    node = Node(dev_spec(), "mm", {})
    pipe = StoragePipeline(PipelineConfig(k=K, m=M,
                                          segment_size=K * FRAG),
                           podr2_key=pkey)
    other = make_engine(4, 8, policy=AdmissionPolicy(max_delay=0.005))
    try:
        with pytest.raises(ValueError, match="RS"):
            MinerAgent(node, "m1", [], pipe, engine=other)
    finally:
        other.close()


def test_program_cache_lru_bounded():
    from cess_tpu.serve.buckets import ProgramCache

    cache = ProgramCache(capacity=3)
    for i in range(5):
        cache.get(("op", i), lambda i=i: (lambda: i))
    assert len(cache) == 3               # oldest two evicted
    # hot keys survive: touch ("op", 2) then insert -> 3 goes, 2 stays
    cache.get(("op", 2), lambda: (lambda: None))
    cache.get(("op", 9), lambda: (lambda: None))
    assert len(cache) == 3
    built = []
    cache.get(("op", 2), lambda: built.append(1))
    assert not built                     # still cached


# -- buckets + program cache -------------------------------------------------

def test_codec_program_keys_name_shapes_only():
    """One program model: the codec's classes key their programs by
    (op, row counts, width, bucket) and nothing else. After an encode,
    a warm_repair and claims of all three repair kinds the cache holds
    flat tuples of str / int, one a shape: no pattern, no coefficient,
    nothing a lowering added."""
    k, m, n = 2, 1, 448                 # a width no other test compiles
    eng = make_engine(k, m, rs_backend="regen",
                      policy=AdmissionPolicy(max_delay=0.002))
    try:
        eng.encode(rnd((1, k, n), 90), timeout=60)
        eng.warm_repair([((1, 2), (0,)), ((0, 2), (1,))], n, buckets=(1,))
        warmed = set(eng.programs._programs)
        for kind in ("reconstruct", "decode_data", "repair_symbol"):
            payload, args, want = _repair_kind(kind, k, m, n, 91)
            out = getattr(eng, kind)(payload, *args, timeout=60)
            assert out.tobytes() == np.asarray(want).tobytes()
        keys = set(eng.programs._programs)
        # the claims built only what a decode needs: warm_repair does
        # not name that kind
        assert keys - warmed == {("decode", k, n, 1),
                                 ("linear_rows", 1, k, n)}
        assert keys == {("encode", k, n, 1), ("repair", k, 1, n, 1),
                        ("symbol", n, 1), ("decode", k, n, 1),
                        ("linear_rows", 1, 1, n), ("linear_rows", 1, k, n),
                        ("linear_rows", 1, k + m, n)}
        assert all(type(part) in (str, int) for key in keys for part in key)
    finally:
        eng.close()


def test_bucket_padding_and_program_reuse(pkey):
    from cess_tpu.serve.buckets import bucket_rows

    assert [bucket_rows(n) for n in (1, 2, 3, 5, 9)] == [1, 2, 4, 8, 16]
    # even a request past the row budget stays on the power-of-two
    # grid (bounded program count beats exact-size one-off compiles)
    assert bucket_rows(600) == 1024
    eng = make_engine(K, M, policy=AdmissionPolicy(max_delay=0.005))
    try:
        codec = rs.make_codec(K, M, backend="cpu")
        for seed in (1, 2, 3):
            data = rnd((3, K, 128), seed)   # same bucket every time
            assert np.array_equal(eng.encode(data), codec.encode(data))
        snap = eng.stats_snapshot()
        assert snap["programs_built"] == 1
        assert snap["programs_reused"] == 2
    finally:
        eng.close()


def test_mixed_shapes_do_not_cross_coalesce(pkey):
    """Requests with different geometry keys never share a batch but
    all complete correctly (the drain splits by key)."""
    codec = rs.make_codec(K, M, backend="cpu")
    eng = make_engine(K, M, policy=AdmissionPolicy(max_delay=0.2))
    try:
        a, b = rnd((2, K, 128), 1), rnd((2, K, 256), 2)
        fa, fb = eng.submit_encode(a), eng.submit_encode(b)
        assert np.array_equal(fa.result(timeout=30), codec.encode(a))
        assert np.array_equal(fb.result(timeout=30), codec.encode(b))
        assert eng.stats_snapshot()["classes"]["encode"]["batches"] == 2
    finally:
        eng.close()


# -- the drain trigger (PR 39): an idle engine does not make its caller wait -

OP_OF = {"encode": "encode", "repair": "repair", "tag": "tag",
         "prove": "prove", "verify": "verify_agg"}


@pytest.fixture(scope="module")
def round_(pkey):
    """One request's worth of input for every class (same key every
    call, so requests of one class coalesce)."""
    frags = rnd((2, FRAG), 61)
    ids = np.stack([podr2.fragment_id_from_hash(bytes([60 + i]) * 32)
                    for i in range(2)])
    tags = np.asarray(podr2.tag_fragments(pkey, ids, frags))
    blocks = tags.shape[1]
    idx, nu = (np.asarray(a)
               for a in podr2.gen_challenge(b"round-39", blocks))
    r = np.asarray(podr2.aggregate_coeffs(b"round-39", ids))
    mu, sigma = (np.asarray(a) for a in
                 podr2.prove_aggregate(frags, tags, idx, nu, r))
    coded = rs.make_codec(K, M, backend="cpu").encode(rnd((1, K, 256), 62))
    return {
        "encode": lambda e: e.submit_encode(coded[:, :K]),
        "repair": lambda e: e.submit_reconstruct(coded[:, 1:], (1, 2), (0,)),
        "tag": lambda e: e.submit_tag(ids, frags),
        "prove": lambda e: e.submit_prove_aggregate(frags, tags, idx, nu, r),
        "verify": lambda e: e.submit_verify_aggregate(
            ids, blocks, idx, nu, r, mu, sigma),
    }


@pytest.mark.parametrize("cls", sorted(OP_OF))
def test_lone_request_on_an_idle_engine_drains_at_once(pkey, round_, cls,
                                                       queue_accounts):
    """The default policy has no window: a lone caller's request trips
    at its own enqueue (``idle``), waits on no policy at all, and the
    batcher sets no timer for it."""
    eng = make_engine(K, M, podr2_key=pkey)
    try:
        assert eng.policy.max_delay is None
        for _ in range(3):
            round_[cls](eng).result(timeout=60)
        snap = queue_accounts(eng, cls)
        metrics = eng.stats_metrics()
    finally:
        eng.close()
    assert snap["completed"] == snap["batches"] == 3
    assert snap["queue"]["coalesce"]["s"] == 0.0
    assert snap["queue"]["wake"]["s"] > 0.0
    assert snap["drains"] == {"idle": 3, "window": 0, "size": 0,
                              "forced": 0}
    for trigger, n in snap["drains"].items():
        assert metrics[f"cess_engine_{cls}_drains_{trigger}_total"] == n
    assert f"cess_engine_{cls}_drains" not in metrics


@pytest.mark.parametrize("cls", sorted(OP_OF))
def test_requests_behind_a_running_batch_leave_together(
        pkey, round_, cls, gate, queue_accounts):
    """Companions are gathered while the executor is busy, not by a
    timer: what arrives while a batch runs leaves as ONE batch the
    moment the batcher is free, and none of it waited on policy."""
    eng = make_engine(K, M, podr2_key=pkey)
    held = gate(eng, OP_OF[cls])
    try:
        first = round_[cls](eng)
        assert held.running()               # the batcher is busy now
        rest = [round_[cls](eng) for _ in range(3)]
        assert eng.stats_snapshot()["classes"][cls]["queue_depth"] == 3
        held.open()
        want = first.result(timeout=60)
        for f in rest:
            got = f.result(timeout=60)
            assert all(np.array_equal(a, b) for a, b in zip(
                got if isinstance(got, tuple) else (got,),
                want if isinstance(want, tuple) else (want,)))
        snap = queue_accounts(eng, cls)
    finally:
        held.open()
        eng.close()
    assert snap["completed"] == 4 and snap["batches"] == 2
    assert snap["batch_occupancy"] == 2.0       # 1, then 3 together
    assert snap["queue"]["coalesce"]["s"] == 0.0
    assert snap["drains"]["idle"] == 2 and sum(snap["drains"].values()) == 2


def test_gathered_requests_split_by_key_and_budget(gate, queue_accounts):
    """What gathered behind a busy batcher still coalesces by key and
    within the budgets: three of one geometry under a budget of two
    leave as 2 + 1, the other geometry on its own."""
    codec = rs.make_codec(K, M, backend="cpu")
    eng = make_engine(K, M, policy=AdmissionPolicy(max_batch_requests=2))
    held = gate(eng, "encode")
    try:
        a, b = rnd((1, K, 128), 1), rnd((1, K, 256), 2)
        first = eng.submit_encode(a)
        assert held.running()
        futs = [eng.submit_encode(x) for x in (a, b, a, a)]
        held.open()
        for x, f in zip((a, a, b, a, a), [first] + futs):
            assert np.array_equal(f.result(timeout=60), codec.encode(x))
        snap = queue_accounts(eng, "encode")
    finally:
        held.open()
        eng.close()
    # a | a a | b | a
    assert (snap["completed"], snap["batches"]) == (5, 4)
    assert snap["queue"]["coalesce"]["s"] == 0.0
    assert sum(snap["drains"].values()) == 4 and snap["drains"]["window"] == 0


@pytest.mark.parametrize("max_delay,trigger", [(30.0, "forced"),
                                               (0.01, "window")])
def test_a_numeric_window_holds_a_request_on_an_idle_engine(
        max_delay, trigger, gate, queue_accounts):
    """A float keeps the behaviour it had: the oldest request waits up
    to it whatever the device does — on an idle engine too — and a
    flush cuts it short."""
    eng = make_engine(K, M, policy=AdmissionPolicy(max_delay=max_delay))
    held = gate(eng, "encode")
    held.open()
    try:
        fut = eng.submit_encode(rnd((1, K, 64), 3))
        if trigger == "forced":
            # held: no batch reaches the runner, the request stays queued
            assert not held.entered.acquire(timeout=0.05)
            snap = eng.stats_snapshot()["classes"]["encode"]
            assert snap["queue_depth"] == 1
            assert not any(snap["drains"].values())
            assert eng.flush(60)
        fut.result(timeout=60)
        snap = queue_accounts(eng, "encode")
    finally:
        eng.close()
    assert snap["drains"] == {**dict.fromkeys(snap["drains"], 0),
                              trigger: 1}
    if trigger == "window":
        assert snap["queue"]["coalesce"]["s"] \
            == pytest.approx(max_delay, rel=1e-6)
    else:
        assert 0.0 < snap["queue"]["coalesce"]["s"] < max_delay


def test_a_higher_class_that_arrives_while_a_lower_one_gathers_goes_first(
        pkey, round_, gate):
    """Priority across classes is untouched: with the batcher busy, an
    encode gathers first and a verify arrives later, and the verify
    goes to the device first."""
    eng = make_engine(K, M, podr2_key=pkey)
    held = gate(eng, "encode")
    order: list[str] = []
    real_verify = eng._op_verify_agg
    eng._op_verify_agg = lambda *a: (order.append("verify"),
                                     real_verify(*a))[1]
    try:
        first = round_["encode"](eng)
        assert held.running()
        f_enc = round_["encode"](eng)       # gathers behind the batch
        f_ver = round_["verify"](eng)       # ...and arrives LATER
        real_encode, eng._op_encode = eng._op_encode, \
            lambda *a: (order.append("encode"), real_encode(*a))[1]
        held.open()
        for f in (first, f_enc, f_ver):
            f.result(timeout=60)
    finally:
        held.open()
        eng.close()
    assert order == ["verify", "encode"]


def test_many_closed_loops_on_the_default_policy(queue_accounts):
    """More client threads than cores, each a closed loop, on an engine
    without a window, under a short switch interval: every result is
    the direct codec's, every drain is counted once under one trigger,
    batches coalesce (they meet while a batch runs) and nobody waited
    on policy."""
    codec = rs.make_codec(K, M, backend="cpu")
    eng = make_engine(K, M)
    n_threads, rounds = 16, 12
    datas = [rnd((1, K, 128), 200 + i) for i in range(n_threads)]
    want = [codec.encode(d) for d in datas]
    bad: list = []

    def client(i):
        for _ in range(rounds):
            if not np.array_equal(eng.encode(datas[i], timeout=60), want[i]):
                bad.append(i)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        assert not any(t.is_alive() for t in threads)
        snap = queue_accounts(eng, "encode")
    finally:
        sys.setswitchinterval(interval)
        eng.close()
    assert not bad
    assert snap["submitted"] == snap["completed"] == n_threads * rounds
    assert sum(snap["drains"].values()) == snap["batches"] < snap["completed"]
    assert snap["drains"]["window"] == snap["drains"]["forced"] == 0
    assert snap["queue"]["coalesce"]["s"] == 0.0


def _bare_engine(inflight=0, lanes=None, forced_t=None):
    """The trigger's own inputs on an engine that runs no thread."""
    eng = object.__new__(SubmissionEngine)
    eng._closed, eng._flushing = False, int(forced_t is not None)
    eng._forced_t = forced_t or 0.0
    eng._inflight = inflight
    eng.pool = None if lanes is None \
        else types.SimpleNamespace(n_devices=lanes)
    return eng


@pytest.mark.parametrize("case,kw,max_delay,budgets,want", [
    # the default policy: no window
    ("inline, free", {}, None, (8, 8), (10.0, "idle")),
    ("inline, busy", {"inflight": 1}, None, (8, 8), None),
    ("pool, a lane free", {"inflight": 1, "lanes": 2}, None, (8, 8),
     (10.0, "idle")),
    ("pool, every lane busy", {"inflight": 2, "lanes": 2}, None, (8, 8),
     None),
    ("busy, flushed", {"inflight": 1, "forced_t": 10.7}, None, (8, 8),
     (10.7, "forced")),
    ("free, flushed later", {"forced_t": 10.7}, None, (8, 8),
     (10.0, "idle")),
    # a numeric window: as it was, whatever the executors do
    ("window open", {}, 1.5, (8, 8), None),
    ("window over", {}, 0.75, (8, 8), (10.75, "window")),
    ("window over, busy", {"inflight": 1}, 0.75, (8, 8),
     (10.75, "window")),
    ("window open, flushed", {"forced_t": 10.7}, 1.5, (8, 8),
     (10.7, "forced")),
    ("zero window", {}, 0.0, (8, 8), (10.0, "window")),
    # the size budgets (requests, rows) beside either
    ("requests filled, busy", {"inflight": 1}, None, (2, 8),
     (10.5, "size")),
    ("rows filled, busy", {"inflight": 1}, None, (8, 2), (10.5, "size")),
    ("rows filled before the window", {}, 1.5, (8, 2), (10.5, "size")),
    ("the window before the rows", {}, 0.25, (8, 2), (10.25, "window")),
    # one instant, two triggers: the one named first counts
    ("one request over the budget", {}, None, (1, 8), (10.0, "size")),
    ("filled, free: the oldest's enqueue", {}, None, (2, 8),
     (10.0, "idle")),
])
def test_the_trigger_and_its_instant(case, kw, max_delay, budgets, want):
    """_tripped over two queued requests of a row each (enqueued at
    10.0 and 10.5, now 11.0): which trigger, and when."""
    q = [types.SimpleNamespace(enqueue_t=t, rows=1) for t in (10.0, 10.5)]
    assert _bare_engine(**kw)._tripped(q, 11.0, max_delay, *budgets) == want


def test_an_idle_drain_trips_at_the_earliest_stamp_in_the_queue():
    """A request is stamped before it takes the engine's lock, so two
    can queue out of order; no member of an idle drain waited on
    policy, so the instant is the earliest stamp, not q[0]'s (a numeric
    window still counts from q[0], as it did)."""
    q = [types.SimpleNamespace(enqueue_t=t, rows=1) for t in (10.5, 10.0)]
    eng = _bare_engine()
    assert eng._tripped(q, 11.0, None, 8, 8) == (10.0, "idle")
    assert eng._tripped(q, 11.0, 0.25, 8, 8) == (10.75, "window")


@pytest.mark.parametrize("max_delay,want", [(None, 0.25), (0.125, 0.125),
                                            (30.0, 0.25)])
def test_the_batcher_sets_a_timer_only_for_a_window_or_a_deadline(
        max_delay, want):
    """_wake_timeout: a class without a window wakes the batcher for
    deadlines alone (None: it sleeps until it is notified)."""
    eng = _bare_engine()
    eng.adaptive = None
    eng.policy = AdmissionPolicy(max_delay=max_delay)
    eng._queues = {"encode": collections.deque(
        [types.SimpleNamespace(enqueue_t=10.0, deadline=None)])}
    if max_delay is None:
        assert eng._wake_timeout(10.0) is None
    eng._queues["verify"] = collections.deque(
        [types.SimpleNamespace(enqueue_t=10.0, deadline=10.25)])
    assert eng._wake_timeout(10.0) == want


def test_adaptive_over_the_default_policy_seeds_and_caps_as_over_2ms():
    """AdaptiveBatchPolicy steers a number: over a static policy
    without a window it starts from 0.002 and caps at 0.016, and walks
    the same way under the same observations."""
    def walk(policy):
        ad = AdaptiveBatchPolicy(policy, targets={"encode": 1.0},
                                 update_every=4, window=8)
        seeded = ad.knobs("encode")
        for _ in range(64):             # headroom, under-occupied: grow
            ad.note("encode", 0.001, occupancy=1)
        return seeded, ad.delay_cap_s, ad.knobs("encode"), \
            ad.adjustment_log()

    default = walk(AdmissionPolicy())
    assert default == walk(AdmissionPolicy(max_delay=0.002))
    assert default[0] == (0.002, 32, 512) and default[1] == 0.016
    assert default[2][0] == 0.016                   # grew to the cap
    # an explicit window still seeds itself
    assert walk(AdmissionPolicy(max_delay=0.005))[:2] \
        == ((0.005, 32, 512), 0.04)


def test_an_adaptive_engine_over_the_default_policy_keeps_a_window(
        queue_accounts):
    eng = make_engine(K, M, adaptive=AdaptiveBatchPolicy())
    try:
        assert eng.policy.max_delay is None
        assert eng._knobs("encode") == (0.002, 32, 512)
        eng.encode(rnd((1, K, 64), 5))
        snap = queue_accounts(eng, "encode")
    finally:
        eng.close()
    assert snap["drains"]["window"] == 1 and snap["drains"]["idle"] == 0
    assert snap["queue"]["coalesce"]["s"] == pytest.approx(0.002, rel=1e-6)


# -- observability surface ---------------------------------------------------

def test_engine_stats_via_node_metrics_and_rpc(pkey):
    from cess_tpu.node.chain_spec import dev_spec
    from cess_tpu.node.metrics import collect, render_metrics
    from cess_tpu.node.network import Node
    from cess_tpu.node.rpc import RpcServer

    node = Node(dev_spec(), "eng-node",
                {"alice": dev_spec().session_key("alice")})
    srv = RpcServer(node, port=0)
    try:
        # no engine attached: RPC answers null, metrics stay clean
        assert srv.handle("cess_engineStats", []) is None
        assert not any(k.startswith("cess_engine_") for k in collect(node))
        eng = make_engine(K, M, podr2_key=pkey,
                          policy=AdmissionPolicy(max_delay=0.005))
        node.engine = eng
        try:
            eng.encode(rnd((2, K, 128), 8))
            m = collect(node)
            assert m["cess_engine_encode_completed"] == 1
            assert m["cess_engine_encode_batches"] == 1
            assert "cess_engine_verify_queue_depth" in m
            text = render_metrics(node)
            assert "cess_engine_encode_batch_occupancy" in text
            snap = srv.handle("cess_engineStats", [])
            assert snap["classes"]["encode"]["completed"] == 1
            assert set(snap["classes"]) \
                == {"verify", "prove", "tag", "repair", "encode"}
        finally:
            eng.close()
    finally:
        srv.httpd.server_close()
