"""Multi-chip serving plane (cess_tpu/serve/pool.py, ISSUE 10):
deterministic least-loaded placement, per-(backend, device) breakers,
drain-to-sibling on lane failure, device-keyed warm programs, and the
pool's stats/metrics surface.

The hard invariant throughout, inherited from the engine tests: the
pool changes WHERE a batch runs, never what it computes — pool-backed
results are BIT-IDENTICAL to the single-device engine and to the
direct codec/audit calls, fault or no fault.

conftest.py splits the CPU backend into 8 virtual devices, so every
multi-lane path here runs in the tier-1 CPU gate.
"""
import jax
import numpy as np
import pytest

from cess_tpu.obs import flight
from cess_tpu.ops import podr2, rs
from cess_tpu.resilience import ResilienceConfig, faults
from cess_tpu.resilience.faults import FaultPlan
from cess_tpu.serve import (AdmissionPolicy, DevicePool, EngineTimeout,
                            make_engine)

K, M = 2, 1
FRAG = 1024


def rnd(shape, seed=0, dtype=np.uint8):
    rng = np.random.default_rng(seed)
    return rng.integers(0, np.iinfo(dtype).max, shape, dtype=dtype)


def _pool_engine(n=2, res=None, pkey=None,
                 policy=AdmissionPolicy(max_delay=0.002)):
    return make_engine(K, M, rs_backend="jax", podr2_key=pkey,
                       resilience=res, policy=policy,
                       pool=DevicePool(n=n))


# -- determinism: pool == single-device == direct ---------------------------

def test_pool_engine_bit_identical_across_ops():
    pkey = podr2.Podr2Key.generate(21)
    codec = rs.make_codec(K, M, backend="cpu")
    eng = _pool_engine(n=2, pkey=pkey)
    try:
        assert eng.pool.n_devices == 2
        data = rnd((4, K, 256), 5)
        coded = eng.encode(data, timeout=60)
        assert np.array_equal(coded, codec.encode(data))
        surv = coded[:, [1, 2]]
        rec = eng.reconstruct(surv, (1, 2), (0,), timeout=60)
        assert np.array_equal(rec, codec.reconstruct(surv, (1, 2), (0,)))
        frags = rnd((5, FRAG), 7)
        ids = np.stack([podr2.fragment_id_from_hash(bytes([i]) * 32)
                        for i in range(5)])
        tags = eng.tag_fragments(ids, frags, timeout=60)
        assert np.array_equal(
            tags, np.asarray(podr2.tag_fragments(pkey, ids, frags)))
        snap = eng.pool.snapshot()
        assert snap["placements"] >= 3
        assert sum(ln["batches"] for ln in snap["lanes"]) >= 3
        # every placement is in the replay witness, count-sequenced
        log = eng.pool.placement_log()
        assert [row[0] for row in log] == list(range(1, len(log) + 1))
        assert all(row[5] in ("least-loaded", "probe", "all-open",
                              "requeue") for row in log)
    finally:
        eng.close()


def test_pool_stream_entry_bit_identical():
    from cess_tpu.models.pipeline import PipelineConfig, StoragePipeline
    from cess_tpu.serve.stream import StreamingIngest

    pipe = StoragePipeline(PipelineConfig(k=K, m=M, segment_size=2048))
    segs = rnd((6, 2048), 3)
    pool = DevicePool(n=2)
    direct = StreamingIngest(pipe, 4).ingest(segs)
    pooled = StreamingIngest(pipe, 4, pool=pool).ingest(segs)
    assert np.array_equal(np.asarray(pooled["tags"]),
                          np.asarray(direct["tags"]))
    assert np.array_equal(np.asarray(pooled["fragments"]),
                          np.asarray(direct["fragments"]))
    # batch must shard evenly over the lanes
    with pytest.raises(ValueError):
        StreamingIngest(pipe, 3, pool=DevicePool(n=2))


# -- the chaos drill: one sick lane drains to its sibling -------------------

def _drill(seed, n_batches=12):
    """Run the seeded chaos drill: every dispatch on lane 0 raises.
    Returns (outputs, pool snapshot, resilience snapshot, placement
    log, fired fault log)."""
    res = ResilienceConfig()
    eng = _pool_engine(n=2, res=res)
    plan = FaultPlan.seeded(seed, {"engine.dispatch.d0": (1.0, "raise")},
                            horizon=64)
    outs = []
    try:
        with faults.armed(plan):
            for i in range(n_batches):
                outs.append(eng.encode(rnd((3, K, 256), 100 + i),
                                       timeout=60))
                # settle lane counters between offers so the placement
                # log is a pure function of the offered sequence
                assert eng.flush(30)
        return (outs, eng.pool.snapshot(), res.stats.snapshot(),
                eng.pool.placement_log(), plan.fired_log())
    finally:
        eng.close()


def test_chaos_drill_sick_lane_drains_to_sibling():
    outs, snap, rsnap, log, fired = _drill(b"pool-drill")

    # outputs bit-identical to a no-fault single-device engine run
    solo = make_engine(K, M, rs_backend="jax",
                       policy=AdmissionPolicy(max_delay=0.002))
    try:
        for i, out in enumerate(outs):
            assert np.array_equal(
                out, solo.encode(rnd((3, K, 256), 100 + i), timeout=60))
    finally:
        solo.close()

    # the sick lane's breaker tripped; its sibling stayed closed and
    # absorbed every batch (member isolation: the engine-level codec
    # breaker is untouched too)
    br = rsnap["breakers"]
    assert br["codec.d0"]["state"] == "open"
    assert br["codec.d0"]["trips"] == 1
    assert br["codec.d1"]["state"] == "closed"
    assert br["codec.d1"]["trips"] == 0
    assert br["codec"]["trips"] == 0
    lanes = {ln["device"]: ln for ln in snap["lanes"]}
    assert lanes[0]["batches"] == 0
    assert lanes[1]["batches"] == len(outs)
    assert lanes[1]["requeues"] > 0
    # surviving traffic NEVER degraded to CPU: a healthy sibling
    # absorbed the drain before the fallback machinery was reached
    assert rsnap["degraded_batches"] == {}
    # faults fired on the lane-0 site only, until its breaker opened
    assert fired and all(site == "engine.dispatch.d0"
                         for site, _, _ in fired)
    # every pre-trip offer went lane 0 -> requeue to lane 1; post-trip
    # offers placed on lane 1 directly, except deterministic probes
    reasons = [(row[4], row[5]) for row in log]
    assert (0, "least-loaded") in reasons
    assert (1, "requeue") in reasons
    assert (1, "least-loaded") in reasons
    assert (0, "probe") in reasons          # trips are never permanent


def test_chaos_drill_replays_bit_for_bit():
    outs1, _, _, log1, fired1 = _drill(b"pool-replay")
    outs2, _, _, log2, fired2 = _drill(b"pool-replay")
    assert fired1 == fired2
    assert log1 == log2                     # the replay witness
    for a, b in zip(outs1, outs2):
        assert np.array_equal(a, b)


def test_chaos_drill_journals_the_drain():
    rec = flight.FlightRecorder(b"pool-journal")
    with flight.armed(rec):
        _drill(b"pool-drill", n_batches=6)
    requeues = rec.journal_tail("pool")
    assert requeues and all(e["kind"] == "requeue" for e in requeues)
    assert all(e["detail"]["src"] == 0 and e["detail"]["dst"] == 1
               for e in requeues)
    trips = [e for e in rec.journal_tail("breaker")
             if e["kind"] == "trip"]
    assert any(e["detail"]["name"] == "codec.d0" for e in trips)


# -- warm programs are device-keyed (the one-device key bugfix) -------------

@pytest.mark.parametrize("strategy", ["gather", "pallas"])
def test_warm_reconstruct_hits_only_its_own_device(strategy, compiles):
    devs = jax.devices()
    assert len(devs) >= 3       # conftest: 8 virtual CPU devices
    codec = rs.TPUCodec(K, M, strategy=strategy)
    data = rnd((K, 264), 11)    # a width no other test compiles
    coded = np.asarray(codec.encode(data))
    surv, present, missing = coded[[1, 2]], (1, 2), (0,)
    codec.warm_reconstruct(present, missing, surv.shape,
                           device=devs[1])
    # under a DIFFERENT device's placement scope the dev-1 program
    # must not run (pre-fix, the device-free key dispatched a program
    # staged on the wrong chip); the cold path still serves correctly
    compiled = compiles()
    with jax.default_device(devs[2]):
        out = np.asarray(codec.reconstruct(surv, present, missing))
    assert compiles() > compiled
    assert np.array_equal(out[0], data[0])
    # warmed FOR its placement the same call compiles nothing, and
    # neither does another pattern of the same shape: the program is
    # the shape's, the pattern's matrix its argument
    compiled = compiles()
    with jax.default_device(devs[1]):
        out2 = np.asarray(codec.reconstruct(surv, present, missing))
        other = np.asarray(codec.reconstruct(coded[[0, 2]], (0, 2), (1,)))
    assert compiles() == compiled
    assert np.array_equal(out2, out) and np.array_equal(other[0], data[1])
    # no scope + no device keeps the PR-2 single-device contract
    codec.warm_reconstruct(present, missing, surv.shape)
    compiled = compiles()
    np.asarray(codec.reconstruct(surv, present, missing))
    np.asarray(codec.reconstruct(coded[[0, 1]], (0, 1), (2,)))
    assert compiles() == compiled


def test_engine_warm_repair_warms_every_lane(compiles):
    eng = _pool_engine(n=2)
    try:
        eng.warm_repair([((1, 2), (0,))], 256, buckets=(1,))
        # one device-free program + one per lane, all under the exact
        # keys _op_repair looks up
        # (the shape's: 2 survivors, 1 lost row; the pattern is the
        # program's argument)
        keys = {("repair", 2, 1, 256, 1),
                ("repair", 2, 1, 256, 1, ("device", 0)),
                ("repair", 2, 1, 256, 1, ("device", 1))}
        assert keys <= set(eng.programs._programs)
        # and the codec's program — a host claim's: linear rows in,
        # stacked on the device (PR 32) — is compiled for every lane's
        # device, whichever pattern of the shape it is called with
        compiled = compiles()
        surv = rnd((1, 2, 256), 12)
        for lane in eng.pool.lanes:
            with jax.default_device(lane.device):
                rows = rs.LinearRows(tuple(jax.device_put(list(surv[0]))),
                                     2)
                jax.block_until_ready(
                    eng.codec.reconstruct(rows, (0, 2), (1,)))
        assert compiles() == compiled
    finally:
        eng.close()


def test_pool_warms_per_device_under_the_chips_lowering(compiles):
    """The Pallas kernel (interpret mode here) behind a pool of two
    lanes: warm_repair compiles the shape's program for each lane's
    device, and after it neither a claim through the engine nor a
    pattern never named, on either lane, compiles anything."""
    from cess_tpu.serve.engine import SubmissionEngine

    n = 392                         # a width no other test compiles
    eng = SubmissionEngine(rs.TPUCodec(K, M, strategy="pallas"),
                           policy=AdmissionPolicy(max_delay=0.002),
                           pool=DevicePool(n=2))
    try:
        eng.warm_repair([((1, 2), (0,))], n, buckets=(1,))
        assert {("repair", 2, 1, n, 1, ("device", 0)),
                ("repair", 2, 1, n, 1, ("device", 1))} \
            <= set(eng.programs._programs)
        coded = rs.make_codec(K, M, backend="cpu").encode(rnd((1, K, n), 14))
        compiled = compiles()
        out = eng.reconstruct(coded[:, [0, 2]], (0, 2), (1,), timeout=60)
        assert np.array_equal(np.asarray(out), coded[:, [1]])
        for lane in eng.pool.lanes:
            with jax.default_device(lane.device):
                rows = rs.LinearRows(
                    tuple(jax.device_put(list(coded[0, [0, 1]]))), 2)
                got = eng.codec.reconstruct(rows, (0, 1), (2,))
                assert got.devices() == {lane.device}
                assert np.array_equal(np.asarray(got), coded[:, [2]])
        assert compiles() == compiled
    finally:
        eng.close()


def test_host_rows_and_result_stay_on_the_batch_lane(compiles, monkeypatch):
    """A host claim's rows are put on the device of the lane that runs
    the batch, stacked and repaired there by that lane's program, and
    the result comes down from there: with lane 0's dispatches failing
    the same claim drains to lane 1 and is served by lane 1's program,
    never lane 0's. Nothing compiles after warm_repair, on either."""
    eng = _pool_engine(n=2, res=ResilienceConfig())
    n = 328                         # a width no other test compiles
    seen = []       # a batch: its program's key, rows' devices, result's
    put, split, get = eng._put_rows, eng._split_rows, eng.programs.get

    def programs_get(key, build):
        if key[0] == "repair":          # _op_repair looks it up first
            seen.append([key])
        return get(key, build)

    def put_rows(rows, q, bucket, n):
        placed = put(rows, q, bucket, n)
        seen[-1].append({d for row in placed.rows for d in row.devices()})
        return placed

    def split_rows(batch, out, lane=None):
        seen[-1] += [out.devices(), lane.index]
        return split(batch, out, lane)

    try:
        eng.warm_repair([((1, 2), (0,))], n, buckets=(1,))
        monkeypatch.setattr(eng, "_put_rows", put_rows)
        monkeypatch.setattr(eng.programs, "get", programs_get)
        monkeypatch.setattr(eng, "_split_rows", split_rows)
        coded = rs.make_codec(K, M, backend="cpu").encode(rnd((1, K, n), 13))
        compiled = compiles()
        built = eng.stats_snapshot()["programs_built"]
        # a pattern warm_repair never named, as host rows
        rows, present, missing = [coded[0, 0], coded[0, 2]], (0, 2), (1,)
        out = eng.reconstruct(rows, present, missing, timeout=60)
        assert np.array_equal(out[0], coded[0, 1])
        plan = FaultPlan.seeded(b"lane0", {"engine.dispatch.d0":
                                           (1.0, "raise")}, horizon=64)
        with faults.armed(plan):
            out = eng.reconstruct(rows, present, missing, timeout=60)
        assert plan.fired_log()
        assert np.array_equal(out[0], coded[0, 1])
        assert compiles() == compiled
        assert eng.stats_snapshot()["programs_built"] == built
        lanes = eng.pool.lanes
        assert [s[3] for s in seen] == [0, 1]
        for key, devices, result, index in seen:
            assert devices == result == {lanes[index].device}
            assert key == ("repair", 2, 1, n, 1, ("device", index))
        st = eng.stats_snapshot()["classes"]["repair"]
        assert st["linear_puts"] == st["batches"] == 2
    finally:
        eng.close()


# -- the drain trigger on the pool path (PR 39) ------------------------------
# (the ``gate`` fixture, tests/conftest.py, keeps a lane busy for as long
# as a test wants; ``policy=None`` is the default policy: no window)

def test_a_free_lane_drains_at_once_and_busy_lanes_gather(gate, queue_accounts):
    """The default policy on the pool path: a request goes the moment a
    lane has nothing placed on it; with every lane busy the class
    gathers, and the batch whose end frees a lane takes all of it."""
    codec = rs.make_codec(K, M, backend="cpu")
    data = rnd((1, K, 256), 70)
    eng = _pool_engine(2, policy=None)
    held = gate(eng, "encode")
    try:
        a = eng.submit_encode(data)
        assert held.running()               # lane 0 is busy
        b = eng.submit_encode(data)
        assert held.running()               # ...lane 1 took b at once
        rest = [eng.submit_encode(data) for _ in range(3)]
        # both lanes busy: no third batch reaches a runner, they gather
        assert not held.entered.acquire(timeout=0.05)
        assert eng.stats_snapshot()["classes"]["encode"]["queue_depth"] == 3
        assert [row[4] for row in eng.pool.placement_log()] == [0, 1]
        held.open()
        for f in [a, b] + rest:
            assert np.array_equal(f.result(timeout=60), codec.encode(data))
        snap = queue_accounts(eng, "encode")
        placed = eng.pool.placement_log()
    finally:
        held.open()
        eng.close()
    # a | b | the three that gathered, as one batch (no timer: the
    # end of a batch re-evaluated the class)
    assert (snap["completed"], snap["batches"]) == (5, 3)
    assert [row[2] for row in placed] == [1, 1, 3]
    assert snap["queue"]["coalesce"]["s"] == 0.0
    assert snap["drains"] == {"idle": 3, "window": 0, "size": 0,
                              "forced": 0}


def test_a_deadline_expires_while_gathering_behind_busy_lanes(gate, queue_accounts):
    eng = _pool_engine(1, policy=None)
    held = gate(eng, "encode")
    try:
        a = eng.submit_encode(rnd((1, K, 256), 71))
        assert held.running()
        late = eng.submit_encode(rnd((1, K, 256), 72), timeout=0.05)
        kept = eng.submit_encode(rnd((1, K, 256), 73))
        with pytest.raises(EngineTimeout):
            late.result(timeout=30)         # the batcher's own timer
        assert not a.done() and not kept.done()
        held.open()
        a.result(timeout=60), kept.result(timeout=60)
        snap = queue_accounts(eng, "encode")
    finally:
        held.open()
        eng.close()
    assert snap["timeouts"] == 1 and snap["completed"] == 2


def test_a_higher_class_goes_first_when_the_lane_comes_free(gate):
    pkey = podr2.Podr2Key.generate(26)
    eng = _pool_engine(1, pkey=pkey, policy=None)
    held = gate(eng, "encode")
    order: list[str] = []
    real_verify = eng._op_verify_batch
    eng._op_verify_batch = lambda *a: (order.append("verify"),
                                       real_verify(*a))[1]
    try:
        first = eng.submit_encode(rnd((1, K, 256), 74))
        assert held.running()
        f_enc = eng.submit_encode(rnd((1, K, 256), 75))     # gathers
        blocks = FRAG // podr2.BLOCK_BYTES
        idx, nu = podr2.gen_challenge(b"round-39", blocks)
        f_ver = eng.submit_verify_batch(                    # LATER
            np.zeros((1, 2), np.uint32), blocks, idx, nu,
            np.zeros((1, podr2.SECTORS), np.uint32),
            np.zeros((1, podr2.LIMBS), np.uint32))
        real_encode, eng._op_encode = eng._op_encode, \
            lambda *a: (order.append("encode"), real_encode(*a))[1]
        held.open()
        for f in (first, f_enc, f_ver):
            f.result(timeout=60)
    finally:
        held.open()
        eng.close()
    assert order == ["verify", "encode"]


def test_a_numeric_window_holds_a_request_before_free_lanes(gate, queue_accounts):
    eng = _pool_engine(2, policy=AdmissionPolicy(max_delay=30.0))
    held = gate(eng, "encode")
    held.open()
    try:
        fut = eng.submit_encode(rnd((1, K, 256), 76))
        assert not held.entered.acquire(timeout=0.05)
        assert eng.stats_snapshot()["classes"]["encode"]["queue_depth"] == 1
        assert eng.flush(60)
        fut.result(timeout=60)
        snap = queue_accounts(eng, "encode")
    finally:
        eng.close()
    assert snap["drains"] == {"idle": 0, "window": 0, "size": 0,
                              "forced": 1}


# -- surfaces: zero-cost default, snapshot, metrics, lifecycle --------------

def test_engine_without_pool_is_unchanged():
    eng = make_engine(K, M, rs_backend="jax",
                      policy=AdmissionPolicy(max_delay=0.002))
    try:
        assert eng.pool is None
        assert "devices" not in eng.stats_snapshot()
        assert not any(k.startswith("cess_engine_device")
                       for k in eng.stats.metrics())
        data = rnd((2, K, 128), 1)
        assert np.array_equal(
            eng.encode(data, timeout=60),
            rs.make_codec(K, M, backend="cpu").encode(data))
    finally:
        eng.close()


def test_pool_snapshot_and_metrics_surface():
    eng = _pool_engine(n=2, res=ResilienceConfig())
    try:
        eng.encode(rnd((3, K, 128), 2), timeout=60)
        assert eng.flush(30)
        snap = eng.stats_snapshot()["devices"]
        assert snap["n_devices"] == 2 and snap["placements"] >= 1
        assert [ln["device"] for ln in snap["lanes"]] == [0, 1]
        for ln in snap["lanes"]:
            assert ln["breakers"] == {"codec": "closed"}
            assert ln["inflight_rows"] == 0
        m = eng.stats.metrics()
        assert m["cess_engine_device_count"] == 2.0
        assert m["cess_engine_device_placements"] >= 1.0
        assert sum(m[f"cess_engine_device_{i}_batches"]
                   for i in (0, 1)) >= 1.0
        assert m["cess_engine_device_0_codec_open"] == 0.0
    finally:
        eng.close()


def test_pool_lifecycle_guards():
    with pytest.raises(ValueError):
        DevicePool(devices=[])
    with pytest.raises(ValueError):
        DevicePool(n=1, probe_every=0)
    pool = DevicePool(n=1)
    eng = make_engine(K, M, rs_backend="jax", pool=pool)
    try:
        with pytest.raises(ValueError):     # one pool, one engine
            pool.bind(eng)
    finally:
        eng.close()
    with pytest.raises(RuntimeError):       # closed pools refuse work
        import types

        pool.dispatch([types.SimpleNamespace(key=("encode",), rows=1)])
    # make_engine's count forms: an int builds the pool itself
    eng2 = make_engine(K, M, rs_backend="jax", pool=2)
    try:
        assert eng2.pool.n_devices == 2
    finally:
        eng2.close()


def test_cli_pool_requires_engine():
    from cess_tpu.node.cli import main

    with pytest.raises(SystemExit):
        main(["run", "--dev", "--blocks", "1", "--pool"])
    with pytest.raises(SystemExit):
        main(["run", "--dev", "--blocks", "1", "--engine", "cpu",
              "--pool", "-3"])
    assert main(["run", "--dev", "--blocks", "2", "--engine", "cpu",
                 "--pool", "2"]) == 0
