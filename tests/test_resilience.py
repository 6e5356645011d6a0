"""Chaos harness for the resilience layer (cess_tpu/resilience).

Tier-1 BY DESIGN: every fault here comes from a seeded FaultPlan, so
the same test drives the same faults at the same sites in the same
order on every run — determinism proofs (same seed => identical fault
schedule AND identical outputs, at both MAC limb widths), the engine's
failure-isolation / CPU-degradation machinery, retry/backoff budget
semantics, and the tentpole end-to-end: a full offchain audit round
(upload -> challenge -> prove -> verify) completing correctly while
the engine's device path is failing, via the tripped-breaker CPU
fallback.
"""
import time

import numpy as np
import pytest

from cess_tpu.ops import podr2, rs
from cess_tpu.resilience import (Budget, FaultInjected, FaultPlan,
                                 FaultSpec, HealthMonitor,
                                 ResilienceConfig, RetryPolicy, faults)
from cess_tpu.serve import AdmissionPolicy, make_engine

K, M = 2, 1
FRAG = 1024               # bytes per fragment -> 2 PoDR2 blocks


@pytest.fixture(autouse=True)
def _always_disarm():
    """No chaos test may leak an armed plan into its neighbors."""
    yield
    faults.disarm()


@pytest.fixture(scope="module")
def pkey():
    return podr2.Podr2Key.generate(44)


def rnd(shape, seed=0, dtype=np.uint8):
    rng = np.random.default_rng(seed)
    return rng.integers(0, np.iinfo(dtype).max, shape, dtype=dtype)


# -- fault plans -------------------------------------------------------------

def test_seeded_plan_schedule_is_seed_deterministic():
    sites = {"engine.dispatch": (0.3, "raise"),
             "net.send": (0.5, "drop")}
    a = FaultPlan.seeded(b"seed-1", sites, horizon=128)
    b = FaultPlan.seeded(b"seed-1", sites, horizon=128)
    c = FaultPlan.seeded(b"seed-2", sites, horizon=128)
    assert a.schedule == b.schedule                 # same seed: identical
    assert a.schedule != c.schedule                 # different seed: not
    fired = a.schedule["engine.dispatch"]
    assert fired and len(fired) < 128               # ~30%, not 0/100%


def test_hooks_fire_at_scheduled_ordinals_and_log():
    plan = FaultPlan({
        "a.raise": {1: FaultSpec("raise", message="boom")},
        "b.drop": {0: FaultSpec("drop")},
        "c.corrupt": {0: FaultSpec("corrupt", xor=0x01)},
        "d.delay": {0: FaultSpec("delay", delay_s=0.01)},
    })
    with faults.armed(plan):
        faults.inject("a.raise")                    # ordinal 0: clean
        with pytest.raises(FaultInjected, match="a.raise#1: boom"):
            faults.inject("a.raise")
        assert faults.allow("b.drop") is False      # ordinal 0 drops
        assert faults.allow("b.drop") is True
        assert faults.corrupt("c.corrupt", b"\x10\x20") == b"\x11\x20"
        arr = faults.corrupt("c.corrupt",
                             np.array([4, 5], dtype=np.uint8))
        assert arr.tolist() == [4, 5]               # ordinal 1: clean
        t0 = time.perf_counter()
        faults.inject("d.delay")
        assert time.perf_counter() - t0 >= 0.01
    assert plan.fired_log() == (("a.raise", 1, "raise"),
                                ("b.drop", 0, "drop"),
                                ("c.corrupt", 0, "corrupt"),
                                ("d.delay", 0, "delay"))
    assert plan.counts()["a.raise"] == 2


def test_unarmed_hooks_are_noops():
    faults.disarm()
    faults.inject("anything")
    assert faults.allow("anything") is True
    assert faults.corrupt("anything", b"xy") == b"xy"
    assert faults.armed_plan() is None


# -- retry / backoff / budget -----------------------------------------------

def test_retry_backoff_is_deterministic_and_budgeted():
    pol = RetryPolicy(max_attempts=4, base_delay_s=0.01, multiplier=2.0,
                      max_delay_s=1.0, jitter_frac=0.5)
    # deterministic jitter: same (attempt, token) => same delay; the
    # jitter stays within its fraction; tokens decorrelate
    assert pol.delay_for(1, token="x") == pol.delay_for(1, token="x")
    assert pol.delay_for(1, token="x") != pol.delay_for(1, token="y")
    for attempt, base in ((1, 0.01), (2, 0.02), (3, 0.04)):
        d = pol.delay_for(attempt, token="x")
        assert base <= d <= base * 1.5
    # budget propagation: each attempt sees the SHRUNK remainder
    seen = []
    budget = Budget(10.0)

    def fn(b):
        seen.append(b.remaining())
        raise KeyError("transient")

    with pytest.raises(KeyError):
        pol.call(fn, retry_on=(KeyError,), budget=budget,
                 sleep=lambda s: None)
    assert len(seen) == 4                       # max_attempts exhausted
    assert all(s <= 10.0 for s in seen)
    # a budget smaller than the first backoff abandons immediately
    short = []
    with pytest.raises(KeyError):
        pol.call(lambda b: short.append(1) or (_ for _ in ()).throw(
            KeyError()), retry_on=(KeyError,), budget=Budget(0.001),
            sleep=time.sleep)
    assert len(short) == 1                      # no doomed backoff sleep
    # non-retryable errors pass straight through
    with pytest.raises(ValueError):
        pol.call(lambda b: (_ for _ in ()).throw(ValueError()),
                 retry_on=(KeyError,))


def test_health_monitor_trips_and_probes_by_count():
    mon = HealthMonitor(window=8, error_threshold=0.5, min_samples=4,
                        probe_every=3)
    for _ in range(3):
        mon.record_error()
    assert mon.state == "closed"                # below min_samples
    mon.record_error()
    assert mon.state == "open"                  # 4/4 errors: tripped
    assert mon.snapshot()["trips"] == 1
    # while open: every 3rd allow() is a probe, one in flight at a time
    assert [mon.allow() for _ in range(3)] == [False, False, True]
    assert mon.allow() is False                 # probe still in flight
    mon.record_error()                          # probe failed: stay open
    assert mon.state == "open"
    assert [mon.allow() for _ in range(3)] == [False, False, True]
    mon.record_success(0.01)                    # probe passed: recover
    assert mon.state == "closed"
    assert mon.snapshot()["recoveries"] == 1 \
        and mon.snapshot()["probes"] == 2
    mon.force_open()
    assert mon.state == "open" and mon.snapshot()["trips"] == 2
    mon.force_close()
    assert mon.state == "closed"


# -- engine: degradation, isolation, retry ----------------------------------

def test_device_failure_degrades_to_cpu_bit_identical(pkey):
    """The tentpole's core loop in miniature: every device dispatch
    fails, the breaker trips, batches transparently serve on the CPU
    reference — results bit-identical — and recovery probes close the
    breaker once the faults stop."""
    res = ResilienceConfig(monitor=lambda: HealthMonitor(
        min_samples=2, probe_every=2))
    eng = make_engine(K, M, rs_backend="jax", podr2_key=pkey,
                      policy=AdmissionPolicy(max_delay=0.002),
                      resilience=res)
    codec = rs.make_codec(K, M, backend="cpu")
    plan = FaultPlan.seeded(b"degrade", {"engine.dispatch": (1.0, "raise")},
                            horizon=4096)
    try:
        with faults.armed(plan):
            for seed in range(6):
                data = rnd((2, K, 128), seed)
                out = eng.encode(data, timeout=60)
                assert np.array_equal(out, codec.encode(data))
        assert plan.fired_log()                   # chaos actually fired
        assert eng.monitors["codec"].state == "open"
        snap = res.stats.snapshot()
        assert snap["fallback_batches"].get("encode", 0) >= 1
        assert snap["degraded_batches"].get("encode", 0) >= 1
        m = eng.stats_metrics()
        assert m["cess_resilience_breaker_codec_open"] == 1.0
        assert m["cess_resilience_breaker_codec_trips"] >= 1.0
        assert m["cess_resilience_encode_fallback_batches"] >= 1.0
        # faults stop: recovery probes find the device healthy again
        for seed in range(20):
            data = rnd((1, K, 128), 50 + seed)
            assert np.array_equal(eng.encode(data, timeout=60),
                                  codec.encode(data))
            if eng.monitors["codec"].state == "closed":
                break
        assert eng.monitors["codec"].state == "closed"
        assert eng.stats_metrics()[
            "cess_resilience_breaker_codec_recoveries"] >= 1.0
    finally:
        eng.close()


def test_batch_member_isolation_requeues_individually():
    """A device error against a coalesced batch re-runs the members
    individually: the healthy mate resolves, only the poisoned member
    fails (fallback disabled here so the rejection is observable)."""
    codec = rs.make_codec(K, M, backend="cpu")
    res = ResilienceConfig(fallback=False)
    eng = make_engine(K, M, policy=AdmissionPolicy(max_delay=0.25),
                      resilience=res)
    # ordinal 0 = the coalesced batch; ordinal 2 = member b's solo
    # re-run (member a's solo re-run is ordinal 1, clean)
    plan = FaultPlan({"engine.dispatch": {0: FaultSpec("raise"),
                                          2: FaultSpec("raise")}})
    try:
        with faults.armed(plan):
            a, b = rnd((2, K, 128), 1), rnd((3, K, 128), 2)
            fa = eng.submit_encode(a)
            fb = eng.submit_encode(b)
            assert np.array_equal(fa.result(timeout=30), codec.encode(a))
            with pytest.raises(FaultInjected):
                fb.result(timeout=30)
        assert plan.fired_log() == (("engine.dispatch", 0, "raise"),
                                    ("engine.dispatch", 2, "raise"))
        snap = res.stats.snapshot()
        assert snap["batch_requeues"] == 2
        assert eng.stats_metrics()[
            "cess_resilience_batch_requeues"] == 2.0
        st = eng.stats_snapshot()["classes"]["encode"]
        assert st["completed"] == 1 and st["failed"] == 1
    finally:
        eng.close()


def test_saturated_blocking_submit_retries_with_backoff():
    codec = rs.make_codec(K, M, backend="cpu")
    res = ResilienceConfig(retry=RetryPolicy(max_attempts=10,
                                             base_delay_s=0.02))
    eng = make_engine(K, M,
                      policy=AdmissionPolicy(queue_cap=1,
                                             max_delay=0.005),
                      resilience=res)
    real = eng._op_encode
    eng._op_encode = lambda b, d=False: (time.sleep(0.25), real(b, d))[1]
    try:
        eng.submit_encode(rnd((1, K, 64), 1))   # drains, sleeps 0.25s
        time.sleep(0.05)
        eng.submit_encode(rnd((1, K, 64), 2))   # queued: cap reached
        data = rnd((1, K, 64), 3)
        out = eng.encode(data, timeout=30)      # saturated -> retries
        assert np.array_equal(out, codec.encode(data))
        assert res.stats.snapshot()["retries"].get("encode", 0) >= 1
    finally:
        eng.close()


def test_abandon_when_budget_exhausted():
    from cess_tpu.serve import EngineSaturated

    res = ResilienceConfig(retry=RetryPolicy(max_attempts=8,
                                             base_delay_s=0.05))
    eng = make_engine(K, M,
                      policy=AdmissionPolicy(queue_cap=1,
                                             max_delay=30.0),
                      resilience=res)
    try:
        eng.submit_encode(rnd((1, K, 64), 1))   # parks in the queue
        with pytest.raises(EngineSaturated):
            eng.encode(rnd((1, K, 64), 2), timeout=0.08)
        assert res.stats.snapshot()["abandoned"].get("encode", 0) == 1
    finally:
        eng.close()


def test_tag_seam_fires_on_every_engine_tag_batch(pkey):
    """``podr2.tag.<platform>`` is crossed once per engine tag batch,
    the batches after the first included: they run a compiled program
    (ops/podr2.py TAG_PROGRAM) that jit already holds, and the seam
    must stay outside it. A delay changes nothing, a raise rejects that
    batch alone (no resilience layer here to hide it)."""
    import jax

    site = f"podr2.tag.{jax.devices('cpu')[0].platform}"
    eng = make_engine(podr2_key=pkey,
                      policy=AdmissionPolicy(max_delay=0.002))
    plan = FaultPlan({site: {0: FaultSpec("delay", delay_s=0.001),
                             1: FaultSpec("delay", delay_s=0.001),
                             2: FaultSpec("raise"),
                             3: FaultSpec("delay", delay_s=0.001)}})
    ids = np.stack([podr2.fragment_id_from_hash(bytes([j]) * 32)
                    for j in range(2)])
    try:
        with faults.armed(plan):
            for batch in range(4):
                frags = rnd((2, FRAG), 70 + batch)
                if batch == 2:
                    with pytest.raises(FaultInjected):
                        eng.tag_fragments(ids, frags, timeout=60)
                    continue
                assert np.array_equal(
                    eng.tag_fragments(ids, frags, timeout=60),
                    np.asarray(podr2.tag_fragments(pkey, ids, frags)))
        assert plan.fired_log() == ((site, 0, "delay"), (site, 1, "delay"),
                                    (site, 2, "raise"), (site, 3, "delay"))
        st = eng.stats_snapshot()["classes"]["tag"]
        assert st["completed"] == 3 and st["failed"] == 1
    finally:
        eng.close()


# -- streaming + transfer seams ---------------------------------------------

def test_stream_staging_fault_seams(pkey):
    from cess_tpu.models.pipeline import PipelineConfig, StoragePipeline
    from cess_tpu.serve.stream import StreamingIngest

    cfg = PipelineConfig(k=K, m=M, segment_size=K * FRAG)
    pipe = StoragePipeline(cfg, podr2_key=pkey)
    segs = rnd((5, K * FRAG), 3)
    clean = StreamingIngest(pipe, batch=2).ingest(segs)
    # delay faults perturb timing only: results identical
    plan = FaultPlan({"stream.h2d": {0: FaultSpec("delay", delay_s=0.01),
                                     2: FaultSpec("delay", delay_s=0.01)}})
    with faults.armed(plan):
        delayed = StreamingIngest(pipe, batch=2).ingest(segs)
    assert np.array_equal(np.asarray(clean["tags"]),
                          np.asarray(delayed["tags"]))
    assert plan.fired_log() == (("stream.h2d", 0, "delay"),
                                ("stream.h2d", 2, "delay"))
    # a raise at the dispatch seam surfaces to the consumer
    with faults.armed(FaultPlan({"stream.dispatch":
                                 {1: FaultSpec("raise")}})):
        with pytest.raises(FaultInjected):
            StreamingIngest(pipe, batch=2).ingest(segs)


def test_miner_transfer_retries_drops_and_rejects_corruption(pkey):
    """Fragment transfer: drops are retried under the policy; a
    corrupted transfer FAILS the integrity check (never poisons the
    store) and is retried; a clean retry lands the true bytes."""
    from cess_tpu.models.pipeline import PipelineConfig, StoragePipeline
    from cess_tpu.node.chain_spec import dev_spec
    from cess_tpu.node.network import Node
    from cess_tpu.node.offchain import MinerAgent, OssGateway
    from cess_tpu.crypto.hashing import fragment_hash

    cfg = PipelineConfig(k=K, m=M, segment_size=K * FRAG)
    node = Node(dev_spec(), "res-host", {})
    gw = OssGateway(node, "gw", StoragePipeline(cfg, podr2_key=pkey))
    blob = rnd((cfg.fragment_size,), 9).tobytes()
    h = fragment_hash(blob)
    gw.fragment_store[h] = blob
    gw.tag_store[h] = np.zeros((2, pkey.limbs), np.uint32)
    pol = RetryPolicy(max_attempts=4, base_delay_s=0.001)
    miner = MinerAgent(node, "m1", [gw],
                       StoragePipeline(cfg, podr2_key=pkey), retry=pol)
    # attempt 1 dropped (never reaches the bytes seam); attempt 2
    # delivered but corrupted (fails the integrity check); attempt 3
    # clean — note fetch_bytes ordinals count DELIVERED transfers only
    plan = FaultPlan({"offchain.fetch": {0: FaultSpec("drop")},
                      "offchain.fetch_bytes": {0: FaultSpec("corrupt")}})
    with faults.armed(plan):
        assert miner._fetch(h) is True          # 3rd attempt clean
    assert miner.store[h] == blob
    assert plan.fired_log() == (("offchain.fetch", 0, "drop"),
                                ("offchain.fetch_bytes", 0, "corrupt"))
    # without retry, a single corrupted transfer is a failed fetch —
    # and nothing corrupt ever lands in the store either way
    no_retry = MinerAgent(node, "m2", [gw],
                          StoragePipeline(cfg, podr2_key=pkey))
    with faults.armed(FaultPlan({"offchain.fetch_bytes":
                                 {0: FaultSpec("corrupt")}})):
        assert no_retry._fetch(h) is False
    assert h not in no_retry.store


# -- determinism: replay at both limb widths --------------------------------

@pytest.mark.parametrize("limbs", [2, 3])
def test_identical_seed_identical_faults_and_outputs(limbs):
    """Satellite: same seed + plan => identical fault firing sites/
    ordinals AND identical final outputs, at limbs=2 and limbs=3 —
    with the faults actually biting (device failures absorbed by the
    CPU fallback, results still equal the clean direct path)."""
    key = podr2.Podr2Key.generate(71, podr2.Podr2Params(limbs=limbs))

    def run_once():
        plan = FaultPlan.seeded(b"replay", {
            "engine.dispatch": (0.5, "raise"),
            "rs.encode": (0.4, "raise"),
        }, horizon=256)
        eng = make_engine(K, M, rs_backend="jax", podr2_key=key,
                          policy=AdmissionPolicy(max_delay=0.002),
                          resilience=ResilienceConfig())
        outs = []
        try:
            with faults.armed(plan):
                for seed in range(4):
                    outs.append(eng.encode(rnd((2, K, 128), seed),
                                           timeout=60))
                frags = rnd((3, FRAG), 9)
                ids = np.stack([podr2.fragment_id_from_hash(
                    bytes([limbs, i]) * 16) for i in range(3)])
                tags = eng.tag_fragments(ids, frags, timeout=60)
                outs.append(tags)
                idx, nu = podr2.gen_challenge(b"replay-round",
                                              tags.shape[1])
                r = np.asarray(podr2.aggregate_coeffs(b"replay-round",
                                                      ids))
                mu, sigma = eng.prove_aggregate(frags, tags, idx, nu, r,
                                                timeout=60)
                outs.extend([np.asarray(mu), np.asarray(sigma)])
                ok = eng.verify_aggregate(ids, tags.shape[1], idx, nu,
                                          r, mu, sigma, timeout=60)
        finally:
            eng.close()
        return plan.fired_log(), outs, ok

    log1, outs1, ok1 = run_once()
    log2, outs2, ok2 = run_once()
    assert log1, "plan never fired — the chaos run tested nothing"
    assert log1 == log2                      # sites, ordinals, kinds
    assert ok1 is True and ok2 is True
    assert len(outs1) == len(outs2)
    for a, b in zip(outs1, outs2):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # and the faulted outputs equal the clean direct path: sigma has
    # the requested limb width, encodes match the reference codec
    codec = rs.make_codec(K, M, backend="cpu")
    for seed, out in enumerate(outs1[:4]):
        assert np.array_equal(out, codec.encode(rnd((2, K, 128), seed)))
    assert outs1[6].shape == (limbs,)


# -- the chaos end-to-end: offchain round under device failure ---------------

def _storage_world(pkey, engine):
    """Compact storage network (3 validators, 1 gateway, 3 miners,
    1 TEE, tiny segments) with every agent routed through ``engine`` —
    the tests/test_network.py fixture recipe, resilience-sized."""
    from cess_tpu import constants
    from cess_tpu.chain.attestation import issue_cert, issue_report
    from cess_tpu.crypto import bls12381
    from cess_tpu.crypto.rsa import generate_rsa_keypair
    from cess_tpu.models.pipeline import PipelineConfig, StoragePipeline
    from cess_tpu.node.chain_spec import ChainSpec, ValidatorGenesis
    from cess_tpu.node.network import Network, Node
    from cess_tpu.node.offchain import (MinerAgent, OssGateway, TeeAgent,
                                        ValidatorOcw)

    D = constants.DOLLARS
    spec = ChainSpec(
        name="t", chain_id="resilience-net",
        endowed=(("alice", 1_000_000_000 * D), ("gw", 1_000_000 * D),
                 ("stash1", 10_000_000 * D), ("tee1", 1_000 * D),
                 ("m1", 10_000 * D), ("m2", 10_000 * D),
                 ("m3", 10_000 * D)),
        validators=tuple(ValidatorGenesis(f"v{i}", 4_000_000 * D)
                         for i in range(3)),
        era_blocks=40, epoch_blocks=10,
        audit_challenge_life=6, audit_verify_life=8, sudo="alice")
    nodes = [Node(spec, f"node{i}", {f"v{i}": spec.session_key(f"v{i}")})
             for i in range(3)]
    net = Network(nodes)
    node = nodes[0]
    cfg = PipelineConfig(k=K, m=M, segment_size=16 * 1024)
    pipe = StoragePipeline(cfg, podr2_key=pkey, engine=engine)

    kp = generate_rsa_keypair(1024, seed=5)
    signer_kp = generate_rsa_keypair(1024, seed=6)
    mr = b"\x02" * 32
    for n in nodes:
        n.runtime.apply_extrinsic("root", "tee_worker.update_whitelist",
                                  mr)
        n.runtime.apply_extrinsic("root", "tee_worker.pin_ias_signer",
                                  kp.public)
    cert = issue_cert(kp, "ias-signer", signer_kp.public)
    tee_bls_sk, tee_bls_pk = bls12381.keygen(b"res-tee-master")
    report, rsig = issue_report(signer_kp, mr, b"tee-pk", "tee1",
                                bls_pk=tee_bls_pk)
    node.submit_extrinsic("tee1", "tee_worker.register", "stash1", b"tp",
                          b"tee-pk", report, rsig, (cert,), tee_bls_pk,
                          bls12381.prove_possession(tee_bls_sk,
                                                    tee_bls_pk))
    for w in ("m1", "m2", "m3"):
        node.submit_extrinsic(w, "sminer.regnstk", w, b"p" + w.encode(),
                              2000 * D)
    net.run_slots(2)

    gw = OssGateway(node, "gw", pipe)
    miners = [MinerAgent(node, w, [gw], pipe, engine=engine,
                         retry=RetryPolicy(max_attempts=3,
                                           base_delay_s=0.001))
              for w in ("m1", "m2", "m3")]
    tee = TeeAgent(node, "tee1", pkey, cfg.blocks_per_fragment,
                   bls_seed=b"res-tee-master", engine=engine)
    # protocol idle accounting credits FRAGMENT_SIZE (8 MiB) per
    # filler: 43 x 3 = 129 fillers > 1 GiB, enough for buy_space(1)
    # and for each miner's 3-segment service lock (24 MiB)
    for m in miners:
        m.setup_fillers(tee, 43)
    net.run_slots(2)
    node.submit_extrinsic("alice", "storage_handler.buy_space", 1)
    node.submit_extrinsic("alice", "oss.authorize", "gw")
    net.run_slots(2)
    node.submit_extrinsic("gw", "file_bank.create_bucket", "alice",
                          "photos")
    net.run_slots(2)
    ocws = [ValidatorOcw("v0", spec.session_key("v0")),
            ValidatorOcw("v1", spec.session_key("v1"))]
    node.offchain_agents.extend([*miners, tee, *ocws])
    for n in nodes:
        n.runtime.fund("sminer_reward_pool", 10_000 * D)
    return net, node, gw, miners


def test_chaos_offchain_round_proves_through_tripped_breaker(pkey):
    """THE acceptance scenario: a miner uploads, is challenged, proves
    and is verified end-to-end while the engine's device path fails
    under a seeded plan — the breaker trips and the CPU fallback keeps
    every proof correct (audit passes for honest miners)."""
    res = ResilienceConfig(monitor=lambda: HealthMonitor(
        min_samples=2, probe_every=4))
    eng = make_engine(K, M, rs_backend="jax", podr2_key=pkey,
                      policy=AdmissionPolicy(max_delay=0.002),
                      resilience=res)
    # every device dispatch AND direct device-codec call fails, for
    # the entire run (horizon far above any ordinal this flow reaches)
    plan = FaultPlan.seeded(b"chaos-e2e", {
        "engine.dispatch": (1.0, "raise"),
        "rs.encode": (1.0, "raise"),
    }, horizon=65536)
    try:
        with faults.armed(plan):
            net, node, gw, miners = _storage_world(pkey, eng)
            data = rnd((40_000,), 12).tobytes()
            fh = gw.upload("alice", "photos", "cat.jpg", data)
            net.run_slots(1)
            assert node.runtime.file_bank.deal(fh) is not None
            net.run_slots(2)                      # miners fetch+report
            node.submit_extrinsic("root", "file_bank.calculate_end", fh)
            net.run_slots(1)
            f = node.runtime.file_bank.file(fh)
            assert f is not None and f.state == "active"
            rt = node.runtime
            for _ in range(60):
                net.run_slots(1)
                if rt.state.events_of("audit", "VerifyResult"):
                    break
            results = rt.state.events_of("audit", "VerifyResult")
            assert results, "audit round never produced verify results"
            assert all(dict(e.data)["idle"] and dict(e.data)["service"]
                       for e in results), \
                "honest miners must pass under chaos"
        # the device path really was failing, and really was bypassed:
        # the audit backend (tag/prove/verify — the round's whole
        # traffic) tripped its breaker, and the upload's encode batch
        # was served on the CPU fallback too (one sample is below the
        # codec breaker's min_samples, by design)
        assert plan.fired_log()
        assert eng.monitors["audit"].state == "open"
        snap = res.stats.snapshot()
        assert snap["fallback_batches"].get("encode", 0) >= 1
        assert sum(snap["fallback_batches"].values()) \
            + sum(snap["degraded_batches"].values()) >= 3
        m = eng.stats_metrics()
        assert m["cess_resilience_breaker_audit_trips"] >= 1.0
    finally:
        eng.close()


# -- surfaces: CLI flag + metrics exposition --------------------------------

def test_cli_resilience_flag_wires_engine():
    import argparse

    from cess_tpu.node.chain_spec import dev_spec
    from cess_tpu.node.cli import _make_cli_engine

    def ns(engine, resilience):
        return argparse.Namespace(engine=engine, resilience=resilience)

    eng = _make_cli_engine(ns("cpu", "on"), dev_spec())
    try:
        assert eng is not None and eng.resilience is not None
        assert "codec" in eng.monitors
        assert "cess_resilience_batch_requeues" in eng.stats_metrics()
    finally:
        eng.close()
    plain = _make_cli_engine(ns("cpu", "off"), dev_spec())
    try:
        assert plain.resilience is None
        assert not any(k.startswith("cess_resilience_")
                       for k in plain.stats_metrics())
    finally:
        plain.close()
    assert _make_cli_engine(ns("off", "off"), dev_spec()) is None
    with pytest.raises(SystemExit, match="resilience"):
        _make_cli_engine(ns("off", "on"), dev_spec())


def test_resilience_gauges_ride_node_metrics(pkey):
    from cess_tpu.node.chain_spec import dev_spec
    from cess_tpu.node.metrics import collect, render_metrics
    from cess_tpu.node.network import Node

    node = Node(dev_spec(), "res-node", {})
    eng = make_engine(K, M, podr2_key=pkey,
                      policy=AdmissionPolicy(max_delay=0.002),
                      resilience=ResilienceConfig())
    node.engine = eng
    try:
        eng.encode(rnd((1, K, 64), 1))
        m = collect(node)
        assert m["cess_resilience_batch_requeues"] == 0.0
        assert m["cess_resilience_breaker_codec_open"] == 0.0
        assert "cess_resilience_breaker_audit_open" in m
        assert "cess_resilience_batch_requeues" in render_metrics(node)
        # and the RPC snapshot carries the structured form
        snap = eng.stats_snapshot()
        assert snap["resilience"]["breakers"]["codec"]["state"] \
            == "closed"
    finally:
        eng.close()
