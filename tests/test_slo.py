"""SLO monitors + per-tenant accounting + adaptive control (ISSUE 6).

Pins, in order: SloTarget/parse_targets syntax, the multi-window
burn-rate state machine (observation-count deterministic), tenant
accounting bounds, the weighted-fair drain anchor, AdaptiveBatchPolicy
knob movement, the AdmissionController's shed + breaker-hold
responses, HealthMonitor hold/release semantics, the
zero-cost-when-off contract (the NOOP_SPAN analog for the SLO layer),
RPC/CLI wire-up — and THE acceptance drill: under a seeded FaultPlan
that slows device dispatch, the verify-class SLO transitions
ok -> burning, admission sheds encode-class load and CPU-degrades the
surviving codec traffic, verify p99 recovers (burning -> warn -> ok),
the whole episode is one connected trace with ``slo.*`` spans, and two
replays of the same seed produce the identical SLO state-transition
log.
"""
import numpy as np
import pytest

from cess_tpu import obs
from cess_tpu.obs.slo import (DEFAULT_TARGETS, OVERFLOW, SloBoard,
                              SloTarget, parse_targets)
from cess_tpu.ops import podr2
from cess_tpu.resilience import (FaultPlan, FaultSpec, HealthMonitor,
                                 ResilienceConfig, faults)
from cess_tpu.serve import (AdaptiveBatchPolicy, AdmissionController,
                            AdmissionPolicy, EngineShed, make_engine)

K, M = 2, 1


@pytest.fixture(autouse=True)
def _always_disarm():
    yield
    obs.disarm()
    faults.disarm()


def rnd(shape, seed=0, dtype=np.uint8):
    rng = np.random.default_rng(seed)
    return rng.integers(0, np.iinfo(dtype).max, shape, dtype=dtype)


# -- targets + syntax --------------------------------------------------------
class TestTargets:
    def test_target_validation(self):
        t = SloTarget("verify", 0.05, 0.01)
        assert t.budget == pytest.approx(0.02)
        with pytest.raises(ValueError):
            SloTarget("", 0.05)
        with pytest.raises(ValueError):
            SloTarget("verify", 0.0)
        with pytest.raises(ValueError):
            SloTarget("verify", 0.05, 1.0)

    def test_parse_targets_syntax(self):
        got = parse_targets("verify:p99=50ms,err=1%;encode:p99=2s")
        assert got == (SloTarget("verify", 0.05, 0.01),
                       SloTarget("encode", 2.0, 0.0))
        # bare numbers: seconds / fractions
        assert parse_targets("prove:p99=0.1,err=0.02") == \
            (SloTarget("prove", 0.1, 0.02),)
        assert parse_targets("") == DEFAULT_TARGETS
        for bad in ("verify", "verify:err=1%", "verify:p99=50ms,x=1",
                    "verify:p99"):
            with pytest.raises(ValueError):
                parse_targets(bad)

    def test_duplicate_target_class_rejected(self):
        with pytest.raises(ValueError):
            SloBoard((SloTarget("verify", 0.05),
                      SloTarget("verify", 0.10)))


# -- the burn-rate state machine ---------------------------------------------
def small_board(**kw):
    kw.setdefault("fast_window", 4)
    kw.setdefault("slow_window", 16)
    kw.setdefault("eval_every", 4)
    return SloBoard((SloTarget("verify", 0.02, 0.01),), **kw)


class TestBurnRate:
    def test_ok_to_burning_to_ok_on_observation_count(self):
        board = small_board()
        # 8 breaching observations: burning fires at the obs-4 eval
        for _ in range(8):
            board.observe("verify", 1.0)
        assert board.state("verify") == "burning"
        # recovery: fast window clears first (warn), then the slow
        # window flushes (ok) — everything at eval boundaries
        for _ in range(24):
            board.observe("verify", 0.001)
        assert board.state("verify") == "ok"
        log = board.transition_log()
        assert [(c, a, b) for c, a, b, _ in log] == [
            ("verify", "ok", "burning"),
            ("verify", "burning", "warn"),
            ("verify", "warn", "ok")]
        # transitions land on eval_every boundaries: count-determinism
        assert all(n % 4 == 0 for _, _, _, n in log)

    def test_failures_breach_like_slow_requests(self):
        board = small_board()
        for _ in range(8):
            board.observe("verify", 0.001, ok=False)   # fast but failed
        assert board.state("verify") == "burning"

    def test_no_eval_before_fast_window_fills(self):
        board = small_board()
        for _ in range(3):
            board.observe("verify", 1.0)
        assert board.state("verify") == "ok"        # len(slow) < fast

    def test_untargeted_class_is_accounted_not_evaluated(self):
        board = small_board()
        for _ in range(16):
            board.observe("encode", 99.0, tenant="t")
        assert board.state("encode") == "ok"
        assert board.transition_log() == ()
        assert board.snapshot()["tenants"]["t"]["encode"]["requests"] \
            == 16

    def test_transition_spans_ride_the_armed_tracer(self):
        board = small_board()
        tracer = obs.Tracer()
        with obs.armed(tracer):
            for _ in range(8):
                board.observe("verify", 1.0)
        spans = [s for s in tracer.finished()
                 if s["name"] == "slo.transition"]
        assert len(spans) == 1 and spans[0]["sys"] == "slo"
        assert spans[0]["attrs"]["frm"] == "ok"
        assert spans[0]["attrs"]["to"] == "burning"

    def test_listener_fires_outside_the_lock(self):
        board = small_board()
        seen = []
        board.add_listener(
            lambda cls, old, new: seen.append((cls, old, new)))
        for _ in range(8):
            board.observe("verify", 1.0)
        assert seen == [("verify", "ok", "burning")]

    def test_announcements_deliver_in_log_order_under_concurrency(self):
        # two observer threads flap the state; whatever interleaving
        # the scheduler picks, listeners must see transitions in
        # EXACTLY transition-log order — a descheduled observer
        # delivering its older transition late would leave the
        # admission controller engaged against a board that reads ok
        # (review-caught; the announce queue pins FIFO delivery)
        import threading

        board = SloBoard((SloTarget("verify", 0.01),), fast_window=4,
                         slow_window=8, eval_every=2,
                         max_transitions=65536)
        seen = []
        board.add_listener(
            lambda cls, old, new: seen.append((cls, old, new)))

        def feed(latency):
            for _ in range(400):
                board.observe("verify", latency)

        threads = [threading.Thread(target=feed, args=(lat,))
                   for lat in (1.0, 0.0, 1.0)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert seen == [(c, a, b)
                        for c, a, b, _ in board.transition_log()]
        assert len(seen) >= 1


class TestTenantAccounting:
    def test_counters_shed_and_overflow_cap(self):
        board = small_board(max_tenants=3)
        board.observe("encode", 0.001, tenant="a", rows=4)
        board.observe("encode", 0.001, ok=False, tenant="a")
        board.note_shed("encode", "a")
        board.observe("encode", 0.001)                  # untagged
        for t in ("b", "c", "d", "e"):                  # cap is 3
            board.observe("encode", 0.001, tenant=t)
        snap = board.snapshot()["tenants"]
        assert snap["a"]["encode"] == {"requests": 2, "failed": 1,
                                       "shed": 1, "rows": 4}
        assert snap["-"]["encode"]["requests"] == 1     # untagged bucket
        # a, -, b admitted; c/d/e aggregate under the overflow bucket
        assert set(snap) == {"a", "-", "b", OVERFLOW}
        assert snap[OVERFLOW]["encode"]["requests"] == 3

    def test_series_families_and_enum_state(self):
        board = small_board()
        board.observe("verify", 0.001, tenant="t")
        fams = {}
        for family, kind, labels, value in board.series():
            fams.setdefault(family, []).append((kind, labels, value))
        states = {l["state"]: v
                  for k, l, v in fams["cess_slo_state"]}
        assert states == {"ok": 1.0, "warn": 0.0, "burning": 0.0}
        assert all(k == "counter"
                   for k, _, _ in fams["cess_tenant_requests_total"])
        assert ("cess_tenant_latency_seconds", {"tenant": "t",
                                                "class": "verify"}) \
            == board.tenant_histograms()[0][:2]


# -- weighted-fair drain -----------------------------------------------------
class TestFairDrain:
    def test_anchor_prefers_the_deficit_tenant(self):
        board = SloBoard((SloTarget("verify", 0.02),))
        eng = make_engine(K, M,
                          policy=AdmissionPolicy(max_delay=30.0,
                                                 max_batch_requests=64,
                                                 max_batch_rows=4096),
                          slo=board)
        try:
            # nothing triggers a drain (huge delay, small queue), so
            # the queue is inspectable; "heavy" has served 10k rows,
            # "light" none — light's request anchors the next batch
            # even though heavy queued first
            for i in range(4):
                eng.submit_encode(rnd((2, K, 64), i), timeout=60,
                                  tenant="heavy")
            eng.submit_encode(rnd((4, K, 64), 9), timeout=60,
                              tenant="light")
            with eng._cond:
                eng._tenant_rows["encode"] = {"heavy": 10_000,
                                              "light": 0}
                q = eng._queues["encode"]
                assert eng._anchor_index("encode", q) == 4
                batch = eng._drain("encode")
            # the anchor leads the batch; same-key mates still coalesce
            assert batch[0].tenant == "light"
            assert {r.tenant for r in batch} == {"heavy", "light"}
            # resolve the popped requests so close() has nothing to kill
            for r in batch:
                r.future._resolve(None)
                r.span.finish()
        finally:
            eng.close(timeout=0.1)

    def test_over_cap_tenant_reads_the_overflow_deficit(self):
        # a tenant past the board's max_tenants cap is CHARGED to
        # "~other" (_account_batch), so the anchor choice must READ
        # its deficit from "~other" too — otherwise its raw name
        # always looks at 0 served rows and it anchors every drain
        # forever (review-caught)
        board = SloBoard((SloTarget("verify", 0.02),))
        eng = make_engine(K, M,
                          policy=AdmissionPolicy(max_delay=30.0,
                                                 max_batch_requests=64,
                                                 max_batch_rows=4096),
                          slo=board)
        try:
            eng.submit_encode(rnd((2, K, 64), 0), timeout=60,
                              tenant="newcomer")   # over-cap: aliases
            eng.submit_encode(rnd((2, K, 64), 1), timeout=60,
                              tenant="t00")        # in-cap, light
            with eng._cond:
                served = {f"t{i:02d}": 10
                          for i in range(eng.slo.max_tenants)}
                served["~other"] = 10_000          # bucket heavily fed
                eng._tenant_rows["encode"] = served
                q = eng._queues["encode"]
                assert eng._anchor_index("encode", q) == 1
                batch = eng._drain("encode")
            assert batch[0].tenant == "t00"
            for r in batch:
                r.future._resolve(None)
                r.span.finish()
        finally:
            eng.close(timeout=0.1)

    def test_without_a_board_the_oldest_anchors(self):
        eng = make_engine(K, M, policy=AdmissionPolicy(max_delay=30.0))
        try:
            eng.submit_encode(rnd((2, K, 64), 0), timeout=60,
                              tenant="b")
            eng.submit_encode(rnd((2, K, 64), 1), timeout=60,
                              tenant="a")
            with eng._cond:
                assert eng._anchor_index("encode",
                                         eng._queues["encode"]) == 0
                batch = eng._drain("encode")
            assert batch[0].tenant == "b"
            for r in batch:
                r.future._resolve(None)
                r.span.finish()
        finally:
            eng.close(timeout=0.1)


# -- adaptive batching knobs -------------------------------------------------
class TestAdaptiveBatchPolicy:
    def test_over_target_shrinks_under_target_grows(self):
        pol = AdmissionPolicy(max_delay=0.01, max_batch_rows=512)
        ad = AdaptiveBatchPolicy(pol, targets={"verify": 0.02},
                                 update_every=4, window=8,
                                 min_delay_s=0.001, min_rows=8)
        assert ad.knobs("verify") == (0.01, pol.max_batch_requests, 512)
        for _ in range(4):
            ad.note("verify", 0.05)               # p99 over target
        delay, _, rows = ad.knobs("verify")
        assert delay == pytest.approx(0.005) and rows == 256
        assert ad.p99_est("verify") == pytest.approx(0.05)
        # fast + under-occupied observations: one more shrink while
        # the slow samples are still in the window (obs-8 eval), then
        # growth once they roll out (obs-12/16 evals)
        for _ in range(12):
            ad.note("verify", 0.001, occupancy=1)
        delay2, _, rows2 = ad.knobs("verify")
        assert delay2 > 0.0025 and rows2 == 512
        log = ad.adjustment_log()
        assert [e[0] for e in log] == ["verify"] * len(log)
        assert len(log) == ad.snapshot()["verify"]["adjustments"] >= 3
        # the log records both directions
        deltas = [e[3] for e in log]
        assert min(deltas) == pytest.approx(0.0025)
        assert deltas[-1] > min(deltas)

    def test_good_occupancy_blocks_growth(self):
        ad = AdaptiveBatchPolicy(AdmissionPolicy(max_delay=0.01),
                                 targets={"encode": 1.0},
                                 update_every=4, occupancy_target=4.0)
        for _ in range(8):
            ad.note("encode", 0.001, occupancy=16)  # well-batched
        assert ad.knobs("encode")[0] == 0.01        # no change

    def test_untargeted_class_keeps_static_knobs(self):
        pol = AdmissionPolicy(max_delay=0.01)
        ad = AdaptiveBatchPolicy(pol, targets={"verify": 0.02})
        for _ in range(64):
            ad.note("encode", 123.0)
        assert ad.knobs("encode") == (pol.max_delay,
                                      pol.max_batch_requests,
                                      pol.max_batch_rows)

    def test_static_window_alone_exceeds_a_tight_target(self):
        """Why a static policy cannot protect verify: with
        encode-friendly constants a
        lone verify waits out the whole coalescing window, and that
        window alone exceeds a 100 ms verify target — arithmetic over
        AdmissionPolicy, no timing. From the SAME constants the
        adaptive policy brings verify's window under the target after
        one evaluation of over-target observations, and encode keeps
        its coalescing. (That the adaptive engine's measured p99 then
        beats the static one's is a wall-clock claim no test holds.)"""
        target = 0.100
        pol = AdmissionPolicy(max_delay=0.25, queue_cap=4096,
                              max_batch_requests=64)
        assert pol.max_delay > target
        board = SloBoard((SloTarget("verify", target),))
        ad = AdaptiveBatchPolicy(pol, board=board, update_every=4,
                                 window=64, shrink=0.35,
                                 occupancy_target=1.0)
        for _ in range(4):      # a lone verify: window + its own work
            ad.note("verify", pol.max_delay + 0.05, occupancy=1)
            ad.note("encode", 0.001, occupancy=4)
        assert ad.knobs("verify")[0] == pytest.approx(0.25 * 0.35)
        assert ad.knobs("verify")[0] < target
        assert ad.knobs("encode")[0] == pol.max_delay

    def test_board_supplies_targets(self):
        board = SloBoard((SloTarget("verify", 0.07),))
        ad = AdaptiveBatchPolicy(board=board)
        assert ad.target_for("verify") == 0.07
        assert ad.target_for("encode") is None
        assert AdaptiveBatchPolicy(
            board=board, targets={"verify": 0.5}).target_for("verify") \
            == 0.5


# -- admission controller + breaker hold -------------------------------------
class TestHoldOpen:
    def test_held_breaker_admits_nothing_and_releases_clean(self):
        mon = HealthMonitor()
        assert mon.allow()
        mon.hold_open("slo:verify")
        assert mon.state == "held"
        assert not any(mon.allow() for _ in range(32))  # NO probes
        snap = mon.snapshot()
        assert snap["held_reason"] == "slo:verify"
        assert snap["holds"] == 1 and snap["trips"] == 0
        mon.release()
        assert mon.state == "closed" and mon.allow()

    def test_hold_never_masks_a_real_trip(self):
        mon = HealthMonitor(min_samples=2, probe_every=2)
        for _ in range(4):
            mon.record_error()                      # window-tripped
        assert mon.state == "open"
        mon.hold_open("slo:verify")
        assert mon.state == "held"
        mon.release()
        assert mon.state == "open"                  # the trip remains

    def test_exposition_reports_held_as_open(self):
        from cess_tpu.resilience.stats import ResilienceStats

        rs = ResilienceStats()
        mon = HealthMonitor()
        rs.register_monitor("codec", mon)
        mon.hold_open("slo:verify")
        m = rs.metrics()
        assert m["cess_resilience_breaker_codec_open"] == 1.0
        assert m["cess_resilience_breaker_codec_held"] == 1.0


class TestAdmissionController:
    def test_burning_sheds_and_holds_until_ok(self):
        board = small_board()
        ad = AdaptiveBatchPolicy(board=board)
        ctrl = AdmissionController(board, ad)

        class EngineLike:
            monitors = {"codec": HealthMonitor()}

        eng = EngineLike()
        ctrl.bind(eng)
        assert ctrl.admit("encode", 30.0) is None
        assert ctrl.admit("verify", 30.0) is None
        for _ in range(8):
            board.observe("verify", 1.0)            # -> burning
        assert ctrl.engaged
        assert eng.monitors["codec"].state == "held"
        assert ctrl.admit("encode", 30.0) == "slo-burning"
        assert ctrl.admit("verify", 30.0) is None   # protected: never
        for _ in range(8):
            board.observe("verify", 0.001)          # -> warn: still on
        assert board.state("verify") == "warn"
        assert ctrl.engaged
        for _ in range(16):
            board.observe("verify", 0.001)          # -> ok: released
        assert board.state("verify") == "ok"
        assert not ctrl.engaged
        assert eng.monitors["codec"].state == "closed"
        assert ctrl.admit("encode", 30.0) is None
        snap = ctrl.snapshot()
        assert snap["holds"] == snap["releases"] == 1
        assert snap["sheds"]["encode"]["slo-burning"] == 1
        # sheds were charged to tenant accounting
        assert board.snapshot()["tenants"]["-"]["encode"]["shed"] == 1

    def test_deadline_unmeetable_shed(self):
        board = small_board()
        ad = AdaptiveBatchPolicy(board=board, targets={"encode": 0.01},
                                 update_every=4)
        ctrl = AdmissionController(board, ad)
        for _ in range(4):
            ad.note("encode", 5.0)                  # p99 est ~5 s
        assert ctrl.admit("encode", 1.0) == "deadline-unmeetable"
        assert ctrl.admit("encode", 10.0) is None   # budget fits
        assert ctrl.admit("encode", None) is None   # no deadline
        # an IDLE class always admits: the estimate is refreshed by
        # served requests alone, so shedding with no backlog would
        # wedge a stale spike estimate forever (review-caught)
        assert ctrl.admit("encode", 1.0, queued=0) is None
        assert ctrl.admit("encode", 1.0, queued=3) == \
            "deadline-unmeetable"

    def test_engine_submit_raises_engine_shed(self):
        board = small_board()
        eng = make_engine(K, M,
                          policy=AdmissionPolicy(max_delay=0.002),
                          slo=board, adaptive=True)
        try:
            for _ in range(8):
                board.observe("verify", 1.0)        # -> burning
            with pytest.raises(EngineShed, match="slo-burning"):
                eng.encode(rnd((2, K, 64), 3), timeout=5,
                           tenant="bulk")
            snap = eng.stats_snapshot()
            assert snap["classes"]["encode"]["shed"] == 1
            assert snap["slo"]["tenants"]["bulk"]["encode"]["shed"] == 1
            assert "slo" in snap and "adaptive" in snap
            # recovery re-admits, and a served class materializes its
            # adaptive gauges on the exposition
            for _ in range(24):
                board.observe("verify", 0.001)
            assert board.state("verify") == "ok"
            eng.encode(rnd((1, K, 64), 4), timeout=30)
            assert "cess_adaptive_encode_delay_s" in eng.stats_metrics()
        finally:
            eng.close()


# -- the zero-cost-when-off contract -----------------------------------------
def test_disabled_engine_allocates_no_slo_or_tenant_objects():
    """The NOOP_SPAN analog for the SLO layer (acceptance pin): with
    no board configured, the control attributes ARE the None
    singleton, requests carry the bare None tenant default, and after
    real traffic no SLO/tenant/adaptive structure exists anywhere on
    the engine or its exposition."""
    eng = make_engine(K, M, policy=AdmissionPolicy(max_delay=0.002))
    try:
        assert eng.slo is None and eng.adaptive is None \
            and eng.admission is None
        assert eng.stats.slo is None and eng.stats.adaptive is None
        fut = eng.submit_encode(rnd((2, K, 64), 1), timeout=30)
        fut.result(30)
        eng.encode(rnd((2, K, 64), 2), timeout=30)
        # the fair-queue deficit map never materializes a tenant entry
        assert eng._tenant_rows == {}
        snap = eng.stats_snapshot()
        assert "slo" not in snap and "adaptive" not in snap
        assert not any(k.startswith(("cess_slo_", "cess_tenant_",
                                     "cess_adaptive_"))
                       for k in eng.stats_metrics())
        assert eng.labeled_series() == []
        assert eng.labeled_histograms() == []
    finally:
        eng.close()


# -- wire-up: RPC + CLI ------------------------------------------------------
def test_rpc_slo_status():
    from cess_tpu.node.chain_spec import dev_spec
    from cess_tpu.node.network import Node
    from cess_tpu.node.rpc import RpcServer

    node = Node(dev_spec(), "slo-node", {})
    rpc = RpcServer(node, port=0)
    assert rpc.handle("cess_sloStatus", []) is None      # no engine
    board = SloBoard((SloTarget("verify", 0.05),))
    eng = make_engine(K, M, policy=AdmissionPolicy(max_delay=0.002),
                      slo=board, adaptive=True)
    node.engine = eng
    try:
        eng.encode(rnd((1, K, 64), 1), timeout=30, tenant="alice")
        out = rpc.handle("cess_sloStatus", [])
        assert out["targets"]["verify"]["state"] == "ok"
        assert out["tenants"]["alice"]["encode"]["requests"] == 1
        assert "adaptive" in out and "admission" in out
        assert out["admission"]["engaged"] is False
    finally:
        eng.close()


def test_cli_slo_flags_wire_engine():
    import argparse

    from cess_tpu.node.chain_spec import dev_spec
    from cess_tpu.node.cli import _make_cli_engine

    def ns(engine, slo=None, adaptive=False):
        return argparse.Namespace(engine=engine, resilience="off",
                                  slo=slo, adaptive=adaptive)

    eng = _make_cli_engine(ns("cpu", slo="verify:p99=40ms",
                              adaptive=True), dev_spec())
    try:
        assert eng.slo is not None and eng.adaptive is not None \
            and eng.admission is not None
        assert eng.slo.targets == (SloTarget("verify", 0.04),)
        assert eng.adaptive.target_for("verify") == 0.04
    finally:
        eng.close()
    eng = _make_cli_engine(ns("cpu", slo=""), dev_spec())  # defaults
    try:
        assert eng.slo.targets == DEFAULT_TARGETS
        assert eng.adaptive is None and eng.admission is None
    finally:
        eng.close()
    plain = _make_cli_engine(ns("cpu"), dev_spec())
    try:
        assert plain.slo is None
    finally:
        plain.close()
    with pytest.raises(SystemExit, match="slo"):
        _make_cli_engine(ns("off", slo=""), dev_spec())
    with pytest.raises(SystemExit, match="adaptive"):
        _make_cli_engine(ns("off", adaptive=True), dev_spec())
    # --adaptive without --slo would build a tuner with no targets to
    # steer toward (silently never adjusting) — refused loudly instead
    with pytest.raises(SystemExit, match="--adaptive requires --slo"):
        _make_cli_engine(ns("cpu", adaptive=True), dev_spec())


# -- THE acceptance: the SLO drill -------------------------------------------
OBJECTIVE_S = 0.30      # verify p99 objective: ~6x the CPU-jax
                        # verify dispatch floor (~50 ms) — phase-2
                        # classification must stay noise-immune even
                        # on a fully loaded box (one phase-2 breach
                        # poisons the 16-obs slow window and stalls
                        # the warn->ok walk, or re-fires burning)
FAULT_DELAY_S = 0.70    # injected dispatch slowness: ~2.3x objective


def _run_drill(seed: bytes):
    """One full drill episode; returns (board, engine stats snapshot,
    shed count, phase-2 verify latencies, spans)."""
    import time

    pkey = podr2.Podr2Key.generate(44)
    params = podr2.Podr2Params()
    blocks = params.blocks_for(512)
    ids = np.stack([np.arange(2, dtype=np.uint32),
                    np.zeros(2, dtype=np.uint32)], axis=1)
    idx, nu = podr2.gen_challenge(b"slo-drill", blocks)
    mu = np.zeros((2, params.sectors), dtype=np.uint32)
    sigma = np.zeros((2, podr2.LIMBS), dtype=np.uint32)

    board = SloBoard((SloTarget("verify", OBJECTIVE_S, 0.01),),
                     fast_window=4, slow_window=16, eval_every=4)
    adaptive = AdaptiveBatchPolicy(board=board)
    admission = AdmissionController(board, adaptive,
                                    protect=("verify",),
                                    shed=("encode",))
    tracer = obs.Tracer(capacity=65536)
    eng = make_engine(K, M, rs_backend="jax", podr2_key=pkey,
                      policy=AdmissionPolicy(max_delay=0.002),
                      resilience=ResilienceConfig(),
                      tracer=tracer, slo=board, adaptive=adaptive,
                      admission=admission)
    plan = FaultPlan.seeded(seed, {
        "engine.dispatch": (1.0, FaultSpec("delay",
                                           delay_s=FAULT_DELAY_S)),
    }, horizon=64)
    bulk = rnd((1, K, 512), 7)
    sheds = 0
    lats2 = []
    try:
        with obs.armed(tracer):
            # -- phase 1: every device dispatch is slow ---------------
            with faults.armed(plan):
                for i in range(8):
                    try:
                        eng.encode(bulk, timeout=30, tenant="bulk")
                    except EngineShed:
                        sheds += 1
                    eng.verify_batch(ids, blocks, idx, nu, mu, sigma,
                                     timeout=30, tenant="auditor")
                # the verify SLO is burning; encode is being shed and
                # the codec breaker is HELD: surviving codec traffic
                # (a repair claim) serves CPU-degraded, correct, fast
                assert board.state("verify") == "burning"
                assert eng.monitors["codec"].state == "held"
                shards = np.asarray(eng._fallback_codec.encode(bulk))
                rec = eng.reconstruct(shards[:, (0, 1)], (0, 1), (2,),
                                      timeout=30, tenant="repairer")
                assert np.array_equal(np.asarray(rec),
                                      shards[:, (2,)])
            # -- phase 2: the device is healthy again -----------------
            for i in range(20):
                try:
                    eng.encode(bulk, timeout=30, tenant="bulk")
                except EngineShed:
                    sheds += 1
                t0 = time.perf_counter()
                eng.verify_batch(ids, blocks, idx, nu, mu, sigma,
                                 timeout=30, tenant="auditor")
                lats2.append(time.perf_counter() - t0)
        snap = eng.stats_snapshot()
    finally:
        eng.close()
    return board, snap, sheds, lats2, tracer.finished()


def test_slo_drill_end_to_end_and_replay_deterministic():
    board1, snap1, sheds1, lats2, spans = _run_drill(b"slo-drill-seed")

    # the episode: ok -> burning (dispatch slowness), admission
    # response, then recovery through warn back to ok
    log1 = board1.transition_log()
    assert [(c, a, b) for c, a, b, _ in log1] == [
        ("verify", "ok", "burning"),
        ("verify", "burning", "warn"),
        ("verify", "warn", "ok")]
    assert board1.state("verify") == "ok"

    # encode-class load was shed while the SLO was at risk, and
    # admitted again after recovery (the last loop-2 encodes ran)
    assert sheds1 >= 4
    assert snap1["classes"]["encode"]["shed"] == sheds1
    assert snap1["slo"]["tenants"]["bulk"]["encode"]["shed"] == sheds1
    assert snap1["classes"]["encode"]["completed"] >= 1
    # the held breaker CPU-degraded the surviving codec traffic
    assert snap1["resilience"]["breakers"]["codec"]["holds"] == 1
    assert snap1["resilience"]["breakers"]["codec"]["state"] == "closed"
    degraded = snap1["resilience"]["degraded_batches"]
    assert degraded.get("repair", 0) >= 1
    # verify p99 recovered: the phase-2 tail sits under the objective
    tail = sorted(lats2)
    assert tail[int(0.99 * len(tail))] < OBJECTIVE_S

    # one connected trace with slo.* spans: single trace id, no
    # orphaned parents, the transition spans in episode order, and
    # the degraded repair visible on its device span
    assert {s["trace_id"] for s in spans} == {1}
    span_ids = {s["span_id"] for s in spans}
    assert [s for s in spans
            if s["parent_id"] and not s["remote_parent"]
            and s["parent_id"] not in span_ids] == []
    transitions = [(s["attrs"]["frm"], s["attrs"]["to"])
                   for s in spans if s["name"] == "slo.transition"]
    assert transitions == [("ok", "burning"), ("burning", "warn"),
                           ("warn", "ok")]
    systems = {s["sys"] for s in spans}
    assert {"engine", "device", "slo"} <= systems
    assert any(s["name"] == "device.repair"
               and s["attrs"].get("degraded") for s in spans)
    assert any(s["attrs"].get("tenant") == "auditor" for s in spans)

    # determinism: replaying the same seed reproduces the identical
    # SLO state-transition log, observation count for observation
    # count (the fired_log analog of resilience/faults.py)
    board2, snap2, sheds2, _, _ = _run_drill(b"slo-drill-seed")
    assert board2.transition_log() == log1
    assert sheds2 == sheds1
