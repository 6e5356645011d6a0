"""Continuous-profiling plane (cess_tpu/obs/profile.py) — ISSUE 13:

- THE acceptance drill: a seeded FaultPlan delays ``engine.dispatch``,
  live throughput falls below the bench-anchored guard, the
  PerfWatchdog walks ok -> regressed edge-triggered, a
  ``perf-regression`` incident bundle snapshots with BOTH ledgers
  embedded, and a same-seed replay reproduces the plane's
  ``witness()`` byte-for-byte;
- PadLedger's top-ranked class x bucket entry on a crafted ragged
  workload matches a hand-computed padded-row count, and the stream
  driver's ragged-tail pads ride the SAME ledger as the engine's
  bucket pads (the unified end-to-end pad bill);
- zero-cost-when-off: a disarmed engine holds no profile plane, the
  program cache times nothing, and no ``cess_profile_*`` key reaches
  GET /metrics;
- ``load_baseline`` reads the baseline artifact (fixture under
  tests/data/) and refuses anything else, and an unanchored plane has
  no watchdog;
- wire-up: the ``cess_profileDump`` RPC, the ``node.cli --profile``
  flag (requires ``--engine``), and ``Scenario.profile=True`` riding
  ``SimReport``.
"""
import dataclasses
import json
import os

import numpy as np
import pytest

from cess_tpu.models.pipeline import PipelineConfig, StoragePipeline
from cess_tpu.obs import flight, profile
from cess_tpu.obs.incident import IncidentReporter
from cess_tpu.resilience import faults
from cess_tpu.serve import make_engine
from cess_tpu.serve.stream import StreamingIngest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(REPO, "tests", "data")
BASELINE_FIXTURE = os.path.join(DATA, "bench_baseline_r05.json")
ENCODE_METRIC = "rs_4p8_encode_GiBps_per_chip"

K, M = 2, 1
SEG = K * 512


def rnd(shape, seed=0, dtype=np.uint8):
    rng = np.random.default_rng(seed)
    return rng.integers(0, np.iinfo(dtype).max, shape, dtype=dtype)


def make_pipe():
    return StoragePipeline(PipelineConfig(k=K, m=M, segment_size=SEG))


# -- baseline loading --------------------------------------------------------
class TestBaselineLoaders:
    def test_fixture_anchors_the_default_tracked_metric(self):
        base = profile.load_baseline(BASELINE_FIXTURE)
        assert base[ENCODE_METRIC] > 0
        assert profile.TRACKED_DEFAULT["encode"] == ENCODE_METRIC

    def test_load_baseline_rejects_non_artifact(self, tmp_path):
        p = tmp_path / "not_an_artifact.json"
        p.write_text(json.dumps({"metric": "x", "value": 1.0}))
        with pytest.raises(ValueError):
            profile.load_baseline(str(p))


# -- OpProfiler --------------------------------------------------------------
class TestOpProfiler:
    def test_accounts_accumulate_per_class_bucket_device(self):
        ops = profile.OpProfiler(window=4)
        assert ops.observe("encode", 4, 0, rows=3, padded=1, requests=2,
                           nbytes=100, queue_s=0.5, dispatch_s=0.25,
                           sync_s=0.05) == 1
        assert ops.observe("encode", 4, 0, rows=4, padded=0, requests=1,
                           nbytes=50, dispatch_s=0.25) == 2
        ops.observe("encode", 8, 1, rows=8, padded=0, requests=1)
        snap = ops.snapshot()
        assert snap["observations"] == 3
        a = {(e["cls"], e["bucket"], e["device"]): e
             for e in snap["accounts"]}
        e40 = a[("encode", 4, 0)]
        assert (e40["batches"], e40["requests"], e40["rows"],
                e40["padded_rows"], e40["bytes"]) == (2, 3, 7, 1, 150)
        assert e40["queue_s"] == 0.5 and e40["dispatch_s"] == 0.5
        assert ("encode", 8, 1) in a

    def test_windowed_gauge_and_timing_free_canon(self):
        ops = profile.OpProfiler(window=2)
        ops.observe("encode", 1, 0, rows=1, nbytes=1 << 30,
                    dispatch_s=0.0)
        assert ops.windowed_gibps() == {"encode": None}  # no busy time
        ops.observe("encode", 1, 0, rows=1, nbytes=1 << 30,
                    dispatch_s=0.5)
        assert ops.windowed_gibps() == {"encode": 4.0}   # 2 GiB / 0.5 s
        canon = ops.canon()
        assert canon["observations"] == 2
        acct = canon["accounts"]["encode|1|d0"]
        assert acct == {"batches": 2, "requests": 0, "rows": 2,
                        "padded_rows": 0, "bytes": 2 << 30}
        assert not any(k.endswith("_s") for k in acct)

    def test_window_validation(self):
        with pytest.raises(ValueError):
            profile.OpProfiler(window=0)


# -- PadLedger ---------------------------------------------------------------
class TestPadLedger:
    def test_top_ranked_entry_matches_hand_computed_pad_count(self):
        """THE acceptance (pad half): a crafted ragged workload — 10
        segments staged in batches of 4 — pads exactly 2 rows (the
        4+4+2 tail), and that is the ledger's top-ranked entry."""
        plane = profile.ProfilePlane()
        eng = make_engine(K, M, profile=plane)
        try:
            StreamingIngest(make_pipe(), 4, engine=eng).ingest(
                rnd((10, SEG), 5))
            # engine side pads less: one 3-row encode -> bucket 4, 1 pad
            eng.encode(rnd((3, K, 64), 6), timeout=30)
        finally:
            eng.close()
        cls, bucket, acct = plane.pads.ranked()[0]
        assert (cls, bucket) == ("stream", 4)
        assert acct == {"batches": 3, "served": 10, "padded": 2,
                        "sources": {"stream": 2}}

    def test_stream_and_engine_pads_unify_on_identical_workload(self):
        """Satellite: the SAME 7-row ragged workload through both
        paths — stream staging (batches 4+3, tail pads 1) and engine
        bucket coalescing (4-row and 3-row submits, the 3-row pads 1
        up to bucket 4) — lands in ONE ledger with an identical
        per-source pad bill."""
        plane = profile.ProfilePlane()
        eng = make_engine(K, M, profile=plane)
        try:
            StreamingIngest(make_pipe(), 4, engine=eng).ingest(
                rnd((7, SEG), 8))
            eng.encode(rnd((4, K, 64), 9), timeout=30)
            eng.encode(rnd((3, K, 64), 10), timeout=30)
        finally:
            eng.close()
        total = plane.pads.total()
        assert total["sources"] == {"engine": 1, "stream": 1}
        by_key = {(c, b): a for c, b, a in plane.pads.ranked()}
        stream, engine = by_key[("stream", 4)], by_key[("encode", 4)]
        assert stream["served"] == engine["served"] == 7
        assert stream["padded"] == engine["padded"] == 1

    def test_ranking_is_deterministic_worst_first(self):
        led = profile.PadLedger()
        led.add("a", 8, served=6, padded=2)
        led.add("b", 4, served=1, padded=3, source="stream")
        led.add("a", 4, served=1, padded=3)
        ranked = led.ranked()
        assert [(c, b) for c, b, _ in ranked] \
            == [("a", 4), ("b", 4), ("a", 8)]      # ties break on key
        assert led.total() == {"served": 8, "padded": 8,
                               "sources": {"engine": 5, "stream": 3}}
        assert led.canon()["b|4"]["sources"] == {"stream": 3}


# -- CompileLedger -----------------------------------------------------------
class TestCompileLedger:
    def test_keys_canonicalize_and_events_are_bounded(self):
        led = profile.CompileLedger(max_events=2)
        key = ("encode", 4, (K, 64), b"\x01")
        led.record(key, 0.25)
        led.record(key, 0.5)
        led.record(("encode", 8), 0.125)
        ks = "(encode,4,(2,64),01)"
        snap = led.snapshot()
        assert snap["builds"] == 3
        assert snap["programs"][ks] == {"builds": 2, "wall_s": 0.75}
        assert [e[0] for e in snap["events"]] == [2, 3]  # bounded deque
        canon = led.canon()
        assert canon == {"builds": 3,
                         "programs": {ks: 2, "(encode,8)": 1}}

    def test_program_cache_feeds_the_ledger_on_miss_only(self):
        plane = profile.ProfilePlane()
        eng = make_engine(K, M, profile=plane)
        try:
            data = rnd((1, K, 64), 4)
            eng.encode(data, timeout=30)
            builds = plane.compiles.canon()["builds"]
            assert builds >= 1
            eng.encode(data, timeout=30)     # same bucket: cache HIT
            assert plane.compiles.canon()["builds"] == builds
            eng.encode(rnd((3, K, 64), 4), timeout=30)  # new bucket
            assert plane.compiles.canon()["builds"] == builds + 1
        finally:
            eng.close()


# -- PerfWatchdog ------------------------------------------------------------
class TestPerfWatchdog:
    def test_parameter_validation(self):
        for kw in ({"guard": 0.0}, {"guard": 1.5}, {"window": 0},
                   {"max_transitions": 0}):
            with pytest.raises(ValueError):
                profile.PerfWatchdog({"m": 1.0}, **kw)

    def test_unanchored_metric_is_ignored(self):
        wd = profile.PerfWatchdog({"m": 1.0}, window=1)
        wd.observe("other", 1 << 30, 10.0)
        assert wd.canon() == {"observations": 0, "windows": {},
                              "transitions": []}

    def test_zero_busy_window_is_fast_not_regressed(self):
        wd = profile.PerfWatchdog({"m": 100.0}, window=2)
        for _ in range(2):
            wd.observe("m", 1 << 20, 0.0)
        assert wd.state("m") == "ok" and not wd.regressed()
        assert wd.canon()["windows"] == {"m": 1}
        assert wd.transition_log() == ()

    def test_edge_triggered_both_ways_with_announcements(self):
        # guard 0.5 x 10 GiB/s baseline -> the window threshold is 5
        wd = profile.PerfWatchdog({"m": 10.0}, guard=0.5, window=2)
        got = []
        wd.add_listener(lambda *a: got.append(a))
        rec = flight.FlightRecorder(b"wd")
        with flight.armed(rec):
            for _ in range(4):              # two windows at 1 GiB/s
                wd.observe("m", 1 << 29, 0.5)
            assert wd.state("m") == "regressed" and wd.regressed()
            for _ in range(2):              # one window at 16 GiB/s
                wd.observe("m", 1 << 32, 0.25)
        assert wd.state("m") == "ok"
        # one transition per EDGE: two regressed windows collapse to
        # one ok->regressed, then the recovery edge
        assert wd.transition_log() == (
            (2, "m", "ok", "regressed", 1),
            (6, "m", "regressed", "ok", 3))
        assert got == [("m", "ok", "regressed", 1),
                       ("m", "regressed", "ok", 3)]
        notes = rec.journal_tail("perf")
        assert [n["kind"] for n in notes] == ["regression"] * 2
        snap = wd.snapshot()
        assert snap["regressions"] == 1     # only the bad edge counts
        assert snap["last_GiBps"]["m"] == 16.0
        assert snap["states"] == {"m": "ok"}

    def test_canon_excludes_measured_values(self):
        wd = profile.PerfWatchdog({"m": 10.0}, window=1)
        wd.observe("m", 1 << 30, 2.0)
        canon = wd.canon()
        assert canon == {"observations": 1, "windows": {"m": 1},
                         "transitions": [(1, "m", "ok", "regressed", 1)]}
        assert "last_GiBps" not in canon and "baseline" not in canon


# -- ProfilePlane surfaces ---------------------------------------------------
class TestProfilePlane:
    def test_unanchored_plane_profiles_without_judging(self):
        plane = profile.ProfilePlane()
        assert plane.watchdog is None
        plane.on_batch("encode", 4, 0, rows=3, padded=1, nbytes=100,
                       dispatch_s=1.0)
        plane.on_stream(batch=4, rows=3, nbytes=100, dispatch_s=1.0)
        m = plane.metrics()
        assert m["cess_profile_watchdog_armed"] == 0
        assert "cess_profile_regressions_total" not in m
        assert m["cess_profile_observations"] == 2
        assert m["cess_profile_pad_rows_total"] == 2
        assert m["cess_profile_pad_rows_engine"] == 1
        assert m["cess_profile_pad_rows_stream"] == 1

    def test_snapshot_and_witness_are_canonical(self):
        def feed():
            plane = profile.ProfilePlane(
                baseline={"rs_4p8_encode_GiBps_per_chip": 10.0},
                window=2)
            plane.on_batch("encode", 4, 0, rows=3, padded=1,
                           nbytes=1 << 20, queue_s=0.001,
                           dispatch_s=0.5)
            plane.on_batch("encode", 4, 0, rows=4, padded=0,
                           nbytes=1 << 20, queue_s=0.002,
                           dispatch_s=0.25)
            plane.compile_event(("encode", 4), 0.125)
            return plane

        plane = feed()
        snap = plane.snapshot()
        json.dumps(snap)                     # the RPC payload contract
        assert snap["watchdog"]["states"] == {
            "rs_4p8_encode_GiBps_per_chip": "regressed"}
        assert plane.metrics()["cess_profile_regressed"] == 1
        assert set(plane.ledgers()) == {"pads", "compiles"}
        w = plane.witness()
        assert isinstance(w, bytes)
        assert w == feed().witness()         # same feed, same bytes
        # host timings differ, witness must not: replay the same
        # counters with different measured stage times
        plane2 = profile.ProfilePlane(
            baseline={"rs_4p8_encode_GiBps_per_chip": 10.0}, window=2)
        plane2.on_batch("encode", 4, 0, rows=3, padded=1,
                        nbytes=1 << 20, queue_s=0.9, dispatch_s=0.7)
        plane2.on_batch("encode", 4, 0, rows=4, padded=0,
                        nbytes=1 << 20, queue_s=0.8, dispatch_s=0.6)
        plane2.compile_event(("encode", 4), 9.0)
        assert plane2.witness() == w


# -- zero-cost-when-off ------------------------------------------------------
class TestZeroCostDisarmed:
    def test_disarmed_engine_has_no_profile_surface(self):
        eng = make_engine(K, M)
        try:
            assert eng.profile is None
            assert eng.programs.profile is None
            assert eng.stats.profile is None
            eng.encode(rnd((1, K, 64), 3), timeout=30)
            assert not [k for k in eng.stats.metrics()
                        if k.startswith("cess_profile_")]
            assert "profile" not in eng.stats.snapshot()
        finally:
            eng.close()

    def test_disarmed_stream_feeds_nothing(self):
        eng = make_engine(K, M)
        try:
            out = StreamingIngest(make_pipe(), 4, engine=eng).ingest(
                rnd((7, SEG), 4))
            assert out["tags"].shape[0] == 7
        finally:
            eng.close()

    def test_armed_engine_exports_the_gauges(self):
        plane = profile.ProfilePlane()
        eng = make_engine(K, M, profile=plane)
        try:
            eng.encode(rnd((3, K, 64), 3), timeout=30)
            m = eng.stats.metrics()
            assert m["cess_profile_observations"] == 1
            assert m["cess_profile_served_rows_total"] == 3
            assert m["cess_profile_pad_rows_total"] == 1
            assert m["cess_profile_watchdog_armed"] == 0
            snap = eng.stats.snapshot()
            assert snap["profile"]["ops"]["observations"] == 1
            assert snap["profile"]["pads"]["total"]["padded"] == 1
        finally:
            eng.close()


# -- incident trigger --------------------------------------------------------
class TestIncidentTrigger:
    def test_only_the_regressed_edge_is_an_incident(self):
        rec = flight.FlightRecorder(b"inc")
        rep = IncidentReporter(rec)
        rec.note("perf", "regression", metric="m", frm="regressed",
                 to="ok", window=2)
        assert rep.bundles() == []           # recovery is good news
        rec.note("perf", "regression", metric="m", frm="ok",
                 to="regressed", window=3)
        (b,) = rep.bundles()
        assert b["trigger"] == "perf-regression" and b["key"] == "m"
        assert "profile" not in b["snapshots"]   # no plane attached
        json.dumps(b)

    def test_bundle_embeds_both_ledgers_when_a_plane_is_attached(self):
        plane = profile.ProfilePlane()
        plane.on_batch("encode", 4, 0, rows=3, padded=1)
        plane.compile_event(("encode", 4), 0.5)
        rec = flight.FlightRecorder(b"inc")
        rep = IncidentReporter(rec, profile=plane)
        rec.note("perf", "regression", metric="m", frm="ok",
                 to="regressed", window=1)
        (b,) = rep.bundles()
        prof = b["snapshots"]["profile"]
        assert prof["pads"]["total"] == {"served": 3, "padded": 1,
                                         "sources": {"engine": 1}}
        assert prof["compiles"]["builds"] == 1
        json.dumps(b)


# -- THE acceptance drill ----------------------------------------------------
# injected dispatch slowness per batch: with ~hundreds of payload
# bytes, a faulted window is bounded above by ~1e-5 GiB/s — five
# orders of magnitude under guard x the checked-in encode baseline
# (~32 GiB/s), so the regression decision is decisive on any host and
# the replay witness is byte-stable
DRILL_DELAY_S = 0.05
DRILL_WINDOW = 2


def _run_perf_drill(seed: bytes):
    """Drive 4 sequential encodes through an engine whose dispatch is
    delayed by a seeded FaultPlan, under an armed flight recorder with
    a profile-aware IncidentReporter; returns the replay evidence."""
    baseline = profile.load_baseline(BASELINE_FIXTURE)
    assert baseline[ENCODE_METRIC] > 0   # anchored by the fixture
    plane = profile.ProfilePlane(baseline=baseline, window=DRILL_WINDOW)
    eng = make_engine(K, M, profile=plane)
    rec = flight.FlightRecorder(seed)
    rep = IncidentReporter(rec, engine=eng, profile=plane)
    plan = faults.FaultPlan.seeded(
        seed, {"engine.dispatch":
               (1.0, faults.FaultSpec(kind="delay",
                                      delay_s=DRILL_DELAY_S))},
        horizon=16)
    data = rnd((1, K, 64), 7)
    try:
        with flight.armed(rec), faults.armed(plan):
            for _ in range(2 * DRILL_WINDOW):
                eng.encode(data, timeout=30)
    finally:
        eng.close()
    return plane, rep, plan


class TestPerfRegressionDrill:
    def test_watchdog_walks_the_edge_and_bundles_the_ledgers(self):
        plane, rep, plan = _run_perf_drill(b"perf-drill")
        # every dispatch crossed the delayed seam
        assert [f[:1] + f[2:] for f in plan.fired_log()] \
            == [("engine.dispatch", "delay")] * 4
        wd = plane.watchdog
        assert wd.state(ENCODE_METRIC) == "regressed"
        # EDGE-triggered: two closed windows both regressed, ONE
        # transition — at the first window, observation count 2
        assert wd.transition_log() == (
            (DRILL_WINDOW, ENCODE_METRIC, "ok", "regressed", 1),)
        assert wd.canon()["windows"] == {ENCODE_METRIC: 2}
        m = plane.metrics()
        assert m["cess_profile_watchdog_armed"] == 1
        assert m["cess_profile_regressions_total"] == 1
        assert m["cess_profile_regressed"] == 1
        # the incident bundle snapshotted with BOTH ledgers embedded
        (b,) = rep.bundles()
        assert b["trigger"] == "perf-regression"
        assert b["key"] == ENCODE_METRIC
        assert b["detail"]["frm"] == "ok" \
            and b["detail"]["to"] == "regressed"
        prof = b["snapshots"]["profile"]
        # built at the transition (the 2nd dispatch): 2 served rows
        assert prof["pads"]["total"]["served"] == 2
        assert prof["compiles"]["builds"] == 1      # one bucket-1 build
        json.dumps(b)       # must survive the cess_incidentDump path

    def test_same_seed_replay_reproduces_the_witness_bytes(self):
        a_plane, _, a_plan = _run_perf_drill(b"perf-replay")
        b_plane, _, b_plan = _run_perf_drill(b"perf-replay")
        w = a_plane.witness()
        assert isinstance(w, bytes)
        assert w == b_plane.witness()
        assert a_plan.fired_log() == b_plan.fired_log()
        # the witness really carries all four parts
        canon = json.loads(w)
        assert set(canon) == {"ops", "pads", "compiles", "watchdog"}
        assert canon["watchdog"]["transitions"] \
            == [[DRILL_WINDOW, ENCODE_METRIC, "ok", "regressed", 1]]


# -- wire-up: RPC, CLI, sim --------------------------------------------------
class TestRpcSurface:
    def test_profile_dump_serves_the_node_plane(self):
        from cess_tpu.node.chain_spec import dev_spec
        from cess_tpu.node.network import Node
        from cess_tpu.node.rpc import RpcServer

        node = Node(dev_spec(), "rpc-node", {})
        rpc = RpcServer(node, port=0).start()
        try:
            assert rpc.handle("cess_profileDump", []) is None
            plane = profile.ProfilePlane()
            plane.on_batch("encode", 4, 0, rows=3, padded=1)
            node.profile = plane
            dump = rpc.handle("cess_profileDump", [])
            assert dump["ops"]["observations"] == 1
            assert dump["pads"]["total"]["padded"] == 1
            assert dump["watchdog"] is None
            json.dumps(dump)
        finally:
            rpc.stop()


class TestCliFlag:
    def test_profile_requires_engine(self):
        from cess_tpu.node.cli import main

        with pytest.raises(SystemExit) as ei:
            main(["--dev", "--blocks", "1", "--profile"])
        assert "requires --engine" in str(ei.value)

    @staticmethod
    def _cli_engine(profile_spec):
        """What ``--engine cpu --profile[=SPEC]`` builds ("" = bare)."""
        import argparse

        from cess_tpu.node.chain_spec import dev_spec
        from cess_tpu.node.cli import _make_cli_engine

        return _make_cli_engine(
            argparse.Namespace(engine="cpu", resilience="off",
                               profile=profile_spec), dev_spec())

    def test_cli_engine_builds_an_anchored_plane(self):
        eng = self._cli_engine(BASELINE_FIXTURE)
        try:
            assert eng.profile is not None
            wd = eng.profile.watchdog
            assert wd is not None
            assert wd.snapshot()["baseline"] \
                == profile.load_baseline(BASELINE_FIXTURE)
        finally:
            eng.close()

    def test_cli_bare_profile_builds_an_unanchored_plane(self, capsys):
        from cess_tpu.node.cli import _finish_cli_profile

        eng = self._cli_engine("")
        try:
            assert eng.profile is not None
            assert eng.profile.watchdog is None
            _finish_cli_profile(eng)
        finally:
            eng.close()
        assert "watchdog off (no baseline)" in capsys.readouterr().err

    def test_help_says_what_bare_profile_does(self, capsys):
        # argparse %-formats every help string: a bare "%" in any one
        # of them makes --help raise instead of print
        from cess_tpu.node.cli import main

        with pytest.raises(SystemExit) as ei:
            main(["--help"])
        assert ei.value.code == 0
        text = " ".join(capsys.readouterr().out.split())
        assert "Bare --profile has no baseline" in text
        assert "watchdog off" in text
        assert "err=1%;encode" in text

    def test_cli_refuses_a_file_that_is_no_artifact(self, tmp_path):
        p = tmp_path / "not_an_artifact.json"
        p.write_text(json.dumps({"metric": "x", "value": 1.0}))
        with pytest.raises(ValueError, match="not a bench baseline"):
            self._cli_engine(str(p))


class TestSimScenario:
    def test_profile_requires_pool(self):
        from cess_tpu.sim import SCENARIOS, run_scenario

        sc = dataclasses.replace(SCENARIOS["gateway_hotspot_pool"],
                                 pool=False)
        assert sc.profile
        with pytest.raises(ValueError, match="pool=True"):
            run_scenario(sc, b"x", n_nodes=4)

    def test_profile_snapshot_rides_the_report(self):
        from cess_tpu.sim import SCENARIOS, run_scenario

        report = run_scenario(SCENARIOS["gateway_hotspot_pool"],
                              b"prof", n_nodes=8)
        snap = report.profile
        assert snap is not None
        assert snap["ops"]["observations"] >= 1
        assert snap["watchdog"] is None      # sim planes are unanchored
        json.dumps(snap)
