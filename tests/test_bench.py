"""bench.py --smoke is a tier-1 gate: every metric code path must run
CPU-safe on tiny shapes and produce a finite positive value, so bench
code paths cannot silently rot between measurement rounds (the metrics
only run on the real chip otherwise). Also pins the r06 satellites:
raw per-side speedup timings recorded, the warm repair metric emitted
separately from cold dispatch, and the streamed from-host-bytes metric
reporting its stage counters — plus the tools/bench_diff.py regression
gate over checked-in fixture records (ISSUE 6 satellite).
"""
import json
import math
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(REPO, "tests", "data")

EXPECTED = (
    "rs_4erasure_decode_GiBps_per_chip",
    "fragment_repair_p99_ms",
    "fragment_repair_warm_p99_ms",
    "podr2_100k_tag_verify_frags_per_s",
    "stream_encode_tag_GiBps",
    "stream_encode_tag_traced_GiBps",
    "degraded_encode_GiBps",
    "adaptive_mixed_p99_ms",
    "sim_500node_round_drain_s",
    "rs_4p8_encode_GiBps_per_chip",
    "pool_stream_encode_tag_GiBps",
    "pool_podr2_tag_verify_frags_per_s",
    "fleet_federate_100nodes_ms",
    "stream_encode_tag_profiled_GiBps",
    "chainwatch_100node_scan_ms",
    "repair_storm_drain_s",
    "ingress_bytes_per_recovered_byte",
    "remediation_react_rounds",
    "stream_encode_tag_remediated_GiBps",
    "cesslint_full_tree_s",
    "rs_xor_encode_GiBps_per_chip",
    "xor_schedule_saving_frac",
    "custody_scan_100node_ms",
    "durability_margin_min",
)


def test_bench_smoke_every_metric_finite():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--smoke"],
        capture_output=True, text=True, cwd=REPO, timeout=300, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    recs = [json.loads(line) for line in proc.stdout.splitlines()
            if line.startswith("{")]
    got = {r["metric"]: r for r in recs}
    for name in EXPECTED:
        assert name in got, f"missing metric {name}"
        v = got[name]["value"]
        assert math.isfinite(v) and v > 0, (name, v)
    # the speedup metric (either the native name or the renamed numpy
    # fallback) records RAW per-side timings (r05 drift satellite)
    speedup = next(r for r in recs
                   if r["metric"].startswith("cpu_speedup_encode"))
    assert math.isfinite(speedup["value"]) and speedup["value"] > 0
    for field in ("device_GiBps", "cpu_GiBps", "device_window_GiBps",
                  "cpu_times_ms"):
        assert field in speedup, field
    assert len(speedup["cpu_times_ms"]) >= 5
    # r06 protocol fix (ISSUE 18 satellite): BOTH sides of the ratio
    # run min-of-3-windows, and the baseline's per-window rates ride
    # the record so drift is attributable to one side
    assert len(speedup["cpu_window_GiBps"]) == 3
    assert len(speedup["device_window_GiBps"]) == 3
    assert speedup["cpu_GiBps"] == max(speedup["cpu_window_GiBps"])
    # the XOR-scheduled codec pins (ISSUE 18): the scheduled encode
    # row carries the dense-vs-CSE'd op counts, and the compiler
    # clears the >= 25% reduction acceptance bar on the (4,8) matrix
    xor = got["rs_xor_encode_GiBps_per_chip"]
    assert xor["n_xors"] < xor["dense_xors"]
    assert xor["scratch_high_water"] >= 1
    saving = got["xor_schedule_saving_frac"]
    assert saving["value"] >= 0.25
    assert saving["value"] == round(
        1.0 - saving["n_xors"] / saving["dense_xors"], 3)
    # warm repair is measured separately from cold dispatch
    warm = got["fragment_repair_warm_p99_ms"]
    assert warm["cold_compile_first_call_ms"] > 0
    # the streamed metric reports its per-stage counters
    stream = got["stream_encode_tag_GiBps"]
    assert stream["batches"] >= 1 and stream["segments"] >= 1
    assert stream["padded_segments"] >= 1          # ragged tail hit
    for field in ("h2d_s", "dispatch_s", "stall_s", "stall_frac"):
        assert field in stream, field
    # degraded mode (breaker forced open) asserted bit-identical to
    # the device path before the metric is even emitted (ISSUE 4)
    assert got["degraded_encode_GiBps"]["bit_identical"] is True
    # the tracing-cost pin (ISSUE 5): armed-vs-off throughput on the
    # streamed path, with the overhead fraction recorded and finite
    traced = got["stream_encode_tag_traced_GiBps"]
    assert math.isfinite(traced["trace_overhead_frac"])
    assert traced["spans"] >= 1          # the armed run really traced
    assert math.isfinite(traced["untraced_GiBps"]) \
        and traced["untraced_GiBps"] > 0
    # the retention-cost pin (ISSUE 9): the same run with a
    # FlightRecorder attached — the overhead fraction is finite and
    # the armed throughput is real
    assert math.isfinite(traced["flight_overhead_frac"])
    assert math.isfinite(traced["flight_GiBps"]) \
        and traced["flight_GiBps"] > 0
    assert traced["pinned"] >= 0
    # the adaptive-policy pin (ISSUE 6): sustained mixed traffic at a
    # fixed verify p99 target — the adaptive knobs beat the static
    # constants by a wide margin (the target itself is recorded, and
    # met_target rides along informationally; the static policy's miss
    # is structural: its coalescing window alone exceeds the target)
    ad = got["adaptive_mixed_p99_ms"]
    for field in ("static_p99_ms", "target_ms", "met_target",
                  "static_met_target", "static_encode_GiBps",
                  "adaptive_encode_GiBps"):
        assert field in ad, field
    assert ad["value"] < ad["static_p99_ms"]
    assert ad["static_met_target"] is False
    assert ad["static_p99_ms"] > ad["target_ms"]
    # the sim drain metric (ISSUE 8): one churned+partitioned virtual
    # round drained in finite wall time, with the sim's throughput
    # counters riding along
    sim = got["sim_500node_round_drain_s"]
    assert sim["events"] >= 1 and sim["events_per_s"] > 0
    assert sim["virtual_s"] > 0 and sim["n_nodes"] >= 2
    # the pool metrics (ISSUE 10): multi-lane runs on >=2 (virtual)
    # devices, asserted bit-identical to the single-device engine
    # in-bench, with the scaling ratio recorded honestly (CPU lanes
    # share cores, so no threshold here — the >=0.8x claim rides the
    # MULTICHIP dry-run on real chips)
    for name in ("pool_stream_encode_tag_GiBps",
                 "pool_podr2_tag_verify_frags_per_s"):
        pool = got[name]
        assert pool["n_devices"] >= 2, name
        assert pool["bit_identical"] is True, name
        assert math.isfinite(pool["scaling_efficiency"]) \
            and pool["scaling_efficiency"] > 0, name
    assert got["pool_podr2_tag_verify_frags_per_s"]["lanes_used"] >= 2
    # the fleet federation metric (ISSUE 12): the SAME 100-node shape
    # runs under --smoke — parse + clamp + merge + board + scan over
    # 100 synthesized expositions, with the federated series counts
    # riding along so a silently-empty federation can't pass
    fl = got["fleet_federate_100nodes_ms"]
    assert fl["n_nodes"] == 100
    assert fl["counters"] >= 100 and fl["gauges"] >= 100
    assert fl["histograms"] >= 1
    # the profiling-cost pin (ISSUE 13): the same streamed run feeding
    # an armed ProfilePlane through the attached engine — overhead
    # fraction finite, and the armed run really profiled (every staged
    # batch observed, the ragged tail's pad rows billed)
    prof = got["stream_encode_tag_profiled_GiBps"]
    assert math.isfinite(prof["profile_overhead_frac"])
    assert math.isfinite(prof["unprofiled_GiBps"]) \
        and prof["unprofiled_GiBps"] > 0
    assert prof["observations"] >= 1
    assert prof["pad_rows"] >= 1 and prof["served_rows"] >= 1
    # the chain-plane scan metric (ISSUE 14): the SAME 100-node shape
    # runs under --smoke — tail-diff + equivocation doubles + market
    # ledger + detectors over 100 synthesized states, with the
    # detector counts riding along so a silently-empty scan can't pass
    cw = got["chainwatch_100node_scan_ms"]
    assert cw["n_nodes"] == 100
    assert cw["equivocations"] >= 1 and cw["anomalies"] >= 1
    assert cw["miners"] >= 1
    # the repair-storm metrics (ISSUE 15): a batch miner kill drained
    # through the regenerating repair plane — every order cleared via
    # symbol chains, and the measured ingress per recovered byte beats
    # the k=2 whole-fragment baseline
    storm = got["repair_storm_drain_s"]
    assert storm["orders"] >= 1 and storm["symbol_repairs"] >= 1
    assert storm["fallbacks"] == 0
    assert storm["recovered_bytes"] > 0
    ing = got["ingress_bytes_per_recovered_byte"]
    assert ing["baseline_bytes_per_byte"] == 2.0
    assert ing["value"] < ing["baseline_bytes_per_byte"]
    assert ing["ingress_bytes"] < 2 * ing["recovered_bytes"]
    # the remediation pins (ISSUE 16): edge->action latency is
    # count-sequenced — measured in the plane's own observation rounds,
    # never wall-clock — and the armed-plane cost on the streamed path
    # rides along as a finite overhead fraction (noise-level values,
    # including slightly negative, mean the listener is free)
    react = got["remediation_react_rounds"]
    assert react["value"] >= 1 and react["release_rounds"] >= 1
    assert react["journal_entries"] >= 2     # a fire AND a release
    rem = got["stream_encode_tag_remediated_GiBps"]
    assert math.isfinite(rem["remediation_overhead_frac"])
    assert math.isfinite(rem["unremediated_GiBps"]) \
        and rem["unremediated_GiBps"] > 0
    # the analyzer-cost pin (ISSUE 17): one full in-process cesslint
    # scan of cess_tpu/ — every family including the interprocedural
    # flow fixpoint — with the scan's own counters riding along so a
    # silently-empty scan can't pass; the 10 s per-commit budget is
    # the vs_baseline denominator
    lint = got["cesslint_full_tree_s"]
    assert lint["files"] > 50 and lint["rules"] >= 17
    assert lint["findings"] == 0 and lint["errors"] == 0
    assert lint["stale_suppressions"] == 0
    # the durability pins (ISSUE 20): the custody margin fold at the
    # same 100-node shape, with the detector counts riding along so a
    # silently-empty ledger can't pass — and the synthesized decayed
    # segment pins the margin floor AT the at-risk threshold (so the
    # smoke gate's v > 0 holds and a fold that loses or invents
    # healthy fragments moves the number)
    cu = got["custody_scan_100node_ms"]
    assert cu["n_miners"] == 100 and cu["segments"] >= 100
    assert cu["margin_min"] == 1
    assert cu["at_risk"] >= 1 and cu["lost"] == 0
    dm = got["durability_margin_min"]
    assert dm["value"] == 1.0 and dm["at_risk"] >= 1
    # EVERY record carries n_devices so tools/bench_diff.py can refuse
    # to cross-compare a per-chip row against a pool row
    for r in recs:
        assert "n_devices" in r, r["metric"]


# -- tools/bench_diff.py: the perf-trajectory regression gate ---------------
def _bench_diff(*argv):
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "bench_diff.py"),
         *argv],
        capture_output=True, text=True, cwd=REPO, timeout=60)
    return proc.returncode, proc.stdout, proc.stderr


class TestBenchDiff:
    CURR = os.path.join(DATA, "bench_diff_curr.json")
    PREV = os.path.join(DATA, "bench_diff_prev.json")

    def test_regression_past_threshold_fails_the_gate(self):
        # the fixture encodes a -25% rs_4p8 encode drop: past the
        # default 10% threshold the gate exits 1 and names the metric
        code, out, _ = _bench_diff(self.CURR, "--against", self.PREV)
        assert code == 1, out
        assert "rs_4p8_encode_GiBps_per_chip" in out
        assert "REGRESSION" in out

    def test_threshold_is_configurable(self):
        code, out, _ = _bench_diff(self.CURR, "--against", self.PREV,
                                   "--threshold", "30")
        assert code == 0, out
        assert "OK" in out

    def test_json_report_directions_and_new_metrics(self):
        code, out, _ = _bench_diff(self.CURR, "--against", self.PREV,
                                   "--json")
        assert code == 1
        rep = json.loads(out)
        rows = {r["metric"]: r for r in rep["rows"]}
        # higher-is-better: the -25% encode drop is the regression
        assert rows["rs_4p8_encode_GiBps_per_chip"]["delta_pct"] == -25.0
        assert rows["rs_4p8_encode_GiBps_per_chip"]["regression_pct"] \
            == 25.0
        # lower-is-better: +8.33% repair p99 is a (sub-threshold)
        # regression, NOT an improvement
        repair = rows["fragment_repair_p99_ms"]
        assert repair["delta_pct"] > 0
        assert repair["regression_pct"] == repair["delta_pct"]
        # an improvement never counts as regression in either direction
        assert rows["podr2_100k_tag_verify_frags_per_s"][
            "regression_pct"] == 0.0
        # a metric new this round is reported, never gate-failing
        assert rows["adaptive_mixed_p99_ms"]["note"] == "only in current"
        assert rep["regressions"] == ["rs_4p8_encode_GiBps_per_chip"]

    def test_wallclock_seconds_are_lower_is_better(self):
        # ISSUE 8 satellite: the sim drain metric ends in _s and must
        # regress UPWARD — without swallowing _per_s throughput names
        sys.path.insert(0, os.path.join(REPO, "tools"))
        try:
            import bench_diff
        finally:
            sys.path.pop(0)
        assert bench_diff.lower_is_better("sim_500node_round_drain_s")
        assert bench_diff.lower_is_better("fragment_repair_p99_ms")
        assert not bench_diff.lower_is_better(
            "podr2_100k_tag_verify_frags_per_s")
        assert not bench_diff.lower_is_better("stream_encode_tag_GiBps")
        # ISSUE 15 satellite: the repair-cost ratio regresses UPWARD,
        # and adding it must not flip any _per_s rate
        assert bench_diff.lower_is_better(
            "ingress_bytes_per_recovered_byte")
        assert bench_diff.lower_is_better("repair_storm_drain_s")
        assert not bench_diff.lower_is_better(
            "repair_storm_orders_per_s")
        # ISSUE 18 satellite: the CSE saving fraction regresses
        # DOWNWARD (bigger saving = fewer ops = better), explicitly —
        # and adding it flips no wall-clock name
        assert not bench_diff.lower_is_better("xor_schedule_saving_frac")
        assert bench_diff.lower_is_better("anything_else_ending_in_s")
        # ISSUE 20 satellite: the erasure-margin floor regresses
        # DOWNWARD (more healthy fragments above k = safer), the
        # durability decay counts regress UPWARD — and neither rule
        # swallows the existing suffix families
        assert not bench_diff.lower_is_better("durability_margin_min")
        assert bench_diff.lower_is_better("custody_scan_100node_ms")
        assert bench_diff.lower_is_better("custody_segments_at_risk")
        assert bench_diff.lower_is_better("custody_segments_lost")
        assert not bench_diff.lower_is_better(
            "podr2_100k_tag_verify_frags_per_s")
        assert bench_diff.lower_is_better("repair_storm_drain_s")

    def test_default_against_is_the_next_lower_round(self, tmp_path,
                                                      monkeypatch):
        # "the round before the current one" means the next-LOWER
        # round number — never a newer record, which would invert the
        # timeline and report later improvements as regressions
        # (review-caught)
        sys.path.insert(0, os.path.join(REPO, "tools"))
        try:
            import bench_diff
        finally:
            sys.path.pop(0)
        for rnd, val in (("r02", 8), ("r03", 10), ("r06", 20)):
            (tmp_path / f"BENCH_{rnd}.json").write_text(
                json.dumps({"metric": "x_GiBps", "value": val}) + "\n")
        monkeypatch.setattr(bench_diff, "REPO", str(tmp_path))
        # r03 vs the default partner: must pick r02 (8 -> 10, an
        # improvement, rc 0) — not r06 (20 -> 10, a fake regression)
        assert bench_diff.main(
            [str(tmp_path / "BENCH_r03.json")]) == 0
        # no current given: newest (r06) against next-lower (r03)
        assert bench_diff.main([]) == 0
        # the oldest round has nothing earlier to diff against
        assert bench_diff.main(
            [str(tmp_path / "BENCH_r02.json")]) == 2

    def test_topology_change_is_a_note_not_a_regression(self, tmp_path):
        # ISSUE 10 satellite: when n_devices differs between rounds
        # the row becomes a note — a per-chip number vs a pool number
        # is a topology change, not a perf regression, even when the
        # raw value halves
        sys.path.insert(0, os.path.join(REPO, "tools"))
        try:
            import bench_diff
        finally:
            sys.path.pop(0)
        prev = tmp_path / "prev.jsonl"
        curr = tmp_path / "curr.jsonl"
        prev.write_text(json.dumps(
            {"metric": "pool_stream_encode_tag_GiBps", "value": 8.0,
             "n_devices": 1}) + "\n")
        curr.write_text(json.dumps(
            {"metric": "pool_stream_encode_tag_GiBps", "value": 4.0,
             "n_devices": 2}) + "\n")
        vals, devs = bench_diff.load_record(str(curr))
        assert devs == {"pool_stream_encode_tag_GiBps": 2}
        code, out, _ = _bench_diff(str(curr), "--against", str(prev),
                                   "--json")
        assert code == 0, out
        rep = json.loads(out)
        assert rep["regressions"] == []
        row = rep["rows"][0]
        assert row["delta_pct"] is None
        assert row["regression_pct"] == 0.0
        assert row["note"] == "n_devices changed (1 -> 2); not comparable"
        # same topology on both sides: the normal gate still fires
        curr.write_text(json.dumps(
            {"metric": "pool_stream_encode_tag_GiBps", "value": 4.0,
             "n_devices": 1}) + "\n")
        code, out, _ = _bench_diff(str(curr), "--against", str(prev))
        assert code == 1 and "REGRESSION" in out
        # records without n_devices (pre-r10 fixtures) compare normally
        prev.write_text(json.dumps(
            {"metric": "x_GiBps", "value": 8.0}) + "\n")
        curr.write_text(json.dumps(
            {"metric": "x_GiBps", "value": 9.0}) + "\n")
        code, out, _ = _bench_diff(str(curr), "--against", str(prev))
        assert code == 0, out

    def test_baseline_out_emits_the_watchdog_artifact(self, tmp_path):
        # ISSUE 13 satellite: --baseline-out writes the per-metric
        # baseline JSON the profile plane's PerfWatchdog consumes
        # (node.cli --profile=PATH). The checked-in fixture is that
        # artifact for a round-5 record: a wrapper holding its values
        # must reproduce it exactly
        with open(os.path.join(DATA, "bench_baseline_r05.json")) as f:
            fixture = json.load(f)
        rec = tmp_path / "BENCH_r05.json"
        rec.write_text(json.dumps({"n": 5, "cmd": "bench", "rc": 0,
                                   "tail": "\n".join(
            json.dumps({"metric": m, "value": e["value"]})
            for m, e in fixture["metrics"].items())}))
        out = tmp_path / "baseline.json"
        code, _, err = _bench_diff(str(rec), "--baseline-out", str(out))
        assert code == 0, err
        art = json.loads(out.read_text())
        assert art == fixture
        assert art["round"] == "r05"
        assert art["metrics"]["rs_4p8_encode_GiBps_per_chip"]["value"] \
            > 0
        # an explicit record is honored (per-metric n_devices rides
        # along so the watchdog's human-facing provenance is complete)
        code, _, _ = _bench_diff(self.CURR, "--baseline-out", str(out))
        assert code == 0
        assert json.loads(out.read_text())["source"] \
            == "bench_diff_curr.json"
        # incompatible with --history / multi-record invocations
        code, _, err = _bench_diff("--history", "--baseline-out",
                                   str(out))
        assert code == 2 and "at most one" in err
        code, _, err = _bench_diff(self.CURR, self.PREV,
                                   "--baseline-out", str(out))
        assert code == 2 and "at most one" in err

    def test_missing_previous_round_is_a_usage_error(self):
        code, _, err = _bench_diff(self.CURR, "--against",
                                   os.path.join(DATA, "nope.json"))
        assert code == 2
        assert "nope.json" in err


class TestBenchHistory:
    """ISSUE 12 satellite: --history renders the full per-round
    trajectory and flags plateaus — both the strict >= 3-round kind
    and the 2-round trailing kind that may be a plateau in the
    making."""
    FIX = [os.path.join(DATA, f"bench_history_{r}.jsonl")
           for r in "abcd"]

    def test_fixture_trajectory_flags_plateaus(self):
        code, out, _ = _bench_diff("--history", *self.FIX, "--json")
        assert code == 0, out
        rep = json.loads(out)
        assert len(rep["rounds"]) == 4
        # codec is flat (< 2% per round) across all 4 rounds: the
        # strict plateau flag fires, and the run reaches the newest
        # round so it is also ongoing
        assert rep["flagged"] == ["codec_GiBps"]
        codec = rep["metrics"]["codec_GiBps"]["plateaus"]
        assert codec == [{"start": "bench_history_a.jsonl",
                          "end": "bench_history_d.jsonl",
                          "rounds": 4, "ongoing": True}]
        # repair moved hard then went flat for the last 2 rounds: a
        # trailing plateau NOTE, never the >= 3-round flag
        repair = rep["metrics"]["repair_p99_ms"]["plateaus"]
        assert repair == [{"start": "bench_history_c.jsonl",
                           "end": "bench_history_d.jsonl",
                           "rounds": 2, "ongoing": True}]
        # a steadily-improving metric has no plateau at all
        assert rep["metrics"]["verify_frags_per_s"]["plateaus"] == []
        # a metric absent in early rounds renders as None, and its
        # flat tail still registers
        fleet = rep["metrics"]["fleet_federate_100nodes_ms"]
        assert fleet["values"][:2] == [None, None]

    def test_round_wrappers_surface_a_trailing_ceiling(self, tmp_path):
        # a five-round trajectory of driver round wrappers whose last
        # two rounds sit within 1% of each other: the ceiling must
        # surface as an ongoing trailing plateau, labelled by round
        recs = []
        for n, val in enumerate((36.1, 38.7, 24.4, 64.141, 63.585), 1):
            p = tmp_path / f"BENCH_r{n:02d}.json"
            p.write_text(json.dumps({"n": n, "cmd": "bench", "rc": 0,
                                     "tail": json.dumps(
                {"metric": "rs_4p8_encode_GiBps_per_chip",
                 "value": val})}))
            recs.append(str(p))
        code, out, _ = _bench_diff("--history", *recs, "--json")
        assert code == 0, out
        rep = json.loads(out)
        assert rep["rounds"][0] == "r01" and rep["rounds"][-1] == "r05"
        enc = rep["metrics"]["rs_4p8_encode_GiBps_per_chip"]["plateaus"]
        assert enc and enc[-1]["ongoing"] is True
        assert enc[-1]["end"] == "r05" and enc[-1]["rounds"] >= 2

    def test_text_mode_and_usage_errors(self):
        code, out, _ = _bench_diff("--history", *self.FIX)
        assert code == 0
        assert "PLATEAU" in out and "codec_GiBps" in out
        assert "trailing plateau" in out
        # two records without --history is a usage error pointing at it
        code, _, err = _bench_diff(self.FIX[0], self.FIX[1])
        assert code == 2 and "--history" in err
        # history over a single record cannot show a trajectory
        code, _, err = _bench_diff("--history", self.FIX[0])
        assert code == 2 and "two" in err
