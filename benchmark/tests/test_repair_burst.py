"""The cell ``repair-4p8.erasure4`` (configuration ``baseline-4p8-decode``,
PR 52; traffic kind ``repair_burst``): rehearsed on the CPU end to end,
traced and untraced; each of its three controls comes out not correct; a
window too short to keep two bursts still has two compared with the
reference, one of them with a data row lost (made in the check); the
configuration's widths are ``baseline-4p8``'s; and its readers over canned
counters and a canned trace that holds two bucket shapes — the roofline
share, with each call's work reckoned from its own shape, stays under
100% where one work figure for all calls would pass it."""
import json
import os
import types

import pytest

import kernel_work
import run as bench_run
import test_run
from conftest import BENCH

CELL = "repair-4p8.erasure4"
# test_run.py's own table cannot be edited from here; its check that
# every cell has controls reads the table when it runs
test_run.CONTROLS[CELL] = ["flip_byte", "wrong_helpers", "dropped_segment"]
NEW = ("decode_gib_per_s.erasure4", "requests_per_batch.erasure4",
       "engine_pad_share.erasure4", "fetch_regroup_ms.erasure4")
JOINED = ("engine_queue_ms.repair", "engine_coalesce_ms.repair",
          "engine_wake_ms.repair", "engine_wait_ms.repair",
          "engine_fetch_ms.repair", "engine_handoff_ms.repair",
          "wait_device_share.repair")
ROOFLINE = "rs_kernel_roofline.erasure4"
MIB = 1 << 20


def _config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def read(name, view):
    return bench_run.load_by_path("layer_metrics", name).read(view)


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal(trace):
    rc, lines, err = test_run.run("--workload", CELL, "--rehearse",
                                  "--seed", str(2 ** 31 + 52), "--trace",
                                  str(trace))
    assert rc == 0, err[-2000:]
    last = lines[-1]
    assert last["rehearsal"] == "passed" and last["correct"] is True
    compares = [x for x in lines if "compare" in x]
    assert compares and all(c["ok"] and c["value"] == 0 for c in compares)
    run_line = next(x for x in lines if x.get("info") == "run")
    assert run_line["compiled_in_window"] == 0 and run_line["failed"] == 0
    check = next(x for x in lines if x.get("info") == "check")
    assert len(check["kept"]) >= 2
    assert check["rows_compared"] == 32 * len(check["kept"])
    assert any(row < 4 for _, _, lost in check["kept"] for row in lost)
    for _, helpers, lost in check["kept"]:
        assert helpers == [j for j in range(12) if j not in lost][:4]
    assert check["rows_hashed"] >= 32 * run_line["attempted"]
    # every run says where its process spent a burst's clock
    host = next(x for x in lines if x.get("info") == "host side")
    assert host["bursts"] == run_line["attempted"]
    assert host["first_result_ms_p50"] > 0 and host["copy_out_ms_p50"] > 0
    assert host["minor_faults_per_burst"] >= 0
    if trace:
        assert set(last["metrics_read"]) == set(NEW + JOINED)
        batches = next(x for x in lines
                       if x.get("info") == "repair batches")
        assert batches["requests"] == 8 * run_line["attempted"]
        assert sum(batches["drains"].values()) == batches["batches"]
        regroup = next(x for x in lines
                       if x.get("info") == "fetch regroup")
        # four rows a request: every result byte was copied once more
        assert regroup["regrouped_bytes"] == regroup["result_bytes"] \
            == 32 * 16384 * run_line["attempted"]
    else:
        assert set(last["metrics_read"]) == {"repair_p50_ms",
                                             "repair_p95_ms", "setup_s"}


@pytest.mark.parametrize("control", test_run.CONTROLS[CELL])
def test_broken_path_is_not_correct(control):
    rc, lines, err = test_run.run("--workload", CELL, "--rehearse",
                                  "--seed", "52", "--control", control)
    assert rc == 1, err[-2000:]
    assert lines[-1]["rehearsal"] == "FAILED"
    assert lines[-1]["correct"] is False
    bad = {c["compare"] for c in lines if "compare" in c and not c["ok"]}
    assert any("SHA-256" in what for what in bad)
    assert any("plain reference" in what for what in bad)
    # the engine itself did not fail: it was asked something else
    assert not any("engine failed" in what for what in bad)


def test_a_short_window_makes_its_kept_bursts_in_the_check():
    rc, lines, err = test_run.run("--workload", CELL, "--rehearse",
                                  "--seed", str(2 ** 31 + 53), "--seconds",
                                  "0.001")
    assert rc == 0, err[-2000:]
    check = next(x for x in lines if x.get("info") == "check")
    assert check["bursts_forced"] >= 1 and len(check["kept"]) >= 2
    assert any(row < 4 for row in check["kept"][-1][2])
    assert lines[-1]["correct"] is True


def test_the_widths_are_baseline_4p8s_and_none_is_cut():
    base, decode = _config("baseline-4p8"), _config("baseline-4p8-decode")
    for key in ("k", "m", "segment_size", "fragment_size", "podr2_sectors",
                "podr2_limbs", "podr2_block_bytes", "podr2_key_seed",
                "blocks_per_fragment", "stored_bytes_per_user_byte",
                "rehearse"):
        assert decode[key] == base[key], key
    assert decode["lost_rows"] == 4
    assert set(decode["reduced"]) == {"batch", "roles", "chain", "fillers"}
    entry = next(c for c in test_run.SPEC["configs"]
                 if c["name"] == "baseline-4p8-decode")
    assert entry["reduced"] == list(decode["reduced"])
    assert entry["source"] == decode["source"]
    assert entry["source"].startswith("BASELINE.json configs[2]")
    assert len(entry["source"]) <= 200
    cell = json.load(open(os.path.join(BENCH, "workloads", CELL + ".json")))
    t = cell["traffic"]
    assert (t["kind"], t["pool_deals"], t["deal_segments"], t["lost_rows"],
            t["answering"]) == ("repair_burst", 2, 8, 4, "lowest")
    assert cell["chips"] == 1


def test_the_cell_is_on_the_lists_the_issue_names_and_off_the_others():
    by_name = {m["name"]: m
               for m in test_run.SPEC["end_to_end"]
               + test_run.SPEC["per_layer"]}
    for name in ("repair_p50_ms", "repair_p95_ms") + JOINED:
        assert by_name[name]["workloads"][-1] == CELL, name
    for name in NEW + (ROOFLINE,):
        assert by_name[name]["workloads"] == [CELL], name
        assert by_name[name]["moves"] == "repair_p50_ms"
    # their readers reckon one lost row, or count per request what here
    # happens per burst
    for name in ("rs_kernel_roofline.repair", "engine_p50_ms.repair",
                 "new_pattern_share.helpers", "matrix_build_ms.helpers"):
        assert CELL not in by_name[name]["workloads"], name


# -- the readers on canned counters ------------------------------------------
def _repair_class(**over):
    c = {"batches": 10, "batched_requests": 12, "completed": 12,
         "rows": 12, "padded_rows": 0, "result_bytes": 1000,
         "regroup_s": 0.5, "regrouped_bytes": 1000,
         "patterns_new": 3, "matrix_build_s": 0.25,
         "drains": {"idle": 10, "window": 0, "size": 0, "forced": 0}}
    c.update(over)
    return {"engine": {"classes": {"repair": c}}}


@pytest.fixture
def view():
    """A window of 25 bursts split 1 + 7: 50 batches, 200 requests, a
    pad row every second batch, 25 x 128 MiB handed back, all of it
    regrouped in 1.5 s."""
    said = []
    return types.SimpleNamespace(
        counters_before=_repair_class(),
        counters_after=_repair_class(
            batches=60, batched_requests=212, completed=212, rows=212,
            padded_rows=25, result_bytes=1000 + 25 * 128 * MIB,
            regroup_s=2.0, regrouped_bytes=1000 + 25 * 128 * MIB,
            patterns_new=23, matrix_build_s=0.26,
            drains={"idle": 60, "window": 0, "size": 0, "forced": 0}),
        ops=[{"ok": True, "latency_s": 0.125, "rebuilt_bytes": 128 * MIB,
              "lost_rows": 4}] * 24
        + [{"ok": False, "latency_s": 9.0, "rebuilt_bytes": 128 * MIB,
            "lost_rows": 4}],
        window_s=30.0, trace=None, said=said,
        say=lambda **line: said.append(line))


def test_counter_readers_on_canned_counters(view):
    assert read("requests_per_batch.erasure4", view) == pytest.approx(4.0)
    assert read("engine_pad_share.erasure4", view) \
        == pytest.approx(100 * 25 / 225)
    assert read("fetch_regroup_ms.erasure4", view) == pytest.approx(30.0)
    # the failed burst counts in neither the bytes nor the seconds
    assert read("decode_gib_per_s.erasure4", view) == pytest.approx(1.0)
    said = {line["info"]: line for line in view.said}
    assert said["repair batches"]["drains"]["idle"] == 50
    # 20 of the 25 bursts met a pattern the codec held no matrix for
    assert said["repair batches"]["new_patterns_per_batch"] \
        == pytest.approx(20 / 50)
    assert said["repair batches"]["matrix_build_ms_per_batch"] \
        == pytest.approx(10.0 / 50)
    assert said["fetch regroup"]["regrouped_share"] == pytest.approx(1.0)
    assert said["decode rate"]["bursts"] == 24


def test_a_burst_in_one_batch_reads_eight_and_no_pad(view):
    view.counters_after = _repair_class(
        batches=35, batched_requests=212, rows=212, padded_rows=0)
    assert read("requests_per_batch.erasure4", view) == pytest.approx(8.0)
    assert read("engine_pad_share.erasure4", view) == 0.0


def test_without_the_counters_every_reader_returns_none(view):
    """The parent's snapshot has neither ``batched_requests`` nor the
    three result counters; a cell that drove no engine has no ``engine``
    at all; a window without a batch gives nothing to divide by."""
    for snap in (view.counters_before, view.counters_after):
        for key in ("batched_requests", "result_bytes", "regroup_s",
                    "regrouped_bytes"):
            del snap["engine"]["classes"]["repair"][key]
    assert read("requests_per_batch.erasure4", view) is None
    assert read("fetch_regroup_ms.erasure4", view) is None
    assert read("engine_pad_share.erasure4", view) is not None
    view.counters_before = view.counters_after = {}
    for name in NEW[1:]:
        assert read(name, view) is None
    view.counters_before = view.counters_after = _repair_class()
    for name in NEW[1:]:
        assert read(name, view) is None
    view.ops = []
    assert read("decode_gib_per_s.erasure4", view) is None
    assert read(ROOFLINE, view) is None                # no trace


# -- the roofline reader on a canned trace of two bucket shapes --------------
def _event(bucket, dur_ns, start_ns):
    return {"plane": "/device:TPU:0", "line": "XLA Ops",
            "name": f"%_apply_3d.1 = u8[{bucket},4,4194304]"
                    "{2,1,0:T(4,128)(4,1)} custom-call(%copy-done, "
                    "%copy-done.1, %fusion.7), custom_call_target="
                    '"tpu_custom_call"',
            "start_ns": start_ns, "dur_ns": dur_ns, "stats": {}}


def test_roofline_reckons_each_call_from_its_own_shape():
    """Ten bursts split 1 + 7: ten calls at bucket 1 and ten at bucket 8,
    each at a quarter of its own roofline, beside a flatten that is no
    kernel call."""
    kind = "TPU v5 lite"
    least = {b: kernel_work.least_seconds(
        kernel_work.rs_apply(4, 4, 4 * MIB, b), kind)[0] for b in (1, 8)}
    assert least[8] == pytest.approx(8 * least[1])
    assert least[1] == pytest.approx(32 * MIB / 819e9)     # HBM-bound
    events, t = [], 0
    for _ in range(10):
        for b in (1, 8):
            dur = int(4 * least[b] * 1e9)
            events.append(_event(b, dur, t))
            t += dur + 1000
        events.append({**_event(8, 50_000, t),
                       "name": "%reduce.3 = u8[4194304]{0} reduce(...)"})
        t += 51_000
    said = []
    view = types.SimpleNamespace(
        trace={"events": events, "planes": ["/device:TPU:0"]},
        ctx=types.SimpleNamespace(config={"k": 4}, device_kind=kind),
        say=lambda **line: said.append(line))
    share = read(ROOFLINE, view)
    assert share == pytest.approx(25.0, rel=1e-3)
    (line,) = said
    assert {k: v["calls"] for k, v in line["by_shape"].items()} \
        == {"u8[1,4,4194304]": 10, "u8[8,4,4194304]": 10}
    # one work figure for all twenty calls (the bucket-8 shape's, as the
    # sibling readers reckon) would pass 100% on the same trace
    import trace_reduce
    seconds, calls = trace_reduce.kernel_seconds(
        view.trace, lambda e: e["name"].startswith("%_apply_3d"))
    assert 100 * calls * least[8] / seconds > 40 > share
    # and a window of bucket-1 calls alone, reckoned so, over 100%
    view.trace["events"] = [e for e in events
                            if "u8[1,4," in e["name"]]
    seconds, calls = trace_reduce.kernel_seconds(
        view.trace, lambda e: e["name"].startswith("%_apply_3d"))
    assert 100 * calls * least[8] / seconds > 100
    assert read(ROOFLINE, view) == pytest.approx(25.0, rel=1e-3)


def test_roofline_without_kernel_calls_is_none():
    view = types.SimpleNamespace(
        trace={"events": [{"name": "%fusion.1 = u8[8,4,4194304]{2,1,0} "
                                   "fusion(...)", "dur_ns": 10,
                           "start_ns": 0, "plane": "/device:TPU:0"}],
               "planes": ["/device:TPU:0"]},
        ctx=types.SimpleNamespace(config={"k": 4},
                                  device_kind="TPU v5 lite"),
        say=lambda **line: None)
    assert read(ROOFLINE, view) is None
