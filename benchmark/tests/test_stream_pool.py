"""The cell ``stream-4p8.pool4`` (traffic kind ``stream_pool``): rehearsed
with four virtual CPU devices so that four lanes really run, traced and
untraced; its two controls come out not correct; and its three
device-trace readers on a hand-made four-plane trace
(data/pool_trace.textproto says how the numbers come about): work is one
lane's, a share stays under 100%, the idlest plane is the one picked."""
import os
import types

import pytest

import run as bench_run
import test_run
import trace_reduce

CELL = "stream-4p8.pool4"
# test_run.py's own table cannot be edited from here; its check that
# every cell has controls reads the table when it runs
test_run.CONTROLS[CELL] = ["flip_parity", "stale_tags"]
FOUR = "--xla_force_host_platform_device_count=4"
TRACE = os.path.join(os.path.dirname(__file__), "data",
                     "pool_trace.textproto")
MIB = 1 << 20


@pytest.fixture
def four_devices(monkeypatch):
    monkeypatch.setenv("XLA_FLAGS", FOUR)


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_on_four_lanes(four_devices, trace):
    rc, lines, err = test_run.run("--workload", CELL, "--rehearse",
                                  "--seed", str(2 ** 31 + 27), "--trace",
                                  str(trace))
    assert rc == 0, err[-2000:]
    last = lines[-1]
    assert last["rehearsal"] == "passed" and last["correct"] is True
    assert all(line["on"] == "cpu/cpu x4" for line in lines)
    compares = [x for x in lines if "compare" in x]
    assert compares and all(c["ok"] for c in compares)
    run_line = next(x for x in lines if x.get("info") == "run")
    assert run_line["compiled_in_window"] == 0 and run_line["failed"] == 0
    if trace:
        assert set(last["metrics_read"]) == {
            "stream_stall_share", "lane_ingest_rate.pool4",
            "stream_put_ms.pool4", "lane_busy_min.pool4"}
        lanes = next(x for x in lines if x.get("info") == "lanes")
        assert lanes["lanes"] == 4


@pytest.mark.parametrize("control", test_run.CONTROLS[CELL])
def test_broken_path_is_not_correct(four_devices, control):
    rc, lines, err = test_run.run("--workload", CELL, "--rehearse",
                                  "--seed", "27", "--control", control)
    assert rc == 1, err[-2000:]
    assert lines[-1]["rehearsal"] == "FAILED"
    assert lines[-1]["correct"] is False


# -- the device-trace readers on the four-plane trace ----------------------
@pytest.fixture(scope="module")
def view():
    said = []
    ctx = types.SimpleNamespace(
        config={"k": 4, "m": 8, "fragment_size": 4 * MIB,
                "podr2_block_bytes": 512, "podr2_limbs": 2},
        traffic={"batch": 32, "lanes": 4}, lanes=4,
        device_kind="TPU v5 lite")
    return types.SimpleNamespace(
        ctx=ctx, trace=trace_reduce.reduce(trace_reduce.load(TRACE), 4),
        say=lambda **line: said.append(line), said=said)


def read(name, view):
    return bench_run.load_by_path("layer_metrics", name).read(view)


def test_four_planes_reduce(view):
    assert view.trace["planes"] == [f"/device:TPU:{i}" for i in range(4)]
    assert view.trace["window_s"] == pytest.approx(10000e-6)
    assert view.trace["busy_s"] == pytest.approx(6875e-6)    # the mean


def test_idlest_lane_is_picked(view):
    assert read("lane_busy_min.pool4", view) == pytest.approx(40.0)
    by_plane = next(x for x in view.said
                    if x.get("info") == "busy share by plane")
    assert by_plane["/device:TPU:2"] == pytest.approx(40.0)
    assert by_plane["/device:TPU:3"] == pytest.approx(85.0)


def test_rs_roofline_counts_one_lanes_work(view):
    share = read("rs_kernel_roofline.pool4", view)
    assert share == pytest.approx(100 * (384 * MIB / 819e9) / 1000e-6)
    assert share == pytest.approx(49.16, abs=0.01) and share <= 100
    line = [x for x in view.said if x.get("kernel") == "%_apply_3d"][-1]
    assert line["calls"] == 1 and line["bytes_per_call"] == 384 * MIB
    assert line["device_s"] == pytest.approx(1000e-6)


def test_tag_roofline_counts_one_lanes_work(view):
    share = read("tag_kernel_roofline.pool4", view)
    nbytes = 96 * 4 * MIB + 2 * 4 * 96 * 8192 * 2
    assert share == pytest.approx(100 * (nbytes / 819e9) / 3000e-6)
    assert share == pytest.approx(16.90, abs=0.01) and share <= 100


def test_no_kernel_event_is_none(view):
    """The parent's sharded step tags without the kernel: nothing to
    read, and nothing raised."""
    bare = types.SimpleNamespace(**vars(view))
    bare.trace = dict(view.trace, events=[
        e for e in view.trace["events"] if "_tags_3d" not in e["name"]])
    assert read("tag_kernel_roofline.pool4", bare) is None
    bare.trace = None
    for name in ("lane_busy_min.pool4", "rs_kernel_roofline.pool4",
                 "tag_kernel_roofline.pool4"):
        assert read(name, bare) is None


def _counters(**kw):
    return {"stream": dict(batches=0, bytes_in=0, h2d_s=0.0, wall_s=0.0,
                           **kw)}


def test_lane_rate_with_and_without_the_counter(view):
    after = dict(batches=10, bytes_in=40 << 30, h2d_s=0.1, wall_s=2.0)
    for counted in ({"lanes": 4}, {}):       # the change / the parent
        v = types.SimpleNamespace(
            ctx=view.ctx, counters_before=_counters(**counted),
            counters_after={"stream": {**after, **counted}},
            say=lambda **line: None)
        assert read("lane_ingest_rate.pool4", v) == pytest.approx(5.0)
        assert read("stream_put_ms.pool4", v) == pytest.approx(10.0)
