"""The reduction from a trace to busy/idle, device time by operation and
attributed idle gaps, on a small recorded trace whose numbers are known by
hand (data/small_trace.textproto says how)."""
import os

import pytest

import trace_reduce

TRACE = os.path.join(os.path.dirname(__file__), "data",
                     "small_trace.textproto")


@pytest.fixture(scope="module")
def summary():
    return trace_reduce.reduce(trace_reduce.load(TRACE), 1)


def test_busy_share(summary):
    assert summary["planes"] == ["/device:TPU:0"]
    assert summary["window_s"] == pytest.approx(1000e-6)
    assert summary["busy_s"] == pytest.approx(600e-6)
    assert 1 - summary["busy_s"] / summary["window_s"] == pytest.approx(0.4)


def test_top_op_is_self_time(summary):
    ops = dict(summary["device_ops"])
    assert summary["device_ops"][0][0] == "dynamic_update_slice.9 u8[96,1024]"
    assert ops["dynamic_update_slice.9 u8[96,1024]"] == pytest.approx(300e-6)
    # the while does not count its body again
    assert ops["while.6 (s32[],"] == pytest.approx(100e-6)
    assert "early.9 u8[4]" not in ops          # straddles the window's start
    assert sum(ops.values()) == pytest.approx(summary["busy_s"])


def test_idle_gaps_go_to_the_innermost_span(summary):
    gaps = dict(summary["idle_gaps"])
    assert gaps == pytest.approx({"op_b": 250e-6, "op_a": 100e-6,
                                  "inner": 50e-6})
    assert sum(gaps.values()) == pytest.approx(
        summary["window_s"] - summary["busy_s"])


def test_kernel_seconds(summary):
    secs, calls = trace_reduce.kernel_seconds(
        summary, lambda e: e["name"].startswith("%_apply_3d"))
    assert (secs, calls) == (pytest.approx(100e-6), 1)


def test_union_merges_overlaps():
    assert trace_reduce._union([(4, 6), (1, 3), (2, 5), (8, 9)]) == \
        [(1, 6), (8, 9)]


def test_no_device_plane_is_no_summary():
    events = [e for e in trace_reduce.load(TRACE)
              if not e["plane"].startswith("/device:")]
    assert trace_reduce.reduce(events, 1) is None
