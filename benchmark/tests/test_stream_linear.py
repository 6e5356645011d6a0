"""PR 43's two readers of the stream cells.

``fused_device_ms.ingest`` on a hand-made trace of two streamed batches
(data/fused_trace.textproto says how the numbers come about):
device-busy milliseconds over the ``%_tags_3d`` events, one a batch;
nothing where there is no trace or no batch in it. Beside
test_stream_pool.py's roofline tests, which read the same events.

``linear_put_share.ingest`` on hand-made StreamStats counters: the
window's ``linear_puts`` over its ``batches``; nothing on a program
without the counter (the parent) or a window without a batch. And both
in a traced rehearsal of the stream cells, next to the readers that were
there."""
import os
import types

import pytest

import run as bench_run
import trace_reduce
from test_run import run as run_cell

TRACE = os.path.join(os.path.dirname(__file__), "data",
                     "fused_trace.textproto")
BATCH_TWO_NS = 22_000_000      # the second batch starts here


def _view(keep=lambda e: True):
    events = [e for e in trace_reduce.load(TRACE)
              if e["line"] != trace_reduce.OPS_LINE or keep(e)]
    said = []
    return types.SimpleNamespace(
        ctx=types.SimpleNamespace(cell="no-such-cell"),
        trace=trace_reduce.reduce(events, 1),
        say=lambda **line: said.append(line), said=said)


def read(view):
    return bench_run.load_by_path(
        "layer_metrics", "fused_device_ms.ingest").read(view)


CASES = {
    "two-batches": (lambda e: True, 10.0, 2, 0.020),
    "one-batch": (lambda e: e["start_ns"] < BATCH_TWO_NS, 10.0, 1, 0.010),
    # a batch whose tag pass the trace's end cut off: its other device
    # time still counts, over the one batch that is whole
    "a-batch-cut-short": (lambda e: e["start_ns"] < BATCH_TWO_NS
                          or "_tags_3d" not in e["name"], 16.0, 1, 0.016),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_busy_ms_over_the_batches(case):
    keep, want, batches, busy_s = CASES[case]
    view = _view(keep)
    assert view.trace["busy_s"] == pytest.approx(busy_s)
    assert read(view) == pytest.approx(want)
    line = view.said[-1]
    assert line["info"] == "fused program"
    assert line["batches"] == batches
    assert line["busy_s"] == pytest.approx(busy_s)


def test_nothing_to_read_is_none():
    """An untraced run, and a trace that holds device work but no batch
    of the fused program (no kernel event, and no ``cess:stream.dispatch``
    span to fall back on: the view's cell has no trace directory)."""
    view = _view(lambda e: "_tags_3d" not in e["name"])
    assert view.trace["busy_s"] > 0
    assert read(view) is None and view.said == []
    view.trace = None
    assert read(view) is None


# -- linear_put_share.ingest -----------------------------------------------
def _stream(**kw):
    return {"stream": {**dict(batches=0, bytes_in=0, h2d_s=0.0,
                              wall_s=0.0), **kw}}


SHARES = {
    # counters before, after -> the share
    "every-batch-linear": (dict(batches=3, linear_puts=3, put_arrays=48),
                           dict(batches=1103, linear_puts=1103,
                                put_arrays=48 + 1100 * 16), 100.0),
    "some-packed": (dict(linear_puts=0, put_arrays=0),
                    dict(batches=8, linear_puts=6, put_arrays=96), 75.0),
    "none-linear": (dict(linear_puts=0, put_arrays=0),
                    dict(batches=8, linear_puts=0, put_arrays=0), 0.0),
}


@pytest.mark.parametrize("case", sorted(SHARES))
def test_linear_share_of_the_windows_batches(case):
    before, after, want = SHARES[case]
    said = []
    view = types.SimpleNamespace(
        counters_before=_stream(**before), counters_after=_stream(**after),
        say=lambda **line: said.append(line))
    got = bench_run.load_by_path(
        "layer_metrics", "linear_put_share.ingest").read(view)
    assert got == pytest.approx(want)
    assert said[-1]["info"] == "linear puts"
    assert said[-1]["batches"] == \
        after["batches"] - before.get("batches", 0)
    assert said[-1]["linear_puts"] == \
        after["linear_puts"] - before["linear_puts"]


@pytest.mark.parametrize("case", ["no-counter", "no-batch"])
def test_linear_share_with_nothing_to_read(case):
    """The parent's StreamStats has no ``linear_puts``; a window may hold
    no batch. Neither raises, neither prints."""
    after = dict(batches=9) if case == "no-counter" \
        else dict(linear_puts=5, put_arrays=80)
    view = types.SimpleNamespace(
        counters_before=_stream(**{k: v for k, v in after.items()
                                   if k != "batches"}),
        counters_after=_stream(**after),
        say=lambda **line: pytest.fail("nothing to say"))
    assert bench_run.load_by_path(
        "layer_metrics", "linear_put_share.ingest").read(view) is None


# -- both, where the cells' traced rehearsals read them --------------------
@pytest.mark.parametrize("cell", ["stream-4p8.corpus", "stream-2p1.corpus"])
def test_traced_rehearsal_reads_both(cell):
    rc, lines, err = run_cell("--workload", cell, "--rehearse",
                              "--trace", "1", "--seed", "43")
    assert rc == 0, err[-2000:]
    assert {"fused_device_ms.ingest", "linear_put_share.ingest",
            "stream_stall_share"} <= set(lines[-1]["metrics_read"])
    puts = next(x for x in lines if x.get("info") == "linear puts")
    assert puts["linear_puts"] == puts["batches"] > 0
    fused = next(x for x in lines if x.get("info") == "fused program")
    assert fused["batches"] > 0
