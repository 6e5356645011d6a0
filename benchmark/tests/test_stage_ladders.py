"""PR 54's ten readers: a stage's distribution, the streamed window's
long waits and its remainder, the pooled put's three calls, and the
device's idle time by cause.

The arithmetic of ``stage_ladders.py`` on two scripted snapshots whose
numbers are known by hand, and of ``stream_pairing.py`` on a small
recorded trace (data/stream_seq_trace.textproto says how its numbers come
about) — by the stalls that waited, as the trace has no ``run_id``, and by
``run_id`` once the events are given one; each reader through
``run.load_by_path`` on a hand-built view: its value, and ``None`` where
the program keeps nothing to read (the parent of PR 54)."""
import copy
import json
import os
import types

import pytest

import program_spans
import stage_ladders
import stream_pairing
import trace_reduce

import run as bench_run

TRACE = os.path.join(os.path.dirname(__file__), "data",
                     "stream_seq_trace.textproto")
SPEC = json.load(open(os.path.join(bench_run.ROOT, "BENCHMARK.json")))
REPAIR_CELLS = ["repair-2p1.single", "repair-10p4.helpers",
                "repair-10p4.lowest", "restore-10p4.symbols",
                "restore-10p4.fragments", "repair-4p8.erasure4"]
STREAM_CELLS = ["stream-4p8.corpus", "stream-4p8.pool4",
                "stream-2p1.corpus", "stream-10p4.corpus"]
NEW = {
    "engine_wait_p95_ms.repair": ("repair_p95_ms", REPAIR_CELLS),
    "engine_fetch_p95_ms.repair": ("repair_p95_ms", REPAIR_CELLS),
    "engine_wake_p95_ms.repair": ("repair_p95_ms", REPAIR_CELLS),
    "engine_handoff_p95_ms.repair": ("repair_p95_ms", REPAIR_CELLS),
    "stream_long_wait_s.ingest": ("ingest_rate", STREAM_CELLS),
    "stream_unaccounted_share.ingest": ("ingest_rate", STREAM_CELLS),
    "pool_put_slice_ms.pool4": ("ingest_rate", ["stream-4p8.pool4"]),
    "pool_put_place_ms.pool4": ("ingest_rate", ["stream-4p8.pool4"]),
    "pool_put_assemble_ms.pool4": ("ingest_rate", ["stream-4p8.pool4"]),
    "stream_operand_wait_share.ingest": (
        "ingest_rate", [c for c in STREAM_CELLS if c != "stream-4p8.pool4"]),
}


def read(name, view):
    return bench_run.load_by_path("layer_metrics", name).read(view)


def _view(before=None, after=None, trace=None, events=None):
    said = []
    view = types.SimpleNamespace(
        ctx=types.SimpleNamespace(cell="no-such-cell", window_t0=100.0),
        trace=trace, counters_before=before or {},
        counters_after=after or {},
        say=lambda **line: said.append(line), said=said)
    if events is not None:          # as spans() would have loaded them
        setattr(view, program_spans._KEY, program_spans.from_events(
            events, trace["window_ns"]))
    return view


def test_the_ten_are_declared_for_their_cells():
    by_name = {m["name"]: m for m in SPEC["per_layer"]}
    for name, (moves, cells) in NEW.items():
        assert by_name[name]["moves"] == moves, name
        assert by_name[name]["workloads"] == cells, name
    # repair_p95_ms had no per-layer reading before these four
    assert sorted(n for n, m in by_name.items()
                  if m["moves"] == "repair_p95_ms") == sorted(
        n for n in NEW if n.startswith("engine_"))


# -- two scripted snapshots ---------------------------------------------------
# the ladder's bounds that matter here (upper, inclusive); None: above 10 s
BEFORE = [[0.004, 10, 0.035], [0.25, 1, 0.2], [None, 1, 12.0]]
AFTER = [[0.002, 60, 0.09],              # new bucket: 60 x 1.5 ms
         [0.004, 40, 0.125],             # + 30 x 3 ms
         [0.0044, 8, 0.0336],            # new: 8 x 4.2 ms
         [0.25, 1, 0.2],                 # nothing new: left out
         [0.302, 2, 0.56],               # new: two waits of 0.28 s
         [None, 1, 12.0]]


def test_window_is_the_difference_bucket_by_bucket():
    got = stage_ladders.window(BEFORE, AFTER)
    assert [(le, n) for le, n, _ in got] == [
        (0.002, 60), (0.004, 30), (0.0044, 8), (0.302, 2)]
    assert [s for _, _, s in got] == pytest.approx(
        [0.09, 0.09, 0.0336, 0.56])
    assert stage_ladders.count(got) == 100
    # ranks 1-60 | 61-90 | 91-98 | 99-100: the bucket's own mean
    assert stage_ladders.percentile_s(got, 0.50) == pytest.approx(0.0015)
    assert stage_ladders.percentile_s(got, 0.90) == pytest.approx(0.003)
    assert stage_ladders.percentile_s(got, 0.95) == pytest.approx(0.0042)
    assert stage_ladders.percentile_s(got, 0.99) == pytest.approx(0.28)
    assert stage_ladders.seconds_over(got, 0.25) == pytest.approx(0.56)
    assert stage_ladders.seconds_over(BEFORE, 0.25) == 12.0
    assert stage_ladders.window(AFTER, AFTER) == []
    assert stage_ladders.percentile_s([], 0.95) is None


def _engine(buckets_of: dict) -> dict:
    account = lambda b: {"n": sum(n for _, n, _ in b),   # noqa: E731
                         "s": sum(s for _, _, s in b), "buckets": b}
    return {"engine": {"classes": {"repair": {
        "stages": {"wait": account(buckets_of["wait"]),
                   "fetch": account(buckets_of["fetch"])},
        "queue": {"wake": account(buckets_of["wake"])},
        "caller": {"handoff": account(buckets_of["handoff"])}}}}}


@pytest.mark.parametrize("name,account", [
    ("engine_wait_p95_ms.repair", "wait"),
    ("engine_fetch_p95_ms.repair", "fetch"),
    ("engine_wake_p95_ms.repair", "wake"),
    ("engine_handoff_p95_ms.repair", "handoff")])
def test_an_engine_p95_reads_its_own_account(name, account):
    quiet = [[0.001, 5, 0.004]]
    before = _engine(dict.fromkeys(("wait", "fetch", "wake", "handoff"),
                                   quiet))
    mine = dict.fromkeys(("wait", "fetch", "wake", "handoff"), quiet)
    mine[account] = AFTER
    before["engine"]["classes"]["repair"][
        {"wait": "stages", "fetch": "stages", "wake": "queue",
         "handoff": "caller"}[account]][account]["buckets"] = BEFORE
    view = _view(before, _engine(mine))
    assert read(name, view) == pytest.approx(4.2)
    (line,) = view.said
    assert line["info"] == "stage ladder" and line["n"] == 100
    assert line["p50_ms"] == pytest.approx(1.5)
    assert line["mean_ms"] == pytest.approx(1e3 * 0.7736 / 100)
    assert line["longest_bucket_ms"] == pytest.approx(280.0)
    # the other three accounts stood still: nothing to read there
    others = [n for n in NEW if n.startswith("engine_") and n != name]
    assert [read(n, _view(before, _engine(mine))) for n in others] \
        == [None] * 3


def test_the_parents_counters_give_the_engine_readers_nothing():
    old = {"engine": {"classes": {"repair": {
        "stages": {"wait": {"n": 3, "s": 0.1}, "fetch": {"n": 3, "s": 0.1}},
        "queue": {"wake": {"n": 3, "s": 0.1}},
        "caller": {"handoff": {"n": 3, "s": 0.1}}}}}}
    for name in NEW:
        if name.startswith("engine_"):
            assert read(name, _view(old, old)) is None, name
            assert read(name, _view({}, {})) is None, name


def _stream(stall, gate, long_waits=(), **scalars) -> dict:
    account = lambda b: {"n": sum(n for _, n, _ in b),   # noqa: E731
                         "s": sum(s for _, _, s in b), "buckets": b}
    quiet = [[0.001, 4, 0.002]]
    return {"stream": {
        "stages": {"stream.stall": account(stall),
                   "stream.gate": account(gate),
                   "stream.stage": account(quiet),
                   "stream.put": account(quiet),
                   "stream.dispatch": account(quiet)},
        "long_waits": list(long_waits), **scalars}}


def test_stream_long_wait_s_is_the_seconds_above_the_bound():
    waits = [{"stage": "stream.stall", "start": 99.0, "seconds": 12.0},
             {"stage": "stream.stall", "start": 104.0, "seconds": 0.28,
              "seq": 41, "results_in_flight": 2, "puts_in_flight": 3},
             {"stage": "stream.gate", "start": 110.0, "seconds": 1.9,
              "seq": 97, "results_in_flight": 2, "puts_in_flight": 2}]
    gate_a, gate_b = [[0.004, 9, 0.02]], [[0.004, 30, 0.07],
                                          [2.04, 1, 1.9]]
    view = _view(_stream(BEFORE, gate_a),
                 _stream(AFTER, gate_b, waits))
    assert read("stream_long_wait_s.ingest", view) == pytest.approx(
        0.56 + 1.9)
    (line,) = view.said
    assert line["seconds_over"] == {"stream.stall": pytest.approx(0.56),
                                    "stream.gate": pytest.approx(1.9)}
    # the wait kept from before the window (start 99 < 100) is not its
    assert [w["seq"] for w in line["long_waits"]] == [41, 97]
    sound = _view(_stream(BEFORE, gate_a), _stream(BEFORE, gate_a))
    assert read("stream_long_wait_s.ingest", sound) == 0.0


def test_stream_unaccounted_share_is_the_walls_remainder():
    a = dict(wall_s=10.0, stage_s=0.1, gate_s=1.0, h2d_s=0.5, stall_s=2.0,
             dispatch_s=0.4, consumer_s=0.5, batches=100)
    b = dict(wall_s=40.0, stage_s=0.4, gate_s=10.0, h2d_s=2.0,
             stall_s=11.0, dispatch_s=1.3, consumer_s=2.45, batches=3100)
    view = _view(_stream(BEFORE, BEFORE, **a), _stream(AFTER, AFTER, **b))
    # 30 s of wall; the parts: .3 + 9 + 1.5 + 9 + .9 + 1.95 = 22.65
    assert read("stream_unaccounted_share.ingest", view) \
        == pytest.approx(100.0 * 7.35 / 30.0)
    (line,) = view.said
    assert line["share"]["consumer_s"] == pytest.approx(6.5)
    assert line["ms_per_batch"]["gate_s"] == pytest.approx(3.0)
    assert sum(line["share"].values()) == pytest.approx(75.5)


def test_the_parents_stream_counters_give_nothing():
    old = {"stream": {"batches": 5, "wall_s": 3.0, "stall_s": 1.0,
                      "h2d_s": 0.1, "dispatch_s": 0.1, "gate_s": 0.2}}
    new = {"stream": dict(old["stream"], batches=9, wall_s=6.0)}
    for name in ("stream_long_wait_s.ingest",
                 "stream_unaccounted_share.ingest"):
        assert read(name, _view(old, new)) is None, name


# -- the recorded trace -------------------------------------------------------
@pytest.fixture()
def traced():
    events = trace_reduce.load(TRACE)
    return events, trace_reduce.reduce(events, 1)


def test_the_pooled_puts_three_calls_are_means_a_put(traced):
    events, summary = traced
    for name, want in (("pool_put_slice_ms.pool4", 0.015),
                       ("pool_put_place_ms.pool4", 0.435),
                       ("pool_put_assemble_ms.pool4", 0.140)):
        view = _view(trace=summary, events=events)
        assert read(name, view) == pytest.approx(want), name
    # a program whose put is one span (the parent): nothing to read
    old = [e for e in events if not e["name"].startswith("cess:stream.put.")]
    for name in NEW:
        if name.startswith("pool_put_"):
            assert read(name, _view(trace=summary, events=old)) is None
            assert read(name, _view()) is None       # an untraced run


def test_idle_by_cause_paired_by_the_stalls_that_waited(traced):
    events, summary = traced
    got = stream_pairing.reduce(events, summary)
    assert got["pairing"] == "stall" and got["paired"] == got["runs"] == 3
    assert got["idle_s"] == pytest.approx(16000e-6)
    assert got["operand_wait_s"] == pytest.approx(5000e-6)
    assert got["host_late_s"] == pytest.approx(11000e-6)


def test_idle_by_cause_paired_by_the_gates_or_the_order_alone(traced):
    """No stall waited (a stream the link paces): a gate of batch 8 that
    waited until 16000 us, where the device stood idle and run 1 started,
    says run 1 is batch 6; with no anchor at all the runs go to the last
    dispatches that began before them."""
    events, summary = traced
    quiet = [e for e in copy.deepcopy(events)
             if e["name"] != "cess:stream.stall"]
    gate = {"plane": "/host:CPU", "line": "python3",
            "name": "cess:stream.gate", "start_ns": 14.2e6,
            "dur_ns": 1.85e6, "stats": {"seq": 8}}
    got = stream_pairing.reduce(quiet + [gate], summary)
    assert got["pairing"] == "gate" and got["paired"] == 3
    assert got["operand_wait_s"] == pytest.approx(5000e-6)
    got = stream_pairing.reduce(quiet, summary)
    assert got["pairing"] == "order" and got["paired"] == 3
    assert got["operand_wait_s"] == pytest.approx(5000e-6)


def test_idle_by_cause_paired_by_run_id(traced):
    """The same trace once the runtime says which execution is which: the
    device's events and a launch event inside each dispatch share a
    ``run_id``; the stalls are not needed (and, shifted, would mislead)."""
    events, _ = traced
    events = copy.deepcopy(events)
    launches = []
    for e in events:
        if e["line"] == trace_reduce.OPS_LINE:
            e["stats"]["run_id"] = 900 + int(e["start_ns"] // 12e6)
        elif e["name"] == "cess:stream.dispatch":
            launches.append({
                "plane": e["plane"], "line": e["line"],
                "name": "Executable::Launch", "start_ns": e["start_ns"] + 50,
                "dur_ns": 100,
                "stats": {"run_id": 900 + e["stats"]["seq"] - 5}})
        elif e["name"] == "cess:stream.stall":
            e["start_ns"] -= 5e6
    # runs start at 2, 16 and 24 ms: 900, 901, 902
    got = stream_pairing.reduce(events + launches,
                                trace_reduce.reduce(events, 1))
    assert got["pairing"] == "run_id" and got["paired"] == 3
    assert got["operand_wait_s"] == pytest.approx(5000e-6)


def test_run_ids_of_other_threads_do_not_pair(traced):
    """The runtime's other threads carry run ids too, and one of their
    events falls inside some batch's dispatch by chance (31 of 417 runs
    in the first chip trace, which read 13.7% where the order reads
    99.7%): only the dispatch's own thread counts, and pairs that do not
    cover the runs are dropped for the order."""
    events, _ = traced
    events = copy.deepcopy(events)
    for e in events:
        if e["line"] == trace_reduce.OPS_LINE:
            e["stats"]["run_id"] = 900 + int(e["start_ns"] // 12e6)
    first = min((e for e in events if e["name"] == "cess:stream.dispatch"),
                key=lambda e: e["start_ns"])
    stray = {"plane": first["plane"], "line": "pjrt-tpu-tasks/7",
             "name": "Execute", "start_ns": first["start_ns"] + 50,
             "dur_ns": 100, "stats": {"run_id": 902}}
    summary = trace_reduce.reduce(events, 1)
    got = stream_pairing.reduce(events + [stray], summary)
    assert got["pairing"] == "stall" and got["paired"] == 3
    assert got["operand_wait_s"] == pytest.approx(5000e-6)
    # on the dispatch's own thread, but one pair of three: not enough
    stray["line"] = first["line"]
    got = stream_pairing.reduce(events + [stray], summary)
    assert got["pairing"] == "stall" and got["paired"] == 3


def test_nothing_without_seq_or_a_trace(traced):
    events, summary = traced
    parent = copy.deepcopy(events)
    for e in parent:
        e["stats"].pop("seq", None)
    assert stream_pairing.reduce(parent, summary) is None
    assert stream_pairing.reduce(events, None) is None
    assert read("stream_operand_wait_share.ingest", _view()) is None


def test_the_share_through_its_reader(traced, tmp_path, monkeypatch):
    """Through the reader's own loading: the trace laid where run.py
    leaves a cell's."""
    from jax.profiler import ProfileData

    where = tmp_path / ".bench_trace" / "no-such-cell" / "plugins" \
        / "profile" / "r"
    where.mkdir(parents=True)
    with open(TRACE) as f:
        (where / "host.xplane.pb").write_bytes(
            ProfileData.text_proto_to_serialized_xspace(f.read()))
    monkeypatch.setattr(program_spans, "ROOT", str(tmp_path))
    view = _view(trace=traced[1])
    assert read("stream_operand_wait_share.ingest", view) \
        == pytest.approx(31.25)
    assert view.said[-1]["info"] == "device idle by cause"
    assert view.said[-1]["host_late_s"] == pytest.approx(0.011)
