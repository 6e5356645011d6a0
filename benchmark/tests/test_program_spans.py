"""The program's ``cess:`` spans and stage counters as the per-layer
readers take them: sums, device time inside spans and innermost-span idle
attribution across two host threads on a small recorded trace whose numbers
are known by hand (data/stage_trace.textproto says how), and each reader on
a hand-built view — its value, and None where there is nothing to read."""
import os
import types

import pytest

import program_spans
import trace_reduce

import run as bench_run

TRACE = os.path.join(os.path.dirname(__file__), "data",
                     "stage_trace.textproto")
US = 1e-6


def _view(cell="a-cell", trace=None, before=None, after=None):
    said = []
    view = types.SimpleNamespace(
        ctx=types.SimpleNamespace(cell=cell), trace=trace,
        counters_before=before or {}, counters_after=after or {},
        say=lambda **line: said.append(line), said=said)
    return view


@pytest.fixture()
def view(tmp_path, monkeypatch):
    """A traced run's view: the recorded trace laid where run.py leaves a
    cell's, so that ``spans`` goes through its own loading."""
    from jax.profiler import ProfileData

    where = tmp_path / ".bench_trace" / "a-cell" / "plugins" / "profile" / "r"
    where.mkdir(parents=True)
    with open(TRACE) as f:
        (where / "host.xplane.pb").write_bytes(
            ProfileData.text_proto_to_serialized_xspace(f.read()))
    monkeypatch.setattr(program_spans, "ROOT", str(tmp_path))
    return _view(trace=trace_reduce.reduce(trace_reduce.load(TRACE), 1))


def test_spans_inside_the_window(view):
    got = program_spans.spans(view)
    assert view.trace["window_ns"] == (pytest.approx(100e3),
                                       pytest.approx(1100e3))
    # 13 cess: spans start inside the window; the early gateway.hash
    # (20-80) does not, and bench:/Pjit events are not the program's
    assert len(got) == 13
    assert [n for n, _, _ in got][:3] == [
        "offchain.upload", "gateway.encode", "engine.repair.batch"]
    # the late resolve is clipped to the window's end
    assert [(a, b) for n, a, b in got if n == "engine.prove.resolve"] \
        == [(pytest.approx(1090e3), pytest.approx(1100e3))]
    assert program_spans.spans(view) is got          # kept on the view


def test_sums_by_name(view):
    assert program_spans.total(view, "gateway.hash") \
        == (pytest.approx(200 * US), 1)
    assert program_spans.total(view, "engine.repair.wait") \
        == (pytest.approx(140 * US), 1)
    assert program_spans.total(view, "engine.tag.wait") is None
    (part, n), (whole, uploads) = program_spans.inside(
        view, "gateway.fetch", "offchain.upload")
    assert (part, n) == (pytest.approx(30 * US), 1)
    assert (whole, uploads) == (pytest.approx(900 * US), 1)
    assert program_spans.inside(view, "gateway.hash", "no.such") is None


def test_device_busy_inside_spans(view):
    d = program_spans.device_inside(
        view, ("engine.repair.dispatch", "engine.repair.wait"))
    assert d == {"busy_s": pytest.approx(100 * US), "events": 1,
                 "span_s": pytest.approx(190 * US), "spans": 2}
    d = program_spans.device_inside(
        view, ("engine.prove.batch", "engine.verify.batch"))
    # the while (%c) and the operation it holds (%d) both start inside;
    # busy is their union
    assert d == {"busy_s": pytest.approx(120 * US), "events": 2,
                 "span_s": pytest.approx(290 * US), "spans": 2}
    assert program_spans.device_inside(view, ("engine.tag.batch",)) is None


def test_idle_goes_to_the_innermost_span_across_threads(view):
    gaps = dict(program_spans.idle_by_stage(view))
    assert gaps == pytest.approx({
        program_spans.OUTSIDE: 90 * US, "gateway.encode": 70 * US,
        "engine.repair.batch": 40 * US, "engine.repair.dispatch": 50 * US,
        "engine.repair.wait": 40 * US, "gateway.hash": 180 * US,
        "gateway.tag": 60 * US, "engine.prove.batch": 30 * US,
        "engine.prove.dispatch": 50 * US, "engine.prove.wait": 80 * US,
        "engine.verify.batch": 10 * US, "gateway.fetch": 30 * US,
        "offchain.upload": 10 * US, "engine.prove.resolve": 10 * US})
    assert sum(gaps.values()) == pytest.approx(
        view.trace["window_s"] - view.trace["busy_s"])
    # the first load says it, once, largest first
    program_spans.spans(view)
    lines = [s for s in view.said if s["info"] == "idle by program stage"]
    assert len(lines) == 1
    assert lines[0]["idle_gaps"][0] == ["gateway.hash",
                                        pytest.approx(180 * US)]


def _stages(**seconds):
    return {s: {"n": 1, "s": float(seconds.get(s, 0.0))}
            for s in ("queue", "assemble", "dispatch", "wait", "fetch",
                      "resolve")}


def _engine(**classes):
    return {"engine": {"classes": {
        cls: {"batches": b, "completed": c, "stages": st}
        for cls, (b, c, st) in classes.items()}}}


COUNTERS = dict(
    before=_engine(repair=(2, 2, _stages(queue=1.0, wait=1.0)),
                   prove=(1, 1, _stages(dispatch=1.0)),
                   verify=(1, 1, _stages())),
    after=_engine(
        repair=(12, 12, _stages(queue=1.02, dispatch=0.1, wait=1.3,
                                fetch=0.05)),
        prove=(5, 5, _stages(assemble=0.4, dispatch=2.0, wait=0.2)),
        verify=(5, 5, _stages(assemble=0.1, dispatch=1.5, wait=0.8))))

# reader -> the value it reads off the recorded trace and COUNTERS
EXPECTED = {
    "engine_queue_ms.repair": 2.0,              # 0.02 s / 10 completed
    "engine_wait_ms.repair": 40.0,              # (0.1 + 0.3) s / 10 batches
    "engine_fetch_ms.repair": 5.0,
    "wait_device_share.repair": 100 * 100 / 190,
    # prove + verify in the window: assemble 0.5, dispatch 2.5, wait 1.0
    "engine_assemble_share.audit": 12.5,
    "engine_dispatch_share.audit": 62.5,
    "device_ops_per_round.audit": 2.0,          # %c, %d / 1 prove batch
    "gateway_hash_share": 100 * 200 / 900,
    "gateway_fetch_share": 100 * 30 / 900,
}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_value(view, name):
    view.counters_before = COUNTERS["before"]
    view.counters_after = COUNTERS["after"]
    got = bench_run.load_by_path("layer_metrics", name).read(view)
    assert got == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_reads_nothing_from_an_old_program(name, tmp_path,
                                                  monkeypatch):
    """The parent's program: no cess: events in the trace, no ``stages``
    in the counters, or no trace at all — None, never an error."""
    monkeypatch.setattr(program_spans, "ROOT", str(tmp_path))
    old = {"engine": {"classes": {c: {"batches": 3, "completed": 3}
                                  for c in ("repair", "prove", "verify")}}}
    read = bench_run.load_by_path("layer_metrics", name).read
    assert read(_view(before=old, after=old)) is None
    bare = [e for e in trace_reduce.load(TRACE)
            if not e["name"].startswith(program_spans.PREFIX)]
    traced = _view(trace=trace_reduce.reduce(bare, 1), before=old,
                   after=old)
    setattr(traced, program_spans._KEY,
            program_spans.from_events(bare, traced.trace["window_ns"])
            or None)
    assert read(traced) is None
    # a stream cell: no engine in its counters at all
    assert read(_view(before={"stream": {}}, after={"stream": {}})) is None


def test_every_new_metric_is_declared_for_its_cell():
    bench = bench_run.load_json(bench_run.ROOT, "BENCHMARK.json")
    declared = {m["name"]: m for m in bench["per_layer"]}
    for name in EXPECTED:
        m = declared[name]
        cell = {"repair": "repair-2p1.single", "audit": "audit-2p1.round"
                }.get(name.rpartition(".")[2], "upload-2p1.files")
        assert m["workloads"] == [cell]
        moves = next(e for e in bench["end_to_end"]
                     if e["name"] == m["moves"])
        assert cell in moves["workloads"]
