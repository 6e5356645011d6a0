"""``prove_operand_mib.audit``: the right MiB from a snapshot pair that
has the ``operand_bytes`` counter, None from one without it (the parent's
program), and its entry in BENCHMARK.json."""
import types

import pytest

import run as bench_run

NAME = "prove_operand_mib.audit"
MIB = 1 << 20


def _view(before, after):
    said = []
    return types.SimpleNamespace(
        counters_before=before, counters_after=after,
        say=lambda **line: said.append(line), said=said)


def _engine(batches, **prove):
    return {"engine": {"classes": {
        "prove": {"batches": batches, **prove},
        "verify": {"batches": batches, "operand_bytes": 9 * batches}}}}


def test_mib_per_prove_batch():
    read = bench_run.load_by_path("layer_metrics", NAME).read
    # 3 batches before the window, 43 after: 40 batches of 12.25 MiB
    per_batch = 12 * MIB + MIB // 4
    view = _view(_engine(3, operand_bytes=3 * per_batch),
                 _engine(43, operand_bytes=43 * per_batch))
    assert read(view) == pytest.approx(12.25)
    assert view.said == [{"info": "prove operands", "batches": 40,
                          "operand_bytes": 40 * per_batch}]


@pytest.mark.parametrize("before,after", [
    (_engine(3), _engine(43)),                          # no counter
    (_engine(3, operand_bytes=5), _engine(3, operand_bytes=5)),  # no batch
    ({"stream": {}}, {"stream": {}}),                   # no engine
    ({}, {}),
], ids=["parent", "idle", "stream-cell", "empty"])
def test_nothing_to_read_is_none(before, after):
    read = bench_run.load_by_path("layer_metrics", NAME).read
    assert read(_view(before, after)) is None


def test_declared_for_the_audit_cell_last():
    bench = bench_run.load_json(bench_run.ROOT, "BENCHMARK.json")
    entry = bench["per_layer"][-1]
    assert entry == {
        "name": NAME, "unit": "MiB", "better": "lower",
        "source": "program_counter",
        "layer": "submission engine (serve/engine.py)",
        "moves": "audit_rate", "workloads": ["audit-2p1.round"]}
