"""PR 56's reader ``verify_late_share.missions500``: its arithmetic on
made-up counters, ``None`` (and no exception) on a program without the
counter (the parent), and its entry in ``BENCHMARK.json``."""
import json
import os
import types

import pytest

import run as bench_run

NAME, CELL = "verify_late_share.missions500", "verify-2p1.missions500"


def _view(before, after):
    def snap(verify):
        return {"engine": {"classes": {"verify": verify}}}
    return types.SimpleNamespace(
        counters_before=snap(before), counters_after=snap(after), trace=None,
        ctx=types.SimpleNamespace(cell=CELL), say=lambda **line: None)


def read(view):
    return bench_run.load_by_path("layer_metrics", NAME).read(view)


@pytest.mark.parametrize("late,want", [(700, 100.0), (0, 0.0), (175, 25.0)])
def test_the_share_is_late_batches_over_batches(late, want):
    view = _view({"batches": 40, "late_proofs": 40},
                 {"batches": 740, "late_proofs": 40 + late})
    assert read(view) == pytest.approx(want)


def test_nothing_to_read_is_none():
    old = {"batches": 3, "device_calls": 24}        # the parent's snapshot
    assert read(_view(old, {"batches": 9, "device_calls": 72})) is None
    same = {"batches": 3, "late_proofs": 3}
    assert read(_view(same, same)) is None          # no batch in the window
    view = _view(same, same)
    view.counters_before = view.counters_after = {}
    assert read(view) is None
    view.counters_before = view.counters_after = None
    assert read(view) is None


def test_its_entry_is_the_last_and_names_the_one_cell():
    spec = json.load(open(os.path.join(bench_run.ROOT, "BENCHMARK.json")))
    entry = spec["per_layer"][-1]
    calls = next(m for m in spec["per_layer"]
                 if m["name"] == "verify_calls_per_round.missions500")
    assert entry == {"name": NAME, "unit": "%", "better": "higher",
                     "source": "program_counter", "layer": calls["layer"],
                     "moves": "audit_rate", "workloads": [CELL]}
