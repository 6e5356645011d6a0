"""The cells ``repair-10p4.helpers`` (traffic kind ``repair_helpers``,
configuration ``archival-wide``) and ``stream-2p1.corpus``: the controls of
both come out not correct; a window too short to draw a second lost row
still has a multi-row repair compared with the reference (forced in the
check); and the readers of the archival cell's counters on a recorded
fixture (data/helpers_counters.json says how it was recorded) and on a
program without the counters, where each returns ``None`` and raises
nothing."""
import copy
import json
import os
import types

import pytest

import run as bench_run
import test_run

HELPERS, STREAM = "repair-10p4.helpers", "stream-2p1.corpus"
# test_run.py's own table cannot be edited from here; its check that every
# cell has controls reads the table when it runs
test_run.CONTROLS[HELPERS] = ["flip_byte", "wrong_helpers"]
test_run.CONTROLS[STREAM] = ["flip_parity", "stale_tags"]
FIXTURE = os.path.join(os.path.dirname(__file__), "data",
                       "helpers_counters.json")
# the stages the (2,1) repair cell already had readers for are read by
# those (``.repair``: they take k and the sizes from the configuration);
# ``.helpers`` are the readers this cell brought
COUNTER_READERS = ("engine_assemble_ms.helpers", "engine_wait_ms.repair",
                   "engine_fetch_ms.repair", "engine_queue_ms.repair",
                   "matrix_build_ms.helpers", "new_pattern_share.helpers",
                   "programs_built_per_repair.helpers")


@pytest.mark.parametrize("cell,control", [
    (c, k) for c in (HELPERS, STREAM) for k in test_run.CONTROLS[c]])
def test_broken_path_is_not_correct(cell, control):
    rc, lines, err = test_run.run("--workload", cell, "--rehearse",
                                  "--seed", "31", "--control", control)
    assert rc == 1, err[-2000:]
    assert lines[-1]["rehearsal"] == "FAILED"
    assert lines[-1]["correct"] is False


def test_wrong_helpers_fails_by_the_hashes():
    rc, lines, _ = test_run.run("--workload", HELPERS, "--rehearse",
                                "--seed", "32", "--control", "wrong_helpers")
    assert rc == 1
    bad = {c["compare"]: c for c in lines if "compare" in c and not c["ok"]}
    assert any("SHA-256" in what for what in bad)
    assert any("plain reference" in what for what in bad)
    # the engine itself did not fail: it answered another question
    assert not any("engine failed" in what for what in bad)


def test_a_short_window_forces_a_multi_row_repair_into_the_check():
    rc, lines, err = test_run.run("--workload", HELPERS, "--rehearse",
                                  "--seed", str(2 ** 31 + 31), "--seconds",
                                  "0.02")
    assert rc == 0, err[-2000:]
    check = next(x for x in lines if x.get("info") == "check")
    if check["multi_loss_forced"]:
        assert len(check["kept"][-1][1]) >= 2
    assert any(len(lost) >= 2 for _, lost in check["kept"])
    assert check["rows_compared"] >= 3
    assert lines[-1]["correct"] is True


def test_loss_mix_and_helpers_are_the_sources():
    conf = json.load(open(os.path.join(
        test_run.BENCH, "configs", "archival-wide.json")))
    assert (conf["k"], conf["m"]) == (10, 4)
    assert conf["segment_size"] == 10 * conf["fragment_size"] == 80 << 20
    assert conf["loss_mix"] == {"1": 0.9808, "2": 0.0187, "3": 0.0005}
    assert sum(conf["loss_mix"].values()) == pytest.approx(1.0)
    cell = json.load(open(os.path.join(
        test_run.BENCH, "workloads", HELPERS + ".json")))
    assert cell["traffic"]["helpers"] == conf["k"]
    assert cell["traffic"]["pool_segments"] == 4
    # who answers has no source: the cell's draw is the stated worst case
    assert cell["traffic"]["answering"] == "uniform"
    assert "worst case" in conf["assumed"]["helpers"]
    bench = json.load(open(os.path.join(test_run.ROOT, "BENCHMARK.json")))
    for m in bench["per_layer"]:
        if m["name"].endswith(".repair") and m["name"] != \
                "engine_p50_ms.repair":
            assert m["workloads"] == ["repair-2p1.single", HELPERS]
    stream = json.load(open(os.path.join(
        test_run.BENCH, "workloads", STREAM + ".json")))
    proto = json.load(open(os.path.join(
        test_run.BENCH, "workloads", "stream-4p8.corpus.json")))
    assert stream["traffic"] == proto["traffic"]
    assert stream["rehearse"] == proto["rehearse"]
    assert stream["config"] == "cess-protocol"


# -- the counter readers on the recorded fixture ---------------------------
@pytest.fixture
def view():
    rec = json.load(open(FIXTURE))
    return types.SimpleNamespace(
        counters_before=rec["before"], counters_after=rec["after"],
        say=lambda **line: None)


def read(name, view):
    return bench_run.load_by_path("layer_metrics", name).read(view)


def test_counter_readers_on_the_recording(view):
    a = view.counters_before["engine"]["classes"]["repair"]
    b = view.counters_after["engine"]["classes"]["repair"]
    assert b["completed"] - a["completed"] == b["batches"] - a["batches"] \
        == 21
    # 20 of the 21 patterns were new to the codec; none built a program
    assert read("new_pattern_share.helpers", view) \
        == pytest.approx(100 * 20 / 21)
    assert read("programs_built_per_repair.helpers", view) == 0
    build_ms = read("matrix_build_ms.helpers", view)
    assert build_ms == pytest.approx(
        1e3 * (b["matrix_build_s"] - a["matrix_build_s"]) / 21)
    assert 0 < build_ms < 5
    stages = {k: 1e3 * (b["stages"][k]["s"] - a["stages"][k]["s"]) / 21
              for k in b["stages"]}
    assert read("engine_assemble_ms.helpers", view) \
        == pytest.approx(stages["assemble"])
    assert read("engine_wait_ms.repair", view) \
        == pytest.approx(stages["dispatch"] + stages["wait"])
    assert read("engine_fetch_ms.repair", view) \
        == pytest.approx(stages["fetch"])
    assert read("engine_queue_ms.repair", view) \
        == pytest.approx(stages["queue"])
    # the matrix is built inside dispatch: its time is part of that stage
    assert build_ms < stages["dispatch"]


def test_a_program_per_pattern_reads_one_program_a_repair(view):
    """What the parent does: every new pattern builds a program."""
    view.counters_after = copy.deepcopy(view.counters_after)
    view.counters_after["engine"]["programs_built"] += 20
    assert read("programs_built_per_repair.helpers", view) \
        == pytest.approx(20 / 21)


def test_without_the_counters_every_reader_returns_none(view):
    """A program from before PR 31 has no ``patterns_new`` /
    ``matrix_build_s``; one from before PR 25 no ``stages``; a cell that
    drove no engine no ``engine`` at all."""
    for snap in (view.counters_before, view.counters_after):
        for key in ("patterns_new", "matrix_build_s"):
            del snap["engine"]["classes"]["repair"][key]
    assert read("new_pattern_share.helpers", view) is None
    assert read("matrix_build_ms.helpers", view) is None
    assert read("programs_built_per_repair.helpers", view) == 0
    assert read("engine_fetch_ms.repair", view) is not None
    for snap in (view.counters_before, view.counters_after):
        del snap["engine"]["classes"]["repair"]["stages"]
    for name in COUNTER_READERS[:4]:
        assert read(name, view) is None
    view.counters_before = view.counters_after = {}
    for name in COUNTER_READERS:
        assert read(name, view) is None


def test_no_repair_in_the_window_is_none(view):
    view.counters_after = view.counters_before
    for name in COUNTER_READERS:
        assert read(name, view) is None


def test_span_and_trace_readers_without_anything_to_read():
    spans = types.SimpleNamespace(records=[("repair.stack_survivors",
                                            1.0, 1.5)])
    view = types.SimpleNamespace(
        spans=spans, trace=None, say=lambda **line: None,
        ctx=types.SimpleNamespace(
            window_t0=2.0, cell=HELPERS, device_kind="TPU v5 lite",
            config={"k": 10, "fragment_size": 8 << 20}))
    assert read("stack_ms.helpers", view) is None      # before the window
    assert read("rs_kernel_roofline.repair", view) is None
    assert read("wait_device_share.repair", view) is None
    spans.records += [("repair.stack_survivors", 2.0, 2.010),
                      ("repair.stack_survivors", 3.0, 3.020),
                      ("engine.reconstruct", 3.1, 3.2)]
    assert read("stack_ms.helpers", view) == pytest.approx(15.0)


def test_the_two_ways_of_answering():
    """``uniform``: the cell's worst case, nearly every pattern of a
    window new; ``lowest``: MinerAgent.try_repair's order, at most one
    pattern a set of lost rows."""
    import numpy as np

    driver = bench_run.load_by_path("traffic", "repair_helpers")
    ctx = types.SimpleNamespace(
        config={"k": 10}, rows=14, pool=np.zeros((4, 14, 1), np.uint8),
        losses=[1, 2, 3], loss_p=np.array([0.9808, 0.0187, 0.0005]),
        traffic={"answering": "uniform"})
    rng = np.random.default_rng(31)
    seen = {driver.draw(ctx, rng)[1:] for _ in range(400)}
    assert len(seen) > 350
    ctx.traffic["answering"] = "lowest"
    seen = {driver.draw(ctx, rng)[1:] for _ in range(400)}
    assert 14 <= len(seen) < 14 + 20
    for helpers, lost in seen:
        assert helpers == tuple(
            j for j in range(14) if j not in lost)[:10]


def test_roofline_work_is_one_row_from_ten():
    import kernel_work

    work = kernel_work.rs_apply(10, 1, 8 << 20, 1)
    assert work["bytes"] == 11 * (8 << 20)           # 88 MiB of traffic
    least, bound = kernel_work.least_seconds(work, "TPU v5 lite")
    assert bound == "hbm" and least == pytest.approx(0.1127e-3, rel=1e-3)
    # two or three lost rows move and compute more: reckoned as one row,
    # their share is understated, never over 100% on their account
    for r in (2, 3):
        more = kernel_work.rs_apply(10, r, 8 << 20, 1)
        assert kernel_work.least_seconds(more, "TPU v5 lite")[0] > least
