"""The cell ``stream-10p4.corpus`` (configuration ``archival-ingest``,
PR 47; traffic kind ``stream``, the driver of the two sibling cells,
unedited): rehearsed on the CPU end to end, traced and untraced; its two
controls come out not correct; and its new reader,
``stored_per_user_byte.ingest``, over recorded StreamStats counter pairs:
the fragments' share of ``bytes_out`` over ``bytes_in`` is (k + m) / k
exactly — 1.4 here, 3.0 and 1.5 in the siblings — and a program without
the counter (the parent) gives nothing to read."""
import json
import os
import types

import pytest

import run as bench_run
import test_run
from conftest import BENCH

CELL = "stream-10p4.corpus"
# test_run.py's own table cannot be edited from here; its check that
# every cell has controls reads the table when it runs
test_run.CONTROLS[CELL] = ["flip_parity", "stale_tags"]
READER = "stored_per_user_byte.ingest"


def _config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal(trace):
    rc, lines, err = test_run.run("--workload", CELL, "--rehearse",
                                  "--seed", str(2 ** 31 + 47), "--trace",
                                  str(trace))
    assert rc == 0, err[-2000:]
    last = lines[-1]
    assert last["rehearsal"] == "passed" and last["correct"] is True
    compares = [x for x in lines if "compare" in x]
    assert compares and all(c["ok"] and c["value"] == 0 for c in compares)
    run_line = next(x for x in lines if x.get("info") == "run")
    assert run_line["compiled_in_window"] == 0 and run_line["failed"] == 0
    check = next(x for x in lines if x.get("info") == "check")
    assert check["fragments_compared"] == 14 * len(check["batches_kept"])
    if trace:
        assert {READER, "fused_device_ms.ingest", "linear_put_share.ingest",
                "stream_stall_share"} <= set(last["metrics_read"])
        stored = next(x for x in lines if x.get("info") == "stored bytes")
        assert (stored["bytes_out"] - stored["tag_bytes"]) * 10 \
            == stored["bytes_in"] * 14
        puts = next(x for x in lines if x.get("info") == "linear puts")
        assert puts["put_arrays"] == puts["batches"] * 2 * 10
    else:
        assert set(last["metrics_read"]) == {"ingest_rate", "setup_s"}


@pytest.mark.parametrize("control", test_run.CONTROLS[CELL])
def test_broken_path_is_not_correct(control):
    rc, lines, err = test_run.run("--workload", CELL, "--rehearse",
                                  "--seed", "47", "--control", control)
    assert rc == 1, err[-2000:]
    assert lines[-1]["rehearsal"] == "FAILED"
    assert lines[-1]["correct"] is False


def test_the_widths_are_archival_wides_and_none_is_cut():
    wide, ingest = _config("archival-wide"), _config("archival-ingest")
    for key in ("k", "m", "segment_size", "fragment_size", "podr2_sectors",
                "podr2_limbs", "podr2_block_bytes", "podr2_key_seed",
                "blocks_per_fragment", "stored_bytes_per_user_byte",
                "rehearse"):
        assert ingest[key] == wide[key], key
    assert ingest["guarantees"] == _config("baseline-4p8")["guarantees"]
    assert set(ingest["reduced"]) == {"corpus_bytes", "chips", "chain",
                                      "fillers"}
    entry = next(c for c in test_run.SPEC["configs"]
                 if c["name"] == "archival-ingest")
    assert entry["reduced"] == list(ingest["reduced"])
    assert entry["source"] == ingest["source"] and len(entry["source"]) <= 200


# -- stored_per_user_byte.ingest over recorded counters --------------------
def _tag_bytes(c, segments):
    return segments * (c["k"] + c["m"]) * c["blocks_per_fragment"] \
        * c["podr2_limbs"] * 4


def _pair(c, warm, window):
    """StreamStats.raw() after ``warm`` and after ``warm + window``
    segments of a driver that counts both sides."""
    def at(segments):
        return {"stream": dict(
            segments=segments, bytes_in=segments * c["segment_size"],
            bytes_out=segments * (c["k"] + c["m"]) * c["fragment_size"]
            + _tag_bytes(c, segments))}
    return at(warm), at(warm + window)


def _read(config, before, after):
    said = []
    view = types.SimpleNamespace(
        ctx=types.SimpleNamespace(config=config),
        counters_before=before, counters_after=after,
        say=lambda **line: said.append(line))
    return bench_run.load_by_path("layer_metrics", READER).read(view), said


@pytest.mark.parametrize("name,want", [("archival-ingest", 1.4),
                                       ("baseline-4p8", 3.0),
                                       ("cess-protocol", 1.5)])
def test_the_codes_identity_as_a_count(name, want):
    c = _config(name)
    # the cell's own window: 3 warm batches of 8, then 580 batches
    got, said = _read(c, *_pair(c, 24, 580 * 8))
    assert got == want                      # exactly, not approximately
    assert c["stored_bytes_per_user_byte"] == want
    line = said[-1]
    assert line["info"] == "stored bytes"
    assert line["bytes_in"] == 580 * 8 * c["segment_size"]
    assert line["tag_bytes"] == _tag_bytes(c, 580 * 8)


def test_a_lost_row_shows():
    """A program that wrote 13 of a segment's 14 rows once reads low."""
    c = _config("archival-ingest")
    before, after = _pair(c, 24, 80)
    after["stream"]["bytes_out"] -= c["fragment_size"]
    got, _ = _read(c, before, after)
    assert got == pytest.approx(1.4 - 1 / 800) and got != 1.4


@pytest.mark.parametrize("case", ["no-counter", "no-batch"])
def test_nothing_to_read_is_none(case):
    """The parent's StreamStats has no ``bytes_out``; a window may hold
    no batch. Neither raises, neither prints."""
    c = _config("archival-ingest")
    before, after = _pair(c, 24, 0 if case == "no-batch" else 80)
    if case == "no-counter":
        del before["stream"]["bytes_out"], after["stream"]["bytes_out"]
    got, said = _read(c, before, after)
    assert got is None and said == []
