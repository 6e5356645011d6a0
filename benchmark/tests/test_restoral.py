"""The cells ``restore-10p4.symbols`` and ``restore-10p4.fragments``
(traffic kind ``restoral``, configuration ``archival-restoral``): their
controls come out not correct; the reference's chain ends in the frozen
codec's reconstruction for every pattern; the configuration's geometry is
``archival-wide``'s; and the new readers return ``None``, and raise
nothing, on a program without the counters and spans they read."""
import json
import os
import types

import numpy as np
import pytest

import run as bench_run
import test_run
from reference import gf, rs_ref, symbol_chain_ref

SYMBOLS, FRAGMENTS = "restore-10p4.symbols", "restore-10p4.fragments"
# test_run.py's own table cannot be edited from here; its check that every
# cell has controls reads the table when it runs
test_run.CONTROLS[SYMBOLS] = ["flip_hop", "wrong_coeff", "skip_hash"]
test_run.CONTROLS[FRAGMENTS] = ["flip_hop", "skip_hash"]
READERS = ("restore_host_ms.restore", "symbol_hop_ms.symbols",
           "restore_hash_ms.restore", "restore_ingress_mib.restore",
           "symbol_device_share.symbols", "rs_kernel_roofline.restore")
K, M = 10, 4


@pytest.mark.parametrize("cell,control", [
    (c, k) for c in (SYMBOLS, FRAGMENTS) for k in test_run.CONTROLS[c]])
def test_broken_path_is_not_correct(cell, control):
    rc, lines, err = test_run.run("--workload", cell, "--rehearse",
                                  "--seed", "40", "--control", control)
    assert rc == 1, err[-2000:]
    assert lines[-1]["rehearsal"] == "FAILED"
    assert lines[-1]["correct"] is False
    bad = {c["compare"] for c in lines if "compare" in c and not c["ok"]}
    assert not any("engine failed" in what or "compilations" in what
                   for what in bad)
    run_line = next(x for x in lines if x.get("info") == "run")
    assert run_line["failed"] > 0


def test_each_control_is_caught_where_it_should_be():
    """A flipped or mis-scaled hop is the rebuilder's to catch (its hash
    check fails the chain: a fallback, a failed operation, the ingress
    off); a rebuilder that does not check is the driver's (its own
    SHA-256)."""
    def bad(control):
        _, lines, _ = test_run.run("--workload", SYMBOLS, "--rehearse",
                                   "--seed", "41", "--control", control)
        return {c["compare"] for c in lines
                if "compare" in c and not c["ok"]}
    for control in ("flip_hop", "wrong_coeff"):
        caught = bad(control)
        assert any("fell back" in what for what in caught)
        assert any("repair_ingress_bytes" in what for what in caught)
        assert any("hop aggregates" in what for what in caught)
        assert not any("SHA-256 differs" in what for what in caught)
    caught = bad("skip_hash")
    assert any("SHA-256 differs" in what for what in caught)
    assert not any("fell back" in what for what in caught)


def test_wrong_coeff_is_a_control_of_the_chain_alone():
    rc, lines, err = test_run.run("--workload", FRAGMENTS, "--rehearse",
                                  "--seed", "40", "--control", "wrong_coeff")
    assert rc != 0 and "mode symbols" in err


@pytest.mark.parametrize("cell", [SYMBOLS, FRAGMENTS])
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal(cell, trace):
    rc, lines, err = test_run.run("--workload", cell, "--rehearse",
                                  "--seed", str(2 ** 31 + 40),
                                  "--trace", str(trace))
    assert rc == 0, err[-2000:]
    assert lines[-1]["correct"] is True
    if trace:
        wanted = {r for r in READERS if "roofline" not in r
                  and (cell == SYMBOLS or not r.endswith(".symbols"))}
        assert wanted <= set(lines[-1]["metrics_read"])
    check = next(x for x in lines if x.get("info") == "check")
    assert check["after_the_window_ok"] is True and len(check["kept"]) >= 3
    assert check["hops_compared"] == (
        K * (len(check["kept"]) - 1) if cell == SYMBOLS else 0)
    run_line = next(x for x in lines if x.get("info") == "run")
    assert run_line["failed"] == 0 and run_line["compiled_in_window"] == 0


@pytest.mark.parametrize("lost", range(K + M))
def test_the_chain_ends_in_the_frozen_codecs_reconstruction(lost):
    """Every single-loss pattern of RS(10,4) from its ten lowest
    survivors: the reference chain's last aggregate is
    ``ReferenceCodec.reconstruct``'s row, every earlier one a prefix of
    the same sum, and the repair row is the frozen ``gf.repair_matrix``'s
    (one inverse, two ways to it)."""
    rng = np.random.default_rng(lost)
    codec = rs_ref.ReferenceCodec(K, M)
    coded = codec.encode(rng.integers(0, 256, (K, 1024), dtype=np.uint8))
    present = tuple(j for j in range(K + M) if j != lost)[:K]
    row = symbol_chain_ref.repair_row(K, M, present, lost)
    assert np.array_equal(row, gf.repair_matrix(K, M, present, (lost,))[0])
    hops = symbol_chain_ref.chain(K, M, present, lost,
                                  [coded[j].tobytes() for j in present])
    assert len(hops) == K and all(h.dtype == np.uint8 for h in hops)
    assert np.array_equal(hops[-1], coded[lost])
    assert np.array_equal(hops[-1], codec.reconstruct(
        coded[list(present)], present, (lost,))[0])
    mt = gf.mul_table()
    acc = np.zeros(1024, np.uint8)
    for c, j, hop in zip(row, present, hops):
        acc = acc ^ mt[int(c)][coded[j]]
        assert np.array_equal(hop, acc)


def test_the_chain_refuses_what_cannot_rebuild():
    with pytest.raises(ValueError):
        symbol_chain_ref.repair_row(K, M, tuple(range(K)), 3)   # not lost
    with pytest.raises(ValueError):
        symbol_chain_ref.repair_row(K, M, tuple(range(1, K)), 0)  # nine


def test_sizes_are_the_archival_tiers():
    conf = json.load(open(os.path.join(
        test_run.BENCH, "configs", "archival-restoral.json")))
    wide = json.load(open(os.path.join(
        test_run.BENCH, "configs", "archival-wide.json")))
    for key in ("k", "m", "segment_size", "fragment_size", "podr2_sectors",
                "podr2_limbs", "podr2_block_bytes", "podr2_key_seed",
                "blocks_per_fragment", "stored_bytes_per_user_byte",
                "rehearse"):
        assert conf[key] == wide[key], key
    assert conf["architecture"] is None and conf["helpers"] == conf["k"]
    assert conf["ingress_bytes_per_repair"] == {
        "symbols": conf["fragment_size"],
        "fragments": conf["k"] * conf["fragment_size"]}
    assert len(conf["source"]) <= 200
    spec = json.load(open(os.path.join(test_run.ROOT, "BENCHMARK.json")))
    entry = next(c for c in spec["configs"]
                 if c["name"] == "archival-restoral")
    assert entry["source"] == conf["source"]
    for cell, mode in ((SYMBOLS, "symbols"), (FRAGMENTS, "fragments")):
        t = json.load(open(os.path.join(
            test_run.BENCH, "workloads", cell + ".json")))["traffic"]
        assert (t["kind"], t["mode"], t["pool_segments"]) \
            == ("restoral", mode, 4)
        # the pool: 448 MiB of host memory
        assert t["pool_segments"] * (conf["k"] + conf["m"]) \
            * conf["fragment_size"] == 448 << 20
        for metric in ("repair_p50_ms", "repair_p95_ms"):
            m = next(x for x in spec["end_to_end"] if x["name"] == metric)
            assert cell in m["workloads"]
    # the kernel's share is reckoned by this configuration's own reader:
    # the repair cells' reader would take a fold for ten survivors
    repair = next(m for m in spec["per_layer"]
                  if m["name"] == "rs_kernel_roofline.repair")
    assert not {SYMBOLS, FRAGMENTS} & set(repair["workloads"])


# -- the new readers on a program without what they read -------------------
@pytest.mark.parametrize("name", READERS)
def test_readers_find_nothing_on_the_parent_and_do_not_raise(name):
    old = {"engine": {"classes": {"repair": {"batches": 3, "completed": 3}}}}
    view = types.SimpleNamespace(
        counters_before=old, counters_after=old, trace=None,
        ctx=types.SimpleNamespace(
            cell=SYMBOLS, config={"k": K, "fragment_size": 8 << 20},
            traffic={"mode": "symbols"}, device_kind="TPU v5 lite"),
        say=lambda **line: None)
    assert bench_run.load_by_path("layer_metrics", name).read(view) is None
    view.counters_before = view.counters_after = {}
    assert bench_run.load_by_path("layer_metrics", name).read(view) is None


def test_ingress_reader_on_made_up_counters():
    def snap(repairs, came_in):
        return {"miner": {"repairs": repairs,
                          "repair_ingress_bytes": came_in}}
    view = types.SimpleNamespace(counters_before=snap(28, 28 << 23),
                                 counters_after=snap(300, 300 << 23))
    read = bench_run.load_by_path(
        "layer_metrics", "restore_ingress_mib.restore").read
    assert read(view) == 8.0
    view.counters_after = snap(300, (28 << 23) + 272 * (80 << 20))
    assert read(view) == 80.0
    view.counters_after = snap(28, 28 << 23)         # no repair: nothing
    assert read(view) is None


def test_the_roofline_reader_reckons_the_work_from_the_mode(monkeypatch):
    import kernel_work

    seen = []
    monkeypatch.setattr(kernel_work, "roofline_share",
                        lambda view, prefix, work: seen.append(
                            (prefix, work)) or 1.0)
    read = bench_run.load_by_path(
        "layer_metrics", "rs_kernel_roofline.restore").read
    n = 8 << 20
    for mode in ("symbols", "fragments"):
        read(types.SimpleNamespace(ctx=types.SimpleNamespace(
            config={"k": K, "fragment_size": n}, traffic={"mode": mode})))
    assert [p for p, _ in seen] == ["%_apply_3d"] * 2
    assert seen[0][1]["bytes"] == 3 * n         # two rows in, one out
    assert seen[1][1]["bytes"] == (K + 1) * n
