"""The cells ``verify-2p1.missions500`` (traffic kind ``verify_round``,
configuration ``tee-verify-caps``) and ``repair-10p4.lowest``: the
controls of both come out not correct; the new reference agrees with the
frozen one mission by mission; the cell's sizes and draws are the
configuration's; and the new readers return ``None``, and raise nothing,
on a program without the counters and spans they read (the parent)."""
import json
import os
import sys
import types

import numpy as np
import pytest

import run as bench_run
import test_run
from reference import podr2_ref, verify_round_ref

VERIFY, LOWEST = "verify-2p1.missions500", "repair-10p4.lowest"
# test_run.py's own table cannot be edited from here; its check that every
# cell has controls reads the table when it runs
test_run.CONTROLS[VERIFY] = ["accept_all", "stale_verdicts"]
# the driver's second control, ``wrong_helpers``, tells the engine the k
# lowest survivors whatever was stacked: under ``answering`` "lowest" that
# is the truth, so it breaks nothing here (held below)
test_run.CONTROLS[LOWEST] = ["flip_byte"]
READERS = ("tee_host_ms.missions500", "verify_pad_share.missions500",
           "verify_calls_per_round.missions500",
           "verify_device_share.missions500",
           "prf_gevals_per_s.missions500")


@pytest.mark.parametrize("cell,control", [
    (c, k) for c in (VERIFY, LOWEST) for k in test_run.CONTROLS[c]])
def test_broken_path_is_not_correct(cell, control):
    rc, lines, err = test_run.run("--workload", cell, "--rehearse",
                                  "--seed", "33", "--control", control)
    assert rc == 1, err[-2000:]
    assert lines[-1]["rehearsal"] == "FAILED"
    assert lines[-1]["correct"] is False


def test_wrong_helpers_is_no_fault_when_the_lowest_answer():
    rc, lines, err = test_run.run("--workload", LOWEST, "--rehearse",
                                  "--seed", "33", "--control",
                                  "wrong_helpers")
    assert rc == 0, err[-2000:]
    assert lines[-1]["correct"] is True


def test_stale_verdicts_fail_by_the_reference_too():
    """Remembered verdicts are wrong for the next round's dishonest
    missions: the sampled comparison with the reference says so, not
    only the expected table."""
    rc, lines, _ = test_run.run("--workload", VERIFY, "--rehearse",
                                "--seed", "34", "--control",
                                "stale_verdicts")
    assert rc == 1
    bad = {c["compare"]: c for c in lines if "compare" in c and not c["ok"]}
    assert any("plain reference" in what for what in bad)
    assert not any("engine failed" in what for what in bad)


def test_the_round_reference_agrees_with_the_frozen_one():
    key = podr2_ref.generate_key(24)
    rng = np.random.default_rng(5)
    sizes, blocks, seed = [3, 1, 9], 64, b"agree"
    ids = rng.integers(0, 2 ** 32, (sum(sizes), 2), dtype=np.uint32)
    mu, sigma = verify_round_ref.honest_proofs(key, seed, blocks, ids,
                                               sizes, 8)
    sigma[1, 0] ^= 2
    idx, nu = podr2_ref.gen_challenge(seed, blocks)
    owed, want, at = [], [], 0
    for m, size in enumerate(sizes):
        owed.append(ids[at:at + size])
        want.append(podr2_ref.verify_aggregate(
            key, owed[-1], idx, nu,
            podr2_ref.aggregate_coeffs(seed, owed[-1]), mu[m], sigma[m]))
        at += size
    assert want == [True, False, True]
    assert verify_round_ref.verdicts(key, seed, blocks, owed,
                                     list(zip(mu, sigma))) == want
    # undecodable bytes and an empty owed set, by the rule
    zero = (np.zeros(256, np.uint32), np.zeros(2, np.uint32))
    assert verify_round_ref.verdicts(
        key, seed, blocks, [owed[0], ids[:0], ids[:0]],
        [None, zero, (mu[0], sigma[0])]) == [False, True, False]


def test_sizes_and_draws_are_the_configurations():
    sys.path.insert(0, os.path.join(test_run.BENCH, "traffic"))
    try:
        import verify_round
    finally:
        sys.path.pop(0)
    sizes = verify_round.mission_sizes(500, 100000)
    assert (sum(sizes), sizes[0], sizes[-1], len(sizes)) \
        == (100000, 14722, 29, 500)       # 14,721 and the remainder
    assert sorted(sizes, reverse=True) == sizes
    conf = json.load(open(os.path.join(
        test_run.BENCH, "configs", "tee-verify-caps.json")))
    proto = json.load(open(os.path.join(
        test_run.BENCH, "configs", "cess-protocol.json")))
    for key in ("k", "m", "segment_size", "fragment_size", "podr2_sectors",
                "podr2_limbs", "podr2_block_bytes", "podr2_key_seed",
                "blocks_per_fragment", "rehearse"):
        assert conf[key] == proto[key], key
    assert (conf["missions"], conf["fragments_total"]) == (500, 100000)
    assert conf["challenged_blocks"] == 16384 * 46 // 1000 == 753
    cell = json.load(open(os.path.join(
        test_run.BENCH, "workloads", VERIFY + ".json")))["traffic"]
    assert (cell["missions"], cell["fragments_total"]) == (500, 100000)
    assert sorted(cell["dishonest"]) == sorted(verify_round.TAMPERS)
    assert (cell["rounds_prepared"], cell["check_rounds"],
            cell["check_missions"]) == (4, 2, 8)
    # five tampers fail six missions (the swap fails two): 1.2%
    ctx = types.SimpleNamespace(seed=7, traffic=cell, sizes=sizes,
                                largest=0, real=499)
    for op in (-2, -1, 0, 1, 299):
        drawn = verify_round.tampers(ctx, op)
        hit = [m for ms, _, _ in drawn.values() for m in ms]
        assert len(hit) == len(set(hit)) == 6
        assert not {0, 499} & set(hit)
    assert verify_round.tampers(ctx, 3) != verify_round.tampers(ctx, 4)
    lowest = json.load(open(os.path.join(
        test_run.BENCH, "workloads", LOWEST + ".json")))
    helpers = json.load(open(os.path.join(
        test_run.BENCH, "workloads", "repair-10p4.helpers.json")))
    assert lowest["traffic"] == {**helpers["traffic"],
                                 "answering": "lowest"}
    assert lowest["rehearse"] == helpers["rehearse"]
    assert lowest["config"] == helpers["config"] == "archival-wide"


# -- the new readers on a program without what they read -------------------
@pytest.mark.parametrize("name", READERS)
def test_readers_find_nothing_on_the_parent_and_do_not_raise(name):
    old = {"engine": {"classes": {"verify": {
        "batches": 3, "completed": 3, "pad_waste": 0.1}}}}
    view = types.SimpleNamespace(
        counters_before=old, counters_after=old, trace=None,
        ctx=types.SimpleNamespace(cell=VERIFY), say=lambda **line: None)
    assert bench_run.load_by_path("layer_metrics", name).read(view) is None
    view.counters_before = view.counters_after = {}
    assert bench_run.load_by_path("layer_metrics", name).read(view) is None


def test_counter_readers_on_made_up_counters():
    def snap(batches, rows, pad, calls, evals):
        return {"engine": {"classes": {"verify": {
            "batches": batches, "rows": rows, "padded_rows": pad,
            "device_calls": calls, "prf_evals": evals}}}}
    view = types.SimpleNamespace(
        counters_before=snap(2, 200000, 704, 16, 151130112),
        counters_after=snap(12, 1200000, 4224, 96, 906780672),
        trace=None, say=lambda **line: None)

    def read(name):
        return bench_run.load_by_path("layer_metrics", name).read(view)
    assert read("verify_calls_per_round.missions500") == 8
    assert read("verify_pad_share.missions500") == pytest.approx(
        100 * 3520 / 1003520)
