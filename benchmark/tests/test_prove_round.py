"""The cell ``prove-2p1.deal1000`` (traffic kind ``prove_round``,
configuration ``miner-deal-cap``): its controls come out not correct; the
new reference agrees with the frozen one; the configuration's geometry is
the protocol's and its custody the chain's constants; and the new readers
return ``None``, and raise nothing, on a program without the counters and
spans they read (the parent)."""
import hashlib
import json
import os
import types

import numpy as np
import pytest

import run as bench_run
import test_run
from reference import podr2_ref, prove_round_ref

PROVE = "prove-2p1.deal1000"
# test_run.py's own table cannot be edited from here; its check that every
# cell has controls reads the table when it runs
test_run.CONTROLS[PROVE] = ["drop_fragment", "stale_proof"]
READERS = ("miner_host_ms.deal1000", "prove_gather_gbps.deal1000",
           "prove_pad_share.deal1000", "prove_calls_per_round.deal1000",
           "prove_device_share.deal1000")


@pytest.mark.parametrize("control", test_run.CONTROLS[PROVE])
def test_broken_path_is_not_correct(control):
    rc, lines, err = test_run.run("--workload", PROVE, "--rehearse",
                                  "--seed", "38", "--control", control)
    assert rc == 1, err[-2000:]
    assert lines[-1]["rehearsal"] == "FAILED"
    assert lines[-1]["correct"] is False


def test_a_remembered_proof_fails_by_the_reference_too():
    """``stale_proof`` answers every round with the first round's bytes:
    the verifier rejects them, and so does the sampled comparison with
    the reference's proof."""
    rc, lines, _ = test_run.run("--workload", PROVE, "--rehearse",
                                "--seed", "39", "--control", "stale_proof")
    assert rc == 1
    bad = {c["compare"] for c in lines if "compare" in c and not c["ok"]}
    assert any("verifier rejected" in what for what in bad)
    assert not any("engine failed" in what for what in bad)
    run_line = next(x for x in lines if x.get("info") == "run")
    assert run_line["failed"] >= run_line["attempted"] - 1 > 0


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal(trace):
    rc, lines, err = test_run.run("--workload", PROVE, "--rehearse",
                                  "--seed", str(2 ** 31 + 38),
                                  "--trace", str(trace))
    assert rc == 0, err[-2000:]
    assert lines[-1]["correct"] is True
    if trace:
        assert set(READERS) <= set(lines[-1]["metrics_read"])
    run_line = next(x for x in lines if x.get("info") == "run")
    assert run_line["failed"] == 0 and run_line["compiled_in_window"] == 0


def test_the_round_reference_agrees_with_the_frozen_one():
    """``prove_round_ref.prove`` over the key's own tags is the frozen
    reference's proof (which makes its tags itself), and the reference
    verifier takes it and refuses it with one word altered."""
    key = podr2_ref.generate_key(24)
    rng = np.random.default_rng(6)
    blocks, seed = 64, b"agree"
    blobs = [rng.integers(0, 256, blocks * 512, dtype=np.uint8).tobytes()
             for _ in range(5)]
    hashes = [hashlib.sha256(b).digest() for b in blobs]
    ids = prove_round_ref.ids_from_hashes(hashes)
    frags = np.stack([np.frombuffer(b, np.uint8) for b in blobs])
    with podr2_ref.on_cpu():
        tags = [podr2_ref.tag_fragment(key, i, f)
                for i, f in zip(ids, frags)]
        idx, nu = podr2_ref.gen_challenge(seed, blocks)
        want = podr2_ref.prove_aggregate(
            key, ids, frags, idx, nu,
            podr2_ref.aggregate_coeffs(seed, ids))
    mu, sigma = prove_round_ref.prove(seed, hashes, blobs, tags, blocks)
    assert np.array_equal(mu, want[0]) and np.array_equal(sigma, want[1])
    assert mu.dtype == sigma.dtype == np.uint32
    assert prove_round_ref.accepted(key, seed, blocks, hashes, mu, sigma)
    mu[3] ^= 1
    assert not prove_round_ref.accepted(key, seed, blocks, hashes, mu, sigma)
    # a fragment left out of the fold, still owed: rejected
    part = prove_round_ref.prove(seed, hashes[1:], blobs[1:], tags[1:],
                                 blocks)
    assert not prove_round_ref.accepted(key, seed, blocks, hashes, *part)
    zero = prove_round_ref.prove(seed, [], [], [], blocks)
    assert not zero[0].any() and not zero[1].any()


def test_sizes_are_the_chains_constants():
    conf = json.load(open(os.path.join(
        test_run.BENCH, "configs", "miner-deal-cap.json")))
    proto = json.load(open(os.path.join(
        test_run.BENCH, "configs", "cess-protocol.json")))
    for key in ("k", "m", "segment_size", "fragment_size", "podr2_sectors",
                "podr2_limbs", "podr2_block_bytes", "podr2_key_seed",
                "blocks_per_fragment", "rehearse"):
        assert conf[key] == proto[key], key
    assert conf["deal_segments"] == conf["service_fragments"] == 1000
    assert conf["challenged_blocks"] == 16384 * 46 // 1000 == 753
    assert conf["architecture"] is None
    cell = json.load(open(os.path.join(
        test_run.BENCH, "workloads", PROVE + ".json")))["traffic"]
    assert cell["fragments"] == conf["service_fragments"]
    assert (cell["check_rounds"], cell["check_tags"]) == (2, 2)
    # one deal's share is 7.8 GiB, and a round reads 373.5 MiB of it
    assert cell["fragments"] * conf["fragment_size"] == 8_388_608_000
    read = cell["fragments"] * conf["challenged_blocks"] * (
        conf["podr2_block_bytes"] + 4 * conf["podr2_limbs"])
    assert round(read / 2 ** 20, 1) == 373.4
    spec = json.load(open(os.path.join(test_run.ROOT, "BENCHMARK.json")))
    metric = next(m for m in spec["end_to_end"] if m["name"] == "audit_rate")
    assert PROVE in metric["workloads"]


# -- the new readers on a program without what they read -------------------
@pytest.mark.parametrize("name", READERS)
def test_readers_find_nothing_on_the_parent_and_do_not_raise(name):
    old = {"engine": {"classes": {
        "prove": {"batches": 3, "completed": 3, "rows": 96,
                  "padded_rows": 0, "device_calls": 0, "operand_bytes": 9}}}}
    view = types.SimpleNamespace(
        counters_before=old, counters_after=old, trace=None,
        ctx=types.SimpleNamespace(cell=PROVE), say=lambda **line: None)
    assert bench_run.load_by_path("layer_metrics", name).read(view) is None
    view.counters_before = view.counters_after = {}
    assert bench_run.load_by_path("layer_metrics", name).read(view) is None


def test_counter_readers_on_made_up_counters():
    def snap(batches, rows, pad, calls, nbytes, seconds):
        return {"engine": {"classes": {
            "prove": {"batches": batches, "rows": rows, "padded_rows": pad,
                      "device_calls": calls, "chunks": calls,
                      "gathered_bytes": nbytes, "gather_seconds": seconds}}}}
    view = types.SimpleNamespace(
        counters_before=snap(2, 2000, 48, 32, 10 ** 9, 0.5),
        counters_after=snap(12, 12000, 288, 192, 6 * 10 ** 9, 2.5),
        trace=None, say=lambda **line: None)

    def read(name):
        return bench_run.load_by_path("layer_metrics", name).read(view)
    assert read("prove_calls_per_round.deal1000") == 16
    assert read("prove_pad_share.deal1000") == pytest.approx(
        100 * 240 / 10240)
    assert read("prove_gather_gbps.deal1000") == pytest.approx(2.5)
