"""``tools/round_probe.py`` (PR 56: the four orders of a TEE's round)
stays runnable: its rehearsal on the CPU judges every round under every
form, the forms agree, and each prints its line. Times are the chip's to
give; none is read here."""
import importlib.util
import json
import os

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_the_rehearsal_runs_every_form_and_they_agree(capsys):
    spec = importlib.util.spec_from_file_location(
        "round_probe", os.path.join(REPO, "tools", "round_probe.py"))
    probe = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(probe)
    assert probe.main(["--rehearse"]) == 0
    lines = [json.loads(line) for line in
             capsys.readouterr().out.strip().splitlines()]
    assert [line["form"] for line in lines[:4]] == list(probe.FORMS)
    assert [line["stages_of"] for line in lines[4:]] == ["in_hand", "signal"]
    for line in lines[4:]:
        assert {"tee.round", "tee.round.decode", "engine.verify.proofs",
                "engine.verify.dispatch"} <= set(line["ms_a_round"])
    for line in lines[:4]:
        assert line["rounds"] == 3 and line["missions"] == 12
        assert set(line["batch_ms"]) == {"assemble", "dispatch", "proofs",
                                         "wait", "fetch"}
    # the caps' sizes are the cell's: Zipf over rank, the rest to rank 1
    assert probe.zipf_sizes(500, 100_000)[0] == 14722
    assert sum(probe.zipf_sizes(500, 100_000)) == 100_000
