"""run.py driven end to end on the CPU: every cell rehearses, with and
without the trace; the timed path broken underneath comes out not correct;
without a chip and without --rehearse nothing runs."""
import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT

SPEC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in SPEC["workloads"]]
# cell -> the faults its driver can put under the timed path: the first
# alters an answer where it is produced, the second breaks a guarantee the
# configuration states (a shortened or cached computation)
CONTROLS = {"stream-4p8.corpus": ["flip_parity", "stale_tags"],
            "repair-2p1.single": ["flip_byte", "wrong_row"],
            "upload-2p1.files": ["flip_stored", "stale_tags"],
            "audit-2p1.round": ["flip_mu", "stale_proof"]}


def run(*args, cwd=ROOT):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run([sys.executable, os.path.join(BENCH, "run.py"),
                        *args], cwd=cwd, env=env, capture_output=True,
                       text=True, timeout=600)
    lines = [json.loads(x) for x in p.stdout.splitlines()
             if x.startswith("{")]
    return p.returncode, lines, p.stderr


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal(cell, trace):
    rc, lines, err = run("--workload", cell, "--rehearse", "--seed",
                         str(2 ** 31 + 17), "--trace", str(trace))
    assert rc == 0, err[-2000:]
    last = lines[-1]
    assert last["rehearsal"] == "passed" and last["correct"] is True
    # never the contract's last line, never a value under a metric's name
    assert "metrics" not in last and "device" not in last
    wanted = [m["name"] for m in SPEC["per_layer" if trace else "end_to_end"]
              if "workloads" not in m or cell in m["workloads"]]
    kernels = [n for n in wanted if "_kernel_roofline" in n]
    assert set(last["metrics_read"]) == set(wanted) - set(kernels)
    assert all(line["on"].startswith("cpu/") for line in lines)
    compares = [x for x in lines if "compare" in x]
    assert compares and all(c["ok"] for c in compares)
    run_line = next(x for x in lines if x.get("info") == "run")
    assert run_line["compiled_in_window"] == 0 and run_line["failed"] == 0


@pytest.mark.parametrize("cell,control", [
    (c, k) for c in CELLS for k in CONTROLS.get(c, [])])
def test_broken_path_is_not_correct(cell, control):
    rc, lines, err = run("--workload", cell, "--rehearse", "--seed", "23",
                         "--control", control)
    assert rc == 1, err[-2000:]
    assert lines[-1]["rehearsal"] == "FAILED"
    assert lines[-1]["correct"] is False


def test_every_cell_has_its_controls():
    assert set(CONTROLS) == set(CELLS)


def test_no_chip_no_run():
    rc, lines, err = run("--workload", CELLS[0], "--seed", "1",
                         "--seconds", "1", "--trace", "0")
    assert rc != 0 and lines == []
    assert "no chip found" in err


def test_without_the_program_nothing_runs(tmp_path):
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "benchmark/run.py", "--workload",
                        CELLS[0], "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_run_py_names_no_cell_config_or_metric():
    src = open(os.path.join(BENCH, "run.py")).read()
    names = CELLS + [c["name"] for c in SPEC["configs"]] \
        + [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]] \
        + [os.path.splitext(f)[0] for f in os.listdir(
            os.path.join(BENCH, "traffic")) if not f.startswith("_")]
    # setup_s alone is the contract's own name for the harness's clock
    assert [n for n in names if n in src and n != "setup_s"] == []


def test_every_entry_has_its_files():
    for w in SPEC["workloads"]:
        spec = json.load(open(os.path.join(BENCH, "workloads",
                                           w["name"] + ".json")))
        assert spec["config"] == w["config"] and spec["chips"] == w["chips"]
        assert os.path.isfile(os.path.join(
            BENCH, "traffic", spec["traffic"]["kind"] + ".py"))
    for c in SPEC["configs"]:
        conf = json.load(open(os.path.join(ROOT, c["file"])))
        assert set(c["reduced"]) == set(conf["reduced"])
    for kind, key in (("end_to_end", "end_to_end"),
                      ("layer_metrics", "per_layer")):
        for m in SPEC[key]:
            assert os.path.isfile(os.path.join(BENCH, kind,
                                               m["name"] + ".py"))
