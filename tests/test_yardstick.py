"""Tier-1's guard on the yardstick: every cell of ``BENCHMARK.json``
rehearses, untraced and traced, and every reader of its metrics finds
what it reads in the program.

The driver judges each PR by ``BENCHMARK.json`` + ``benchmark/`` on the
chip. ``benchmark/tests`` is not tier-1 by design, so without this file
a program change that renames a counter, a stage or a span that a
reader depends on shows up only after the session, as a ``null`` metric
or a refusal. ``benchmark/run.py --workload <cell> --rehearse`` runs the
cell's whole control flow at tiny sizes on any backend: set-up, warm-up,
a short window, the frozen reference's verdict (``correct``), and every
metric reader of the cell, each of which must return a value
(``metrics_read``); the values themselves are never reported.

This is the same check as ``benchmark/tests/test_run.py::test_rehearsal``
(and ``test_stream_pool.py::test_rehearsal_on_four_lanes`` for the
four-chip cell), written again here because that directory's conftest
rewrites ``sys.path``: nothing is imported from it. The cells are read
from ``BENCHMARK.json`` at collection, so a new cell is covered without
an edit here. It reads ``benchmark/`` and ``BENCHMARK.json`` and writes
nothing there.

What it cannot hold: a CPU rehearsal has no device trace, so the
``*_kernel_roofline*`` readers are excepted (``tests/test_kernel_names.py``
and ``tests/test_tpu_compile.py`` hold the kernel names they match), and
it says nothing about time.

A traced rehearsal writes ``.bench_trace/<cell>/`` under the repository
root (git-ignored; ``run.py`` fixes the place). Every case therefore
lives in this one file: ``--dist loadfile`` runs them on one worker,
one after another.
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPEC = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
CELLS = {w["name"]: w for w in SPEC["workloads"]}
SEED = 2 ** 31 + 17


def rehearse(cell: str, trace: int):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    chips = CELLS[cell].get("chips", 1)
    if chips > 1:       # one virtual CPU device a chip: the cell's lanes
        env["XLA_FLAGS"] = \
            f"--xla_force_host_platform_device_count={chips}"
    else:
        env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", cell, "--rehearse", "--seed", str(SEED),
         "--trace", str(trace)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    lines = [json.loads(x) for x in p.stdout.splitlines()
             if x.startswith("{")]
    return p.returncode, lines, p.stderr


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", list(CELLS))
def test_cell_rehearses(cell, trace):
    rc, lines, err = rehearse(cell, trace)
    assert rc == 0, (lines[-1:], err[-2000:])
    last = lines[-1]
    assert last["rehearsal"] == "passed" and last["correct"] is True
    wanted = {m["name"]
              for m in SPEC["per_layer" if trace else "end_to_end"]
              if "workloads" not in m or cell in m["workloads"]}
    kernels = {n for n in wanted if "_kernel_roofline" in n}
    assert set(last["metrics_read"]) == wanted - kernels
    run_line = next(x for x in lines if x.get("info") == "run")
    assert run_line["compiled_in_window"] == 0
    assert run_line["failed"] == 0


def test_every_metric_has_a_cell_that_reads_it():
    """A metric listed for no cell, or only for a cell that is gone,
    would be read by no rehearsal above."""
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        cells = m.get("workloads", list(CELLS))
        assert cells and set(cells) <= set(CELLS), m["name"]
