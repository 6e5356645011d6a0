"""chip_smoke.py off the chip: the rehearsals prove its control flow at
tiny sizes on the CPU backend (and never print the chip's last line),
and the real command refuses to run where JAX finds no TPU. The run on
the chip itself is the driver's, through the chip tool."""
import json
import os
import subprocess
import sys

import jax
import pytest

from cess_tpu import jaxcache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _smoke(tmp_path, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, SMOKE, *args], cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=600)
    lines = [json.loads(ln) for ln in proc.stdout.splitlines()
             if ln.startswith("{")]
    return proc, lines


@pytest.mark.parametrize("chips,phases", [
    (1, ["A", "B", "C", "D", "total"]),
    (4, ["pool", "mesh", "total"]),
])
def test_rehearsal_runs_every_phase_and_never_the_chip_line(
        tmp_path, chips, phases):
    proc, lines = _smoke(tmp_path, "--rehearse", "--chips", str(chips))
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert [ln["phase"] for ln in lines[:-1]] == phases
    last = lines[-1]
    assert "ok" not in last and "device" not in last
    assert last["rehearsal"] == "passed"
    assert last["ran_on"] == {"platform": "cpu", "kind": "cpu",
                              "count": chips}
    for ln in lines[:-2]:
        eng = ln.get("engine")
        if eng is not None:
            assert eng["failed"] == eng["fallback"] == eng["degraded"] == 0
    if chips == 1:
        # phase A's burst (PR 52): eight host repairs of one four-row
        # loss pattern through the repair class, every result a view of
        # its own fetched piece (PR 53)
        burst = lines[0]["burst"]
        assert burst["batched_requests"] == 8 >= burst["batches"] >= 1
        assert burst["result_bytes"] == 8 * 4 * (64 * 1024 // 4)
        assert burst["regrouped_bytes"] == 0
        # phase C's second geometry (PR 47): the fused program at the
        # archival tier's RS(10,4), nine segments through batches of 8
        wide = lines[2]["wide"]
        assert (wide["k"], wide["m"]) == (10, 4)
        assert wide["stream"]["segments"] == 9
        assert wide["stream"]["padded_segments"] == 7
    # the cache was placed from outside, and only there
    assert lines[-2]["cache_dir"] == str(tmp_path / "jax_cache")


def test_refuses_to_run_without_a_chip(tmp_path):
    proc, lines = _smoke(tmp_path)
    assert proc.returncode != 0
    assert "no chip found" in proc.stderr
    assert lines == []          # no phase ran, no result printed


def test_compile_cache_is_placed_from_outside(monkeypatch, tmp_path):
    was = jax.config.jax_compilation_cache_dir
    thresholds = ("jax_persistent_cache_min_compile_time_secs",
                  "jax_persistent_cache_min_entry_size_bytes")
    were = [getattr(jax.config, t) for t in thresholds]
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert jaxcache.enable() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == was   # untouched
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert jaxcache.enable() == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir \
            == os.path.join(REPO, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
        for t, v in zip(thresholds, were):
            jax.config.update(t, v)
