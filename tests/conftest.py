"""Test configuration: force an 8-device virtual CPU platform.

Tests never require TPU hardware; multi-chip sharding is exercised on a
virtual 8-device CPU mesh (the driver separately dry-run-compiles the
multi-chip path via __graft_entry__.dryrun_multichip). The platform is
forced here, before the first device use, so the suite runs the same
with or without JAX_PLATFORMS in the environment. The persistent
compile cache (cess_tpu/jaxcache.py) is NOT turned on for tests.
"""
import os
import sys
import threading

import jax
import jax.monitoring
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

_COMPILES = [0]


def _count_compile(event, secs, **_):
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILES[0] += 1


jax.monitoring.register_event_duration_secs_listener(_count_compile)


@pytest.fixture
def compiles():
    """``compiles()``: the XLA compilations this process has made so
    far. A warmed path is proved by differencing it to 0 (the dense RS
    strategies keep no executable of their own to count hits of: their
    programs are jit's)."""
    return lambda: _COMPILES[0]


class _Gate:
    """Holds an engine's runner of one op at its entry until it is
    opened: a batch "runs", and its executor stays busy, for as long
    as the test wants, whatever the box's speed. ``entered`` counts
    the batches that reached the runner."""

    def __init__(self, eng, op):
        self.entered = threading.Semaphore(0)
        self.opened = threading.Event()
        real = getattr(eng, f"_op_{op}")

        def runner(*args):
            self.entered.release()
            assert self.opened.wait(60)
            return real(*args)

        setattr(eng, f"_op_{op}", runner)

    def running(self) -> bool:
        """Wait until one more batch is inside the runner."""
        return self.entered.acquire(timeout=60)

    def open(self):
        self.opened.set()


@pytest.fixture
def gate():
    """``gate(eng, op)``: a _Gate on that engine's ``_op_<op>``. The
    test opens it before it closes the engine (a close drains)."""
    return _Gate


@pytest.fixture
def queue_accounts():
    """``queue_accounts(eng, cls)``: the class's snapshot after a
    flush, held to ``coalesce + wake == queue`` exactly (the queue's
    total is kept as the sum of its halves: serve/stats.py)."""
    def read(eng, cls):
        eng.flush()
        snap = eng.stats_snapshot()["classes"][cls]
        assert snap["queue"]["coalesce"]["s"] + snap["queue"]["wake"]["s"] \
            == snap["stages"]["queue"]["s"]
        return snap
    return read


def pytest_configure(config):
    # tier-1 runs with -m 'not slow' (ROADMAP): anything slow-marked
    # (the 1000-node sim world) is outside the gate
    config.addinivalue_line(
        "markers", "slow: outside the tier-1 gate (large worlds)")
