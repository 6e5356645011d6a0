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


def pytest_configure(config):
    # tier-1 runs with -m 'not slow' (ROADMAP): anything slow-marked
    # (the 1000-node sim world) is outside the gate
    config.addinivalue_line(
        "markers", "slow: outside the tier-1 gate (large worlds)")
