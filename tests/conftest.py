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

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)


def pytest_configure(config):
    # tier-1 runs with -m 'not slow' (ROADMAP): anything slow-marked
    # (the 1000-node sim world) is outside the gate
    config.addinivalue_line(
        "markers", "slow: outside the tier-1 gate (large worlds)")
