"""The persistent XLA compile cache, placed from outside.

The fused encode+tag program takes minutes to compile for the TPU and
every process would pay that again. ``enable()`` is called by the
entry points (chip_smoke.py, benchmark/run.py, node/cli.py) before
the first compile: where ``JAX_COMPILATION_CACHE_DIR`` is set JAX
reads it itself and nothing is set here; otherwise the cache lives at the
fixed path ``<checkout>/.jax_cache`` (the path is part of the cache
key, so it never carries a temporary name, a pid or a time). Either
way every program is kept, however quick its compile: the data plane
dispatches some five hundred small programs around its kernels, and
under JAX's default one-second threshold a second run on the chip
still spent a minute compiling them (PR 22). tests/conftest.py does not
turn the cache on.
"""
from __future__ import annotations

import os

import jax

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def enable() -> str:
    """Returns the directory the cache lives in."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
