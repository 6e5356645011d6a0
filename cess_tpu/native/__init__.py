"""Native (C++) backends: sources, Makefile, and the build-on-load rule.

The shared libraries are build products (git ignores them) compiled
with ``-march=native`` for whatever host ran ``make``. A library left
on disk by another host, or older than its source, must never be
loaded — an illegal instruction is not an ``OSError`` — so the ctypes
bindings call :func:`ensure_built` first. ``make`` is a child process
that never touches JAX.
"""
from __future__ import annotations

import os
import subprocess

DIR = os.path.dirname(os.path.abspath(__file__))
_SOURCES = {"libcessrs.so": "rs_native.cpp", "libcessbls.so": "bls381.cpp"}


def ensure_built(lib: str) -> str:
    """Path of ``lib``, (re)built when it is missing or older than its
    source or the Makefile. Raises ImportError when it cannot be
    built (the bindings' callers fall back on ImportError)."""
    so = os.path.join(DIR, lib)
    deps = [os.path.join(DIR, _SOURCES[lib]), os.path.join(DIR, "Makefile")]
    if os.path.exists(so) \
            and os.path.getmtime(so) >= max(map(os.path.getmtime, deps)):
        return so
    try:
        # one target only: a compile failure in the other backend
        # must not take this one down
        subprocess.run(["make", "-C", DIR, "-s", lib], check=True,
                       capture_output=True)
    except (OSError, subprocess.CalledProcessError) as e:
        raise ImportError(f"cannot build native {lib}: {e}") from e
    return so
