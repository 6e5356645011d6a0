"""Tests of the benchmark's own code. They live under benchmark/ (not
tests/), run on the CPU, and are not part of tier-1:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)
