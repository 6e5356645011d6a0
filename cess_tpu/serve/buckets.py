"""Shape buckets + compile-once program cache for the engine.

Every distinct array shape handed to a jitted op is a fresh XLA
compile; a serving layer that forwards each caller's ragged batch size
verbatim spends its life recompiling (the Ragged Paged Attention
lesson, PAPERS.md arxiv 2604.15464: coalesce ragged requests into a
small set of shape-bucketed device programs). The engine therefore

- pads every coalesced batch's leading (row) axis up to a bucket —
  powers of two, clamped to the policy's row budget — so the device
  only ever sees O(log max_rows) distinct shapes per op, and
- memoizes the bound device callable per (op, bucket shape, aux key)
  in :class:`ProgramCache`, so bucket reuse is visible in the stats
  (``programs_built`` vs ``programs_reused``). A key names a SHAPE:
  what varies from call to call within one shape — a repair's erasure
  pattern (its matrix), an audit round's challenge — is an argument of
  the program, never part of its key, so the number of programs does
  not grow with the number of patterns (RS(10,4) repaired from
  whichever ten helpers answer has 4,004 single-loss patterns and one
  program a shape). The pattern's tables (nibble tables, bit-matrix
  expansion, decode-matrix Gauss-Jordan) are the codec's to keep
  (ops/rs.py ``TPUCodec.MATRICES``).

Padding is with zero rows and is sliced off after the op; every engine
op is row-independent (vmap / per-row matrix apply), so padded results
are bit-identical to unpadded ones — the determinism tests in
tests/test_serve.py pin this.
"""
from __future__ import annotations

import time
from typing import Callable


def bucket_rows(n: int) -> int:
    """Smallest power-of-two >= n — ALWAYS on the power-of-two grid.

    Coalesced batches respect the policy row budget (the drain never
    combines requests past max_batch_rows), so a bigger n happens only
    for a single oversized request. That request still pads to the
    next power of two rather than compiling an exact-size one-off
    program: an irregular caller then costs at most O(log n) extra
    programs and < 2x pad waste, never a compile per distinct size —
    the churn this module exists to prevent."""
    if n < 1:
        raise ValueError(f"bucket for {n} rows")
    b = 1
    while b < n:
        b <<= 1
    return b


class ProgramCache:
    """(op, bucket shape, aux key) -> bound device callable, LRU.

    The underlying jax.jit caches by traced shape anyway; this layer
    exists so (a) host-side table/matrix builds are done once per key,
    (b) the engine can report compile-vs-reuse counts, and (c) the
    bucket policy has one place to be enforced.

    Bounded: the keys that do name data — the per-fragment
    verify_batch closure, whose key still carries its round's digest —
    are hot for a while and dead afterwards, and an unbounded dict
    would be a slow leak of closures and what they captured. LRU with
    a generous capacity keeps everything live resident and lets the
    dead fall out. The repair class (one entry per ``(q, r, n,
    bucket)``, the pattern an argument) and the stacked audit programs
    (prove, verify_agg: the round and the PoDR2 key are operands) hold
    one entry per shape, the same call after call.
    """

    CAPACITY = 256

    def __init__(self, stats=None, capacity: int = CAPACITY):
        import collections
        import threading

        self._programs: "collections.OrderedDict[tuple, Callable]" = \
            collections.OrderedDict()
        self._stats = stats
        self.capacity = capacity
        # continuous profiling (obs/profile.py, opt-in): the engine
        # arms this with its ProfilePlane so every cache MISS lands in
        # the CompileLedger with its key and compile wall time — a
        # recompile storm becomes a ranked account. None = one
        # attribute load + None check per miss.
        self.profile = None
        # the batcher thread owns steady-state lookups, but warm-path
        # callers (SubmissionEngine.warm_repair) pre-populate from the
        # submitter thread — the OrderedDict needs its own tiny lock
        self._mu = threading.Lock()

    def __len__(self) -> int:
        with self._mu:
            return len(self._programs)

    def get(self, key: tuple, build: Callable[[], Callable]) -> Callable:
        with self._mu:
            prog = self._programs.get(key)
            if prog is not None:
                self._programs.move_to_end(key)
                if self._stats is not None:
                    self._stats.programs_reused += 1
                return prog
        # build OUTSIDE the lock: builds compile device programs and
        # must not serialize against concurrent cache hits
        prof = self.profile
        if prof is None:
            prog = build()
        else:
            t0 = time.perf_counter()
            prog = build()
            prof.compile_event(key, time.perf_counter() - t0)
        with self._mu:
            if key not in self._programs:
                self._programs[key] = prog
                if self._stats is not None:
                    self._stats.programs_built += 1
                while len(self._programs) > self.capacity:
                    self._programs.popitem(last=False)
            else:
                prog = self._programs[key]
        return prog
