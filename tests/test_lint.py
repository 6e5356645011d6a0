"""tier-1 gate for cesslint (cess_tpu/analysis + tools/cesslint.py).

Three proofs per analyzer family (ISSUE 2 acceptance):
- the DIRTY fixture makes each rule fire at the seeded line;
- the CLEAN twin — same shape, violation removed — stays silent
  (zero false positives);
- the real repo is clean: ``cess_tpu/`` has no unsuppressed,
  unbaselined finding, and the whole scan stays under the ~10 s
  budget (each file is parsed once and fanned out to every rule).

Plus the suppression / baseline workflow and the CLI surface.
"""
import json
import os
import subprocess
import sys
import textwrap
import time

import pytest

from cess_tpu import analysis

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASELINE = os.path.join(REPO, "tools", "cesslint_baseline.json")


def lint(src: str, path: str) -> analysis.LintResult:
    return analysis.lint_source(textwrap.dedent(src), path)


def rules_at(result: analysis.LintResult) -> set[str]:
    return {f.rule for f in result.findings}


# ---------------------------------------------------------------------------
# trace safety (ops/, serve/)
# ---------------------------------------------------------------------------
DIRTY_TRACE = """
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    COUNT = 0

    @jax.jit
    def bad(x, y):
        global COUNT
        COUNT += 1
        print("tracing", x)
        a = np.asarray(x)
        b = float(y)
        c = x.sum().item()
        return jnp.asarray(a) + b + c

    @functools.partial(jax.jit, static_argnums=(1,))
    def ok_static(x, n):
        return x + int(n)      # n is static: NOT a tracer

    def tables():
        return (np.uint32(2 ** 40),
                np.array([0, 255, 256], dtype=np.uint8))
"""

CLEAN_TRACE = """
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    @jax.jit
    def good(x, y):
        return jnp.sum(x) + y

    @functools.partial(jax.jit, static_argnums=(1,))
    def good_static(x, n):
        return x + int(n)

    def host_side(x):
        print("host", x)                     # not traced
        return (np.asarray(x), np.uint8(255),
                np.array([0, 255], dtype=np.uint8),
                np.uint32((1 << 32) - 1))
"""


class TestTraceSafety:
    def test_dirty_fixture_fires_every_rule(self):
        r = lint(DIRTY_TRACE, "cess_tpu/ops/fixture.py")
        assert rules_at(r) == {
            "trace-global-mutation", "trace-print",
            "trace-host-transfer", "trace-host-sync",
            "dtype-overflow"}
        # the two dtype hits: folded 2**40 and the list element 256
        dtype = [f for f in r.findings if f.rule == "dtype-overflow"]
        assert len(dtype) == 2
        assert any("1099511627776" in f.message for f in dtype)
        assert any("256" in f.message for f in dtype)

    def test_clean_twin_is_silent(self):
        r = lint(CLEAN_TRACE, "cess_tpu/ops/fixture.py")
        assert r.findings == [] and r.suppressed == []

    def test_call_form_jit_respects_static_args(self):
        src = """
            import jax

            def kern(x, n, mode):
                return x * int(n) * float(mode)

            kern_c = jax.jit(kern, static_argnums=(1,),
                             static_argnames=("mode",))
        """
        r = lint(src, "cess_tpu/ops/fixture.py")
        assert r.findings == []     # both static params excluded
        src_traced = """
            import jax

            def kern(x, n):
                return x * int(n)

            kern_c = jax.jit(kern)
        """
        r = lint(src_traced, "cess_tpu/ops/fixture.py")
        assert [f.rule for f in r.findings] == ["trace-host-sync"]

    def test_trace_rules_do_not_apply_outside_device_code(self):
        r = lint(DIRTY_TRACE, "cess_tpu/chain/fixture.py")
        assert "trace-print" not in rules_at(r)


# ---------------------------------------------------------------------------
# lock discipline (serve/, node/)
# ---------------------------------------------------------------------------
# the serve-engine pattern, seeded with the exact bug class the rule
# exists for: a _cond/_lock-guarded counter written lock-free elsewhere
DIRTY_LOCK = """
    import threading
    import time

    class MiniEngine:
        def __init__(self):
            self._lock = threading.Lock()
            self._cond = threading.Condition(self._lock)
            self._inflight = 0
            self._closed = False

        def submit(self):
            with self._cond:
                self._inflight += 1
                time.sleep(0.05)             # blocks peers out

        def fast_path(self):
            self._inflight -= 1              # guarded elsewhere!

        def close(self):
            with self._lock:
                self._closed = True

    class TwoLocks:
        def __init__(self):
            self.a = threading.Lock()
            self.b = threading.Lock()

        def forward(self):
            with self.a:
                with self.b:
                    pass

        def backward(self):
            with self.b:
                with self.a:
                    pass
"""

CLEAN_LOCK = """
    import threading
    import time

    class MiniEngine:
        def __init__(self):
            self._lock = threading.Lock()
            self._cond = threading.Condition(self._lock)
            self._inflight = 0

        def submit(self):
            with self._cond:
                self._inflight += 1
                self._cond.wait(0.05)        # releases the lock: fine
            time.sleep(0.05)                 # outside the lock: fine

        def _drain_locked(self):
            self._inflight -= 1              # *_locked convention

        def drain(self):
            with self._lock:
                self._drain_locked()

    class TwoLocks:
        def __init__(self):
            self.a = threading.Lock()
            self.b = threading.Lock()

        def forward(self):
            with self.a:
                with self.b:
                    pass

        def also_forward(self):
            with self.a:
                with self.b:
                    pass
"""


class TestLockDiscipline:
    def test_dirty_fixture_fires_every_rule(self):
        r = lint(DIRTY_LOCK, "cess_tpu/serve/fixture.py")
        assert rules_at(r) == {"lock-unguarded-write",
                               "lock-blocking-call", "lock-order-cycle"}
        unguarded = [f for f in r.findings
                     if f.rule == "lock-unguarded-write"]
        assert len(unguarded) == 1
        assert "fast_path" in unguarded[0].message
        assert "_inflight" in unguarded[0].message

    def test_clean_twin_is_silent(self):
        r = lint(CLEAN_LOCK, "cess_tpu/serve/fixture.py")
        assert r.findings == [] and r.suppressed == []

    def test_inconsistent_guard_across_two_locks(self):
        # written under _a in one method, _b in another: no common
        # guard — a data race even though every write "holds a lock"
        src = """
            import threading

            class M:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()
                    self.x = 0

                def f(self):
                    with self._a:
                        self.x += 1

                def f2(self):
                    with self._a:
                        self.x += 2

                def g(self):
                    with self._b:
                        self.x -= 1
        """
        r = lint(src, "cess_tpu/serve/fixture.py")
        bad = [f for f in r.findings if f.rule == "lock-unguarded-write"]
        assert len(bad) == 1
        assert "`g`" in bad[0].message and "_b instead" in bad[0].message

    def test_self_deadlock_and_wait_semantics(self):
        src = """
            import threading

            class M:
                def __init__(self):
                    self.lk = threading.Lock()
                    self.other = threading.Lock()
                    self._cond = threading.Condition(self.lk)
                    self._done = threading.Event()

                def re_enter(self):
                    with self.lk:
                        with self.lk:            # self-deadlock
                            pass

                def event_wait(self):
                    with self.lk:
                        self._done.wait()        # Event.wait BLOCKS

                def cross_wait(self):
                    with self.other:
                        with self._cond:
                            # releases lk only; `other` stays held
                            self._cond.wait()
        """
        r = lint(src, "cess_tpu/serve/fixture.py")
        by_rule = {}
        for f in r.findings:
            by_rule.setdefault(f.rule, []).append(f)
        deadlock = [f for f in by_rule.get("lock-order-cycle", [])
                    if "re-acquired" in f.message]
        assert len(deadlock) == 1
        waits = [f.message for f in by_rule.get("lock-blocking-call", [])]
        assert any("_done.wait" in m for m in waits)
        assert any("_cond.wait" in m for m in waits)

    def test_rlock_reentry_and_own_cond_wait_are_fine(self):
        src = """
            import threading

            class M:
                def __init__(self):
                    self.lk = threading.RLock()
                    self._cond = threading.Condition(self.lk)

                def re_enter(self):
                    with self.lk:
                        with self.lk:            # RLock: reentrant
                            pass

                def wait(self):
                    with self._cond:
                        self._cond.wait()        # releases its lock
        """
        r = lint(src, "cess_tpu/serve/fixture.py")
        assert r.findings == []

    def test_dtype_overflow_applies_to_serve_too(self):
        src = """
            import numpy as np

            PAD = np.uint8(300)
        """
        r = lint(src, "cess_tpu/serve/fixture.py")
        assert [f.rule for f in r.findings] == ["dtype-overflow"]

    def test_serve_engine_is_clean(self):
        """Satellite: the real 700-line lock-and-condvar core passes
        its own analyzer with no unsuppressed findings."""
        path = os.path.join(REPO, "cess_tpu", "serve", "engine.py")
        r = analysis.lint_paths([path], root=REPO)
        assert [f.format() for f in r.findings] == []

    def test_stream_driver_is_clean(self):
        """r06 satellite: the double-buffered streaming driver (host
        loops + device handoffs, a prime trace-safety/lock target)
        passes the serve/ analyzer families with zero findings."""
        paths = [os.path.join(REPO, "cess_tpu", "serve", f)
                 for f in ("stream.py", "stats.py", "buckets.py")]
        r = analysis.lint_paths(paths, root=REPO)
        assert [f.format() for f in r.findings] == []

    def test_node_locking_layers_are_clean(self):
        paths = [os.path.join(REPO, "cess_tpu", "node", f)
                 for f in ("net.py", "rpc.py", "dht.py")]
        r = analysis.lint_paths(paths, root=REPO)
        assert [f.format() for f in r.findings] == []

    def test_resilience_layer_is_clean(self):
        """ISSUE 4 satellite: the resilience package is scanned by the
        lock-discipline family (HealthMonitor windows and
        ResilienceStats counters are touched from batcher + submitter
        threads) and carries zero findings."""
        r = analysis.lint_paths(
            [os.path.join(REPO, "cess_tpu", "resilience")], root=REPO)
        assert r.errors == []
        assert [f.format() for f in r.findings] == []
        # the family really applies there (a dirty fixture fires)
        d = lint(DIRTY_LOCK, "cess_tpu/resilience/fixture.py")
        assert "lock-unguarded-write" in rules_at(d)

    def test_obs_layer_is_clean(self):
        """ISSUE 5 satellite: the tracing package joins the
        trace-safety + lock-discipline clean scan (Tracer ring and
        Span attrs are shared across submitter/batcher/scrape
        threads) and carries zero findings."""
        r = analysis.lint_paths(
            [os.path.join(REPO, "cess_tpu", "obs")], root=REPO)
        assert r.errors == []
        assert [f.format() for f in r.findings] == []
        # both families really apply under obs/ (dirty fixtures fire)
        assert "lock-unguarded-write" in rules_at(
            lint(DIRTY_LOCK, "cess_tpu/obs/fixture.py"))
        assert "trace-print" in rules_at(
            lint(DIRTY_TRACE, "cess_tpu/obs/fixture.py"))

    def test_slo_and_adaptive_layers_are_clean(self):
        """ISSUE 6 satellite: the new SLO board (obs/slo.py — burn
        windows + tenant counters hit from batcher, submitter AND
        scrape threads) and the adaptive control plane
        (serve/adaptive.py — knobs read under the engine lock,
        listeners touching breaker locks) pass the trace-safety,
        lock-discipline and span-balance families with zero findings
        and zero suppressions; the baseline stays empty."""
        paths = [os.path.join(REPO, "cess_tpu", "obs", "slo.py"),
                 os.path.join(REPO, "cess_tpu", "serve", "adaptive.py")]
        r = analysis.lint_paths(paths, root=REPO)
        assert r.errors == []
        assert [f.format() for f in r.findings] == []
        assert r.suppressed == []
        # every family really applies at both paths (dirty fixtures
        # fire there), so the clean scan above is meaningful
        for fixture_path in ("cess_tpu/obs/slo.py",
                             "cess_tpu/serve/adaptive.py"):
            assert "lock-unguarded-write" in rules_at(
                lint(DIRTY_LOCK, fixture_path))
            assert "trace-print" in rules_at(
                lint(DIRTY_TRACE, fixture_path))
            assert "span-balance" in rules_at(
                lint(DIRTY_SPAN, fixture_path))
        baseline = analysis.load_baseline(BASELINE)
        assert baseline == {}

    def test_device_pool_layer_is_clean(self):
        """ISSUE 10 satellite: the device-pool scheduler
        (serve/pool.py — per-lane worker threads draining a shared
        deque under the pool lock, breaker state consulted from the
        submitter thread, flight-journal notes emitted outside the
        lock) passes the trace-safety, lock-discipline and
        span-balance families with zero findings and zero
        suppressions; the baseline stays empty."""
        path = os.path.join(REPO, "cess_tpu", "serve", "pool.py")
        r = analysis.lint_paths([path], root=REPO)
        assert r.errors == []
        assert [f.format() for f in r.findings] == []
        assert r.suppressed == []
        # every family really applies at that path (dirty fixtures
        # fire there), so the clean scan above is meaningful
        assert "lock-unguarded-write" in rules_at(
            lint(DIRTY_LOCK, "cess_tpu/serve/pool.py"))
        assert "trace-print" in rules_at(
            lint(DIRTY_TRACE, "cess_tpu/serve/pool.py"))
        assert "span-balance" in rules_at(
            lint(DIRTY_SPAN, "cess_tpu/serve/pool.py"))
        assert analysis.load_baseline(BASELINE) == {}


# ---------------------------------------------------------------------------
# span balance (tracing discipline, ISSUE 5)
# ---------------------------------------------------------------------------
DIRTY_SPAN = """
    class Engine:
        def __init__(self, tracer):
            self.tracer = tracer

        def go(self):
            sp = self.tracer.start("work", sys="engine")
            sp.set(x=1)
            sp.finish()                  # happy path only: a raise
                                         # between start and here
                                         # leaks the span
"""

CLEAN_SPAN = """
    import threading

    class Engine:
        def __init__(self, tracer):
            self.tracer = tracer
            self._thread = threading.Thread(target=self.go)

        def managed(self):
            with self.tracer.start("work", sys="engine") as sp:
                sp.set(x=1)

        def conditional(self, noop):
            with (self.tracer.start("maybe") if self.tracer else noop):
                pass

        def generator(self):
            sp = None
            try:
                sp = self.tracer.start("run")
                yield 1
            finally:
                if sp is not None:
                    sp.finish()

        def unrelated_start(self):
            self._thread.start()         # Thread.start: not a span
"""


DIRTY_STAGE = """
    from ..obs import trace

    def go(work):
        st = trace.stage("engine.encode.wait")
        st.__enter__()                   # a raise in work() leaves the
        work()                           # profiler annotation open
        st.__exit__(None, None, None)
"""

CLEAN_STAGE = """
    from .. import obs
    from ..obs import trace

    class Engine:
        def _stage(self, cls, stage):
            return trace.stage(f"engine.{cls}.{stage}")

        def go(self, work, sink):
            with trace.stage("gateway.hash"):
                work()
            with obs.stage("stream.put", sink) as put:
                work()
            with self._stage("encode", "wait"):
                work()
            return put.seconds
"""


class TestSpanBalance:
    @pytest.mark.parametrize("dirty,clean,needle", [
        (DIRTY_STAGE, CLEAN_STAGE, "trace.stage"),
        (DIRTY_STAGE.replace('trace.stage("engine.encode.wait")',
                             'self._stage("encode", "wait")'),
         CLEAN_STAGE, "_stage")], ids=["hook", "wrapper"])
    def test_stage_hooks_are_with_items(self, dirty, clean, needle):
        """ISSUE 25: a stage is a with-item like a span — entered by
        hand it can leave a profiler annotation open."""
        r = lint(dirty, "cess_tpu/serve/fixture.py")
        assert [f.rule for f in r.findings] == ["span-balance"]
        assert needle in r.findings[0].message
        r = lint(clean, "cess_tpu/serve/fixture.py")
        assert r.findings == [] and r.suppressed == []

    def test_dirty_fixture_fires(self):
        r = lint(DIRTY_SPAN, "cess_tpu/serve/fixture.py")
        assert [f.rule for f in r.findings] == ["span-balance"]
        assert "tracer.start" in r.findings[0].message

    def test_clean_twin_is_silent(self):
        r = lint(CLEAN_SPAN, "cess_tpu/serve/fixture.py")
        assert r.findings == [] and r.suppressed == []

    def test_only_the_trace_implementation_is_exempt(self):
        # the exemption is exactly obs/trace.py (the implementation
        # being wrapped); the rest of obs/ — slo.py is a CONSUMER of
        # spans — is scanned like everything else (ISSUE 6)
        r = lint(DIRTY_SPAN, "cess_tpu/obs/trace.py")
        assert "span-balance" not in rules_at(r)
        r = lint(DIRTY_SPAN, "cess_tpu/obs/slo.py")
        assert "span-balance" in rules_at(r)

    def test_cross_thread_spans_carry_justified_suppressions(self):
        """The engine's request/batch spans legitimately outlive their
        frames (resolved on the batcher thread): those sites are
        inline-suppressed with justifications, the BASELINE stays
        empty — the rule gates all new code."""
        path = os.path.join(REPO, "cess_tpu", "serve", "engine.py")
        r = analysis.lint_paths([path], root=REPO)
        assert [f.format() for f in r.findings] == []
        assert [f.rule for f in r.suppressed] \
            == ["span-balance"] * 2
        baseline = analysis.load_baseline(BASELINE)
        assert not any(fp.startswith("span-balance|")
                       for fp in baseline)


# ---------------------------------------------------------------------------
# consensus determinism (chain/)
# ---------------------------------------------------------------------------
DIRTY_DET = """
    import hashlib
    import random
    import time

    def apply_block(state, calls):
        h = hashlib.sha256()
        for k, v in state.items():           # dict order -> state root
            h.update(k + v)
        for who in {c.origin for c in calls}:   # set hash order
            pass
        stamp = time.time()
        jitter = random.random()
        fee = 3 / 2
        weight = 0.5
        return h.digest()
"""

CLEAN_DET = """
    import hashlib

    def apply_block(state, calls):
        h = hashlib.sha256()
        for k, v in sorted(state.items()):
            h.update(k + v)
        for who in sorted({c.origin for c in calls}):
            pass
        total = sum(c.fee for c in calls)    # order-insensitive fold
        fee = 3 // 2
        return h.digest()
"""


class TestDeterminism:
    def test_dirty_fixture_fires_every_rule(self):
        r = lint(DIRTY_DET, "cess_tpu/chain/fixture.py")
        assert rules_at(r) == {"consensus-unordered-iter",
                               "consensus-wallclock", "consensus-float"}
        assert len([f for f in r.findings
                    if f.rule == "consensus-unordered-iter"]) == 2
        assert len([f for f in r.findings
                    if f.rule == "consensus-float"]) == 2

    def test_clean_twin_is_silent(self):
        r = lint(CLEAN_DET, "cess_tpu/chain/fixture.py")
        assert r.findings == [] and r.suppressed == []

    def test_bare_iteration_over_locally_built_containers(self):
        src = """
            def apply(items):
                seen = set()
                index = {}
                for it in items:
                    index[it.key] = it
                for k in index:            # bare dict iteration
                    pass
                for s in seen:             # bare set iteration
                    pass
                ordered = sorted(index)
                for k in ordered:          # fine
                    pass
        """
        r = lint(src, "cess_tpu/chain/fixture.py")
        assert [f.rule for f in r.findings] == \
            ["consensus-unordered-iter"] * 2

    def test_reassigned_name_is_ambiguous_not_flagged(self):
        src = """
            def apply(flag, items):
                d = {}
                if flag:
                    d = sorted(items)      # no longer a dict
                for k in d:
                    pass
        """
        assert lint(src, "cess_tpu/chain/fixture.py").findings == []

    def test_chain_rules_do_not_apply_to_device_code(self):
        r = lint(DIRTY_DET, "cess_tpu/ops/fixture.py")
        assert r.findings == []


# ---------------------------------------------------------------------------
# sim determinism (sim/)
# ---------------------------------------------------------------------------
DIRTY_SIM = """
    import random
    import secrets
    import time

    import numpy as np

    def run_round(world):
        start = time.monotonic()
        time.sleep(0.05)
        jitter = random.random()
        noise = np.random.uniform()
        nonce = secrets.token_bytes(8)
        return time.time() - start
"""

CLEAN_SIM = """
    import hashlib

    def run_round(world):
        world.clock.sleep(0.05)
        h = hashlib.sha256(world.seed + b"|round").digest()
        jitter = int.from_bytes(h[:8], "big") / 2**64
        return world.clock.now()
"""


class TestSimDeterminism:
    def test_dirty_fixture_fires_every_rule(self):
        r = lint(DIRTY_SIM, "cess_tpu/sim/fixture.py")
        assert rules_at(r) == {"sim-wallclock", "sim-entropy"}
        wall = [f.message for f in r.findings if f.rule == "sim-wallclock"]
        # time.sleep is banned too: it blocks the host for virtual
        # time the SimClock should absorb
        assert any("time.sleep" in m for m in wall)
        assert any("time.time" in m for m in wall)
        assert any("time.monotonic" in m for m in wall)
        ent = [f.message for f in r.findings if f.rule == "sim-entropy"]
        assert any("random.random" in m for m in ent)
        assert any("np.random" in m for m in ent)
        assert any("secrets." in m for m in ent)

    def test_clean_twin_is_silent(self):
        r = lint(CLEAN_SIM, "cess_tpu/sim/fixture.py")
        assert r.findings == [] and r.suppressed == []

    def test_sim_rules_do_not_apply_elsewhere(self):
        # node/ legitimately sleeps and reads wall clocks
        assert lint(DIRTY_SIM, "cess_tpu/node/fixture.py").findings == []

    def test_retention_layer_joins_the_family(self):
        """ISSUE 9: the flight recorder's pin/bundle decisions are
        under the same replay contract as sim worlds — the determinism
        rules fire at obs/flight.py and obs/incident.py, the clean
        (seeded SHA-256) twin stays silent there, and the rest of
        obs/ (which legitimately reads the wall clock for span
        timing) is untouched."""
        for path in ("cess_tpu/obs/flight.py",
                     "cess_tpu/obs/incident.py"):
            assert rules_at(lint(DIRTY_SIM, path)) == \
                {"sim-wallclock", "sim-entropy"}, path
            assert lint(CLEAN_SIM, path).findings == []
        assert lint(DIRTY_SIM, "cess_tpu/obs/trace.py").findings == []

    def test_fleet_plane_joins_the_family(self):
        """ISSUE 12: the fleet plane's scrape rounds, straggler scans
        and transition logs are count-sequenced into the replay
        witness, so obs/fleet.py joins the determinism family next to
        flight.py and incident.py — and the clean twin stays
        silent."""
        assert rules_at(lint(DIRTY_SIM, "cess_tpu/obs/fleet.py")) == \
            {"sim-wallclock", "sim-entropy"}
        assert lint(CLEAN_SIM, "cess_tpu/obs/fleet.py").findings == []

    def test_profile_plane_joins_the_family(self):
        """ISSUE 13: the continuous-profiling plane's accounts,
        ledgers and watchdog transition log are count-sequenced into
        the replay witness (every timing is measured by serve-layer
        callers and passed in), so obs/profile.py joins the
        determinism family — and the clean twin stays silent."""
        assert rules_at(lint(DIRTY_SIM, "cess_tpu/obs/profile.py")) == \
            {"sim-wallclock", "sim-entropy"}
        assert lint(CLEAN_SIM, "cess_tpu/obs/profile.py").findings == []

    def test_chainwatch_plane_joins_the_family(self):
        """ISSUE 14: the chain plane's scans, evidence log and anomaly
        transitions are count-sequenced into the replay witness, so
        obs/chainwatch.py joins the determinism family next to
        fleet.py and profile.py — and the clean twin stays silent."""
        assert rules_at(
            lint(DIRTY_SIM, "cess_tpu/obs/chainwatch.py")) == \
            {"sim-wallclock", "sim-entropy"}
        assert lint(CLEAN_SIM,
                    "cess_tpu/obs/chainwatch.py").findings == []

    def test_custody_plane_joins_the_family(self):
        """ISSUE 20: the custody plane's ledger event log, margin
        folds and detector transitions are the eighth replay witness
        stream (same seed => byte-identical custody bytes), so
        obs/custody.py joins the determinism family next to
        chainwatch.py — and the clean twin stays silent."""
        assert rules_at(
            lint(DIRTY_SIM, "cess_tpu/obs/custody.py")) == \
            {"sim-wallclock", "sim-entropy"}
        assert lint(CLEAN_SIM,
                    "cess_tpu/obs/custody.py").findings == []

    def test_custody_module_scans_clean_under_every_family(self):
        """ISSUE 20 satellite: the shipped obs/custody.py passes
        trace-safety, lock-discipline, span-balance AND the sim
        determinism family with zero suppressions (witness-purity,
        race and seam-cost apply package-wide and cover it through
        the full-tree scan); the dirty twins prove each family really
        fires at that path, and the baseline stays empty."""
        for dirty, rule in ((DIRTY_TRACE, "trace-print"),
                            (DIRTY_LOCK, "lock-unguarded-write"),
                            (DIRTY_SPAN, "span-balance"),
                            (DIRTY_SIM, "sim-wallclock")):
            assert rule in rules_at(
                lint(dirty, "cess_tpu/obs/custody.py")), rule
        r = analysis.lint_paths(
            [os.path.join(REPO, "cess_tpu", "obs", "custody.py")],
            root=REPO)
        assert r.errors == []
        assert [f.format() for f in r.findings] == []
        assert r.suppressed == []
        assert analysis.load_baseline(BASELINE) == {}

    def test_regen_repair_plane_joins_the_family(self):
        """ISSUE 15: the regenerating repair plane's coefficient and
        matrix constructions feed the repair storm's replay contract,
        so ops/regen.py joins the determinism AND lock-discipline
        families — while the rest of ops/ (pure device math with no
        shared caches) stays exempt from both."""
        assert rules_at(
            lint(DIRTY_SIM, "cess_tpu/ops/regen.py")) == \
            {"sim-wallclock", "sim-entropy"}
        assert lint(CLEAN_SIM, "cess_tpu/ops/regen.py").findings == []
        assert "lock-unguarded-write" in rules_at(
            lint(DIRTY_LOCK, "cess_tpu/ops/regen.py"))
        # the lock-clean twin sleeps outside the lock, which the
        # (also-applying) sim family flags — so assert only that no
        # lock-family rule fires at the regen path
        assert not any(
            r.startswith("lock-")
            for r in rules_at(lint(CLEAN_LOCK, "cess_tpu/ops/regen.py")))
        # other ops modules do NOT inherit the two borrowed families
        assert lint(DIRTY_SIM, "cess_tpu/ops/fixture.py").findings == []
        assert lint(DIRTY_LOCK, "cess_tpu/ops/fixture.py").findings == []

    def test_regen_module_scans_clean_under_every_family(self):
        """ISSUE 15 satellite: the shipped ops/regen.py passes
        trace-safety, lock-discipline, span-balance AND the sim
        determinism family with zero suppressions; the dirty twins
        prove each family really fires at that path, and the baseline
        stays empty."""
        for dirty, rule in ((DIRTY_TRACE, "trace-print"),
                            (DIRTY_LOCK, "lock-unguarded-write"),
                            (DIRTY_SPAN, "span-balance"),
                            (DIRTY_SIM, "sim-wallclock")):
            assert rule in rules_at(
                lint(dirty, "cess_tpu/ops/regen.py")), rule
        r = analysis.lint_paths(
            [os.path.join(REPO, "cess_tpu", "ops", "regen.py")],
            root=REPO)
        assert r.errors == []
        assert [f.format() for f in r.findings] == []
        assert r.suppressed == []
        assert analysis.load_baseline(BASELINE) == {}

    def test_remediate_module_scans_clean_under_every_family(self):
        """ISSUE 16 satellite: the shipped serve/remediate.py passes
        trace-safety, lock-discipline, span-balance AND the sim
        determinism family with zero suppressions — the plane's
        count-sequenced journal is under the same replay contract as
        the retention layer, so the wallclock/entropy bans apply on
        top of the usual serve/ families. The dirty twins prove each
        family really fires at that exact path, the clean sim twin
        stays silent there, and the baseline stays empty."""
        for dirty, rule in ((DIRTY_TRACE, "trace-print"),
                            (DIRTY_LOCK, "lock-unguarded-write"),
                            (DIRTY_SPAN, "span-balance"),
                            (DIRTY_SIM, "sim-wallclock")):
            assert rule in rules_at(
                lint(dirty, "cess_tpu/serve/remediate.py")), rule
        assert lint(CLEAN_SIM,
                    "cess_tpu/serve/remediate.py").findings == []
        # the borrow is scoped to remediate.py: its serve/ siblings do
        # NOT inherit the determinism family
        assert lint(DIRTY_SIM,
                    "cess_tpu/serve/fixture.py").findings == []
        r = analysis.lint_paths(
            [os.path.join(REPO, "cess_tpu", "serve", "remediate.py")],
            root=REPO)
        assert r.errors == []
        assert [f.format() for f in r.findings] == []
        assert r.suppressed == []
        assert analysis.load_baseline(BASELINE) == {}

    def test_chainwatch_module_scans_clean_under_every_family(self):
        """ISSUE 14 satellite: the shipped obs/chainwatch.py passes
        trace-safety, lock-discipline, span-balance AND the sim
        determinism family with zero suppressions; the dirty twins
        prove each family really fires at that path, and the baseline
        stays empty."""
        for dirty, rule in ((DIRTY_TRACE, "trace-print"),
                            (DIRTY_LOCK, "lock-unguarded-write"),
                            (DIRTY_SPAN, "span-balance"),
                            (DIRTY_SIM, "sim-wallclock")):
            assert rule in rules_at(
                lint(dirty, "cess_tpu/obs/chainwatch.py")), rule
        r = analysis.lint_paths(
            [os.path.join(REPO, "cess_tpu", "obs", "chainwatch.py")],
            root=REPO)
        assert r.errors == []
        assert [f.format() for f in r.findings] == []
        assert r.suppressed == []
        assert analysis.load_baseline(BASELINE) == {}

    def test_profile_module_scans_clean_under_every_family(self):
        """ISSUE 13 satellite: the shipped obs/profile.py passes
        trace-safety, lock-discipline, span-balance AND the sim
        determinism family with zero suppressions; the dirty twins
        prove each family really fires at that path, and the baseline
        stays empty."""
        for dirty, rule in ((DIRTY_TRACE, "trace-print"),
                            (DIRTY_LOCK, "lock-unguarded-write"),
                            (DIRTY_SPAN, "span-balance"),
                            (DIRTY_SIM, "sim-wallclock")):
            assert rule in rules_at(
                lint(dirty, "cess_tpu/obs/profile.py")), rule
        r = analysis.lint_paths(
            [os.path.join(REPO, "cess_tpu", "obs", "profile.py")],
            root=REPO)
        assert r.errors == []
        assert [f.format() for f in r.findings] == []
        assert r.suppressed == []
        assert analysis.load_baseline(BASELINE) == {}

    def test_fleet_module_scans_clean_under_every_family(self):
        """ISSUE 12 satellite: the shipped obs/fleet.py passes
        trace-safety, lock-discipline, span-balance AND the sim
        determinism family with zero suppressions; the dirty twins
        prove each family really fires at that path, and the baseline
        stays empty."""
        for dirty, rule in ((DIRTY_TRACE, "trace-print"),
                            (DIRTY_LOCK, "lock-unguarded-write"),
                            (DIRTY_SPAN, "span-balance"),
                            (DIRTY_SIM, "sim-wallclock")):
            assert rule in rules_at(
                lint(dirty, "cess_tpu/obs/fleet.py")), rule
        r = analysis.lint_paths(
            [os.path.join(REPO, "cess_tpu", "obs", "fleet.py")],
            root=REPO)
        assert r.errors == []
        assert [f.format() for f in r.findings] == []
        assert r.suppressed == []
        assert analysis.load_baseline(BASELINE) == {}

    def test_retention_modules_scan_clean(self):
        """ISSUE 9 satellite: the shipped retention layer passes its
        own determinism family (plus every other applicable rule)
        with zero suppressions; baseline stays empty."""
        r = analysis.lint_paths(
            [os.path.join(REPO, "cess_tpu", "obs", "flight.py"),
             os.path.join(REPO, "cess_tpu", "obs", "incident.py")],
            root=REPO)
        assert r.errors == []
        assert [f.format() for f in r.findings] == []
        assert r.suppressed == []
        assert analysis.load_baseline(BASELINE) == {}

    def test_sim_package_is_clean(self):
        """ISSUE 8 satellite: the whole sim harness scans clean under
        its own determinism family PLUS trace-safety and
        lock-discipline, with zero suppressions; baseline stays
        empty."""
        r = analysis.lint_paths(
            [os.path.join(REPO, "cess_tpu", "sim")], root=REPO)
        assert r.errors == []
        assert [f.format() for f in r.findings] == []
        assert r.suppressed == []
        # the borrowed families really apply under sim/ (dirty
        # fixtures fire there), so the clean scan is meaningful
        assert "lock-unguarded-write" in rules_at(
            lint(DIRTY_LOCK, "cess_tpu/sim/fixture.py"))
        assert "trace-print" in rules_at(
            lint(DIRTY_TRACE, "cess_tpu/sim/fixture.py"))
        assert analysis.load_baseline(BASELINE) == {}


# ---------------------------------------------------------------------------
# interprocedural dataflow families (analysis/flow.py):
# witness-purity, race, seam-cost
# ---------------------------------------------------------------------------
DIRTY_TAINT_CALL = """
    import time

    class Report:
        def _stamp(self):
            return time.monotonic()

        def witness(self):
            return (self._stamp(), 42)
"""

DIRTY_TAINT_FIELD = """
    import time

    class Report:
        def __init__(self):
            self.t0 = 0.0
            self._journal = []

        def start(self):
            self.t0 = time.time()

        def note(self, kind):
            self._journal.append((kind, self.t0))
"""

CLEAN_TAINT = """
    import time

    class Report:
        def __init__(self):
            self.seq = 0
            self.t0 = 0.0
            self._journal = []

        def start(self):
            self.t0 = time.time()     # observed, never witnessed

        def note(self, kind):
            self.seq += 1
            self._journal.append((self.seq, kind))   # count-sequenced

        def witness(self):
            return tuple(self._journal)

        def uptime(self):
            return time.time() - self.t0
"""


class TestWitnessPurity:
    def test_taint_through_call(self):
        r = lint(DIRTY_TAINT_CALL, "cess_tpu/node/fixture.py")
        assert rules_at(r) == {"witness-purity"}
        f = r.findings[0]
        assert "time.monotonic" in f.message and "witness" in f.message

    def test_taint_through_field(self):
        r = lint(DIRTY_TAINT_FIELD, "cess_tpu/node/fixture.py")
        assert rules_at(r) == {"witness-purity"}
        assert "_journal" in r.findings[0].message
        assert "time.time" in r.findings[0].message

    def test_clean_twin_is_silent(self):
        # wallclock observed for timing but kept OUT of the witness
        # bytes — the house design, not a finding
        r = lint(CLEAN_TAINT, "cess_tpu/node/fixture.py")
        assert r.findings == [] and r.suppressed == []

    def test_order_escape_into_witness(self):
        src = """
            class Report:
                def __init__(self):
                    self._seen = {}
                    self._journal = []

                def note(self, key):
                    self._seen[key] = True
                    for k in self._seen.keys():
                        self._journal.append(k)
        """
        r = lint(src, "cess_tpu/node/fixture.py")
        assert rules_at(r) == {"witness-purity"}
        assert "iteration order" in r.findings[0].message

    def test_sorted_order_escape_is_clean(self):
        src = """
            class Report:
                def __init__(self):
                    self._seen = {}
                    self._journal = []

                def note(self, key):
                    self._seen[key] = True
                    for k in sorted(self._seen.keys()):
                        self._journal.append(k)
        """
        r = lint(src, "cess_tpu/node/fixture.py")
        assert r.findings == []


DIRTY_RACE = """
    import threading

    class Worker:
        def __init__(self):
            self.count = 0
            self._thread = threading.Thread(target=self._run)
            self._thread.start()

        def _run(self):
            while True:
                self.count += 1

        def poke(self):
            self.count = 0
"""

CLEAN_RACE = """
    import threading

    class Worker:
        def __init__(self):
            self._lock = threading.Lock()
            self.count = 0
            self._thread = threading.Thread(target=self._run)
            self._thread.start()

        def _run(self):
            while True:
                with self._lock:
                    self.count += 1

        def poke(self):
            with self._lock:
                self.count = 0
"""


class TestRace:
    def test_two_thread_unguarded_write_fires(self):
        r = lint(DIRTY_RACE, "cess_tpu/serve/fixture.py")
        assert rules_at(r) == {"race"}
        f = r.findings[0]
        assert "Worker.count" in f.message
        assert "thread:_run" in f.message and "caller" in f.message

    def test_guarded_write_clean(self):
        r = lint(CLEAN_RACE, "cess_tpu/serve/fixture.py")
        assert r.findings == [] and r.suppressed == []

    def test_single_writer_multi_reader_exempt(self):
        src = """
            import threading

            class Worker:
                def __init__(self):
                    self.count = 0
                    self._thread = threading.Thread(target=self._run)
                    self._thread.start()

                def _run(self):
                    while True:
                        self.count += 1

                def snapshot(self):
                    return self.count        # read-only: no guard needed
        """
        r = lint(src, "cess_tpu/serve/fixture.py")
        assert r.findings == []

    def test_pre_thread_start_init_exempt(self):
        # __init__ writes happen before the object is published to
        # any thread — both fixtures above rely on it; make it explicit
        r = lint(CLEAN_RACE, "cess_tpu/serve/fixture.py")
        assert all("__init__" not in f.message for f in r.findings)

    def test_listener_root_counts_as_a_thread(self):
        src = """
            import threading

            class Plane:
                def __init__(self, recorder):
                    self.hits = 0
                    recorder.add_listener(self.on_note)

                def on_note(self, note):
                    self.hits += 1

                def reset(self):
                    self.hits = 0
        """
        r = lint(src, "cess_tpu/serve/fixture.py")
        assert rules_at(r) == {"race"}
        assert "listener:on_note" in r.findings[0].message


DIRTY_SEAM = """
    _RECORDER = None

    def note(subsystem, kind):
        payload = f"{subsystem}:{kind}"
        rec = _RECORDER
        if rec is None:
            return
        rec.note(payload)
"""

CLEAN_SEAM = """
    _RECORDER = None

    def note(subsystem, kind):
        rec = _RECORDER
        if rec is None:
            return
        payload = f"{subsystem}:{kind}"
        rec.note(payload)
"""


class TestSeamCost:
    def test_fat_disarmed_seam_fires(self):
        r = lint(DIRTY_SEAM, "cess_tpu/obs/fixture.py")
        assert rules_at(r) == {"seam-cost"}
        assert "before the disarmed-seam guard" in r.findings[0].message

    def test_one_load_clean(self):
        r = lint(CLEAN_SEAM, "cess_tpu/obs/fixture.py")
        assert r.findings == [] and r.suppressed == []

    def test_allocation_before_attr_seam_fires(self):
        src = """
            class Engine:
                def _account(self, n):
                    detail = {"rows": n}
                    slo = self.slo
                    if slo is None:
                        return
                    slo.observe(detail)
        """
        r = lint(src, "cess_tpu/serve/fixture.py")
        assert rules_at(r) == {"seam-cost"}

    def test_contextvar_get_is_load_equivalent(self):
        # the trace.event idiom: _CURRENT.get() before the guard is
        # one load, not work
        src = """
            import contextvars

            _CURRENT = contextvars.ContextVar("span", default=None)

            def event(name):
                sp = _CURRENT.get()
                if sp is not None:
                    sp.event(name)
        """
        r = lint(src, "cess_tpu/obs/fixture.py")
        assert r.findings == []

    def test_work_then_note_functions_are_not_seams(self):
        # real work before a LATE guard is armed-and-disarmed work,
        # not a seam violation (the audit stops at the first
        # non-bind statement)
        src = """
            _RECORDER = None

            class Engine:
                def close(self):
                    self._drain()
                    rec = _RECORDER
                    if rec is None:
                        return
                    rec.note("closed")

                def _drain(self):
                    pass
        """
        r = lint(src, "cess_tpu/serve/fixture.py")
        assert r.findings == []

    def test_registered_hook_without_guard_fires(self):
        src = """
            _RECORDER = None

            def note(subsystem, kind):
                print(subsystem, kind)
        """
        r = lint(src, "cess_tpu/obs/flight.py")
        assert "seam-cost" in rules_at(r)
        assert "registered zero-cost hook" in r.findings[0].message


# ---------------------------------------------------------------------------
# acceptance seeding: each contract violation planted in the REAL
# tree produces exactly the expected finding (ISSUE 17 acceptance)
# ---------------------------------------------------------------------------
class TestSeededRegressions:
    def test_wallclock_seeded_into_sim_witness_dataflow(self):
        path = os.path.join(REPO, "cess_tpu", "sim", "scenarios.py")
        with open(path, encoding="utf-8") as fh:
            src = fh.read()
        assert "    def witness(self) -> tuple:" in src
        seeded = ("import time\n" + src).replace(
            "    def witness(self) -> tuple:",
            "    def _stamp(self) -> float:\n"
            "        return time.monotonic()\n\n"
            "    def witness(self) -> tuple:", 1).replace(
            "        return (self.world.queue.fired_log(),",
            "        return (self._stamp(),\n"
            "                self.world.queue.fired_log(),", 1)
        assert seeded != "import time\n" + src
        r = analysis.lint_source(seeded, "cess_tpu/sim/scenarios.py")
        # the interprocedural taint finding (plus the per-file
        # sim-wallclock rule seeing the same read)
        assert rules_at(r) == {"witness-purity", "sim-wallclock"}
        wp = [f for f in r.findings if f.rule == "witness-purity"]
        assert len(wp) == 1
        assert "SimReport.witness" in wp[0].message
        assert "time.monotonic" in wp[0].message

    def test_unguarded_cross_thread_write_seeded_into_engine(self):
        path = os.path.join(REPO, "cess_tpu", "serve", "engine.py")
        with open(path, encoding="utf-8") as fh:
            src = fh.read()
        anchor = "    def _run(self) -> None:"
        assert anchor in src
        seeded = src.replace(
            anchor,
            "    def poke_seeded(self) -> None:\n"
            "        self._seeded_counter = 1\n\n"
            + anchor + "\n        self._seeded_counter = 2", 1)
        r = analysis.lint_source(seeded, "cess_tpu/serve/engine.py")
        assert rules_at(r) == {"race"}
        assert len(r.findings) == 1
        assert "_seeded_counter" in r.findings[0].message
        assert "thread:_run" in r.findings[0].message

    def test_allocation_seeded_before_flight_note_guard(self):
        path = os.path.join(REPO, "cess_tpu", "obs", "flight.py")
        with open(path, encoding="utf-8") as fh:
            src = fh.read()
        guard = ("    rec = _RECORDER\n"
                 "    if rec is None:\n"
                 "        return\n")
        assert guard in src
        seeded = src.replace(
            guard,
            "    payload = f\"{subsystem}:{kind}\"\n" + guard, 1)
        r = analysis.lint_source(seeded, "cess_tpu/obs/flight.py")
        assert rules_at(r) == {"seam-cost"}
        assert len(r.findings) == 1
        assert "payload" in r.findings[0].message

    def test_net_conn_alive_race_suppression_is_load_bearing(self):
        # the one in-tree race suppression (monotonic one-shot bool in
        # _Conn.close): still needed, still justified
        path = os.path.join(REPO, "cess_tpu", "node", "net.py")
        with open(path, encoding="utf-8") as fh:
            src = fh.read()
        r = analysis.lint_source(src, "cess_tpu/node/net.py")
        assert r.findings == []
        assert [f.rule for f in r.suppressed] == ["race"]
        assert "_Conn.alive" in r.suppressed[0].message
        stripped = src.replace("        # cesslint: disable=race\n", "")
        assert stripped != src
        r2 = analysis.lint_source(stripped, "cess_tpu/node/net.py")
        assert [f.rule for f in r2.findings] == ["race"]
        assert "_Conn.alive" in r2.findings[0].message


# ---------------------------------------------------------------------------
# suppression + baseline workflow
# ---------------------------------------------------------------------------
class TestSuppression:
    def test_same_line_comment(self):
        src = """
            import time

            def apply_block():
                return time.time()  # cesslint: disable=consensus-wallclock
        """
        r = lint(src, "cess_tpu/chain/fixture.py")
        assert r.findings == []
        assert [f.rule for f in r.suppressed] == ["consensus-wallclock"]

    def test_own_line_comment_covers_next_line(self):
        src = """
            import time

            def apply_block():
                # justified: dev-only scaffolding
                # cesslint: disable=consensus-wallclock
                return time.time()
        """
        r = lint(src, "cess_tpu/chain/fixture.py")
        assert r.findings == []
        assert len(r.suppressed) == 1

    def test_trailing_prose_does_not_break_the_id(self):
        src = """
            import time

            def f():
                return time.time()  # cesslint: disable=consensus-wallclock — why not
        """
        assert lint(src, "cess_tpu/chain/fixture.py").findings == []

    def test_wrong_rule_id_does_not_silence(self):
        src = """
            import time

            def f():
                return time.time()  # cesslint: disable=consensus-float
        """
        r = lint(src, "cess_tpu/chain/fixture.py")
        assert [f.rule for f in r.findings] == ["consensus-wallclock"]

    def test_bare_disable_silences_all(self):
        src = """
            import time

            def f():
                return time.time() / 2  # cesslint: disable
        """
        r = lint(src, "cess_tpu/chain/fixture.py")
        assert r.findings == [] and len(r.suppressed) == 2

    def test_unknown_directive_tail_does_not_blanket_suppress(self):
        # a typo'd directive must not silently disable the gate
        src = """
            import time

            def f():
                return time.time()  # cesslint: disablegarbage
        """
        r = lint(src, "cess_tpu/chain/fixture.py")
        assert [f.rule for f in r.findings] == ["consensus-wallclock"]


class TestBaseline:
    def test_roundtrip_and_line_shift_tolerance(self, tmp_path):
        r = lint(DIRTY_DET, "cess_tpu/chain/fixture.py")
        assert r.findings
        bl_file = str(tmp_path / "bl.json")
        analysis.write_baseline(r.findings, bl_file)
        baseline = analysis.load_baseline(bl_file)
        # identical findings: all baselined
        new, matched = analysis.apply_baseline(r.findings, baseline)
        assert new == [] and len(matched) == len(r.findings)
        # shifting every line (fingerprints are line-independent)
        shifted = lint("\n\n\n" + textwrap.dedent(DIRTY_DET),
                       "cess_tpu/chain/fixture.py")
        new, _ = analysis.apply_baseline(shifted.findings, baseline)
        assert new == []
        # a NEW instance of a baselined pattern still surfaces
        doubled = lint(textwrap.dedent(DIRTY_DET)
                       + "\nBAD_WEIGHT = 0.25\n",
                       "cess_tpu/chain/fixture.py")
        new, _ = analysis.apply_baseline(doubled.findings, baseline)
        assert [f.rule for f in new] == ["consensus-float"]
        assert "0.25" in new[0].message

    def test_missing_baseline_is_empty(self, tmp_path):
        assert analysis.load_baseline(str(tmp_path / "nope.json")) == {}


# ---------------------------------------------------------------------------
# suppression audit (--audit-suppressions): inline disables that no
# longer silence anything are debt, not documentation
# ---------------------------------------------------------------------------
STALE_SUPPRESS = """
    SAFE = 1  # cesslint: disable=consensus-wallclock — long fixed
"""

LIVE_SUPPRESS = """
    import time

    T = time.time()  # cesslint: disable=consensus-wallclock
"""


class TestSuppressionAudit:
    def test_stale_directive_reported(self):
        r = lint(STALE_SUPPRESS, "cess_tpu/chain/fixture.py")
        assert r.findings == [] and r.suppressed == []
        assert r.stale_suppressions == [
            ("cess_tpu/chain/fixture.py", 2, ("consensus-wallclock",))]

    def test_load_bearing_directive_not_reported(self):
        r = lint(LIVE_SUPPRESS, "cess_tpu/chain/fixture.py")
        assert [f.rule for f in r.suppressed] == ["consensus-wallclock"]
        assert r.stale_suppressions == []

    def test_partially_stale_directive_names_the_dead_id(self):
        src = """
            import time

            T = time.time()  # cesslint: disable=consensus-wallclock,consensus-float
        """
        r = lint(src, "cess_tpu/chain/fixture.py")
        assert [f.rule for f in r.suppressed] == ["consensus-wallclock"]
        assert r.stale_suppressions == [
            ("cess_tpu/chain/fixture.py", 4, ("consensus-float",))]

    def test_bare_disable_stale_only_when_nothing_silenced(self):
        live = lint("""
            import time

            T = time.time()  # cesslint: disable
        """, "cess_tpu/chain/fixture.py")
        assert live.stale_suppressions == []
        dead = lint("SAFE = 1  # cesslint: disable\n",
                    "cess_tpu/chain/fixture.py")
        assert dead.stale_suppressions == [
            ("cess_tpu/chain/fixture.py", 1, ("*",))]

    def test_repo_has_no_stale_suppressions(self):
        r = analysis.lint_paths([os.path.join(REPO, "cess_tpu")],
                                root=REPO)
        assert r.stale_suppressions == []

    def test_cli_audit_dirty_and_clean(self, tmp_path):
        d = tmp_path / "chain"
        d.mkdir()
        stale = d / "stale.py"
        stale.write_text(textwrap.dedent(STALE_SUPPRESS))
        # without the flag, a stale disable is invisible (exit 0)
        code, out = _run_cli(str(stale), "--no-baseline")
        assert code == 0, out
        code, out = _run_cli(str(stale), "--no-baseline",
                             "--audit-suppressions")
        assert code == 1
        assert "stale suppression" in out
        assert "consensus-wallclock" in out
        live = d / "live.py"
        live.write_text(textwrap.dedent(LIVE_SUPPRESS))
        code, out = _run_cli(str(live), "--no-baseline",
                             "--audit-suppressions")
        assert code == 0, out

    def test_cli_audit_forbids_rule_filter(self):
        # a narrowed run would mark every other family's suppression
        # stale — refuse instead of lying
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "cesslint.py"),
             "--audit-suppressions", "--rule", "race"],
            capture_output=True, text=True, cwd=REPO, timeout=120)
        assert proc.returncode == 2
        assert "drop --rule" in proc.stderr

    def test_cli_audit_json_shape(self, tmp_path):
        d = tmp_path / "chain"
        d.mkdir()
        stale = d / "stale.py"
        stale.write_text(textwrap.dedent(STALE_SUPPRESS))
        code, out = _run_cli(str(stale), "--no-baseline",
                             "--audit-suppressions", "--json")
        assert code == 1
        data = json.loads(out)
        assert data["findings"] == []
        assert len(data["stale_suppressions"]) == 1
        entry = data["stale_suppressions"][0]
        assert entry["line"] == 2
        assert entry["rules"] == ["consensus-wallclock"]


# ---------------------------------------------------------------------------
# SARIF 2.1.0 export
# ---------------------------------------------------------------------------
# offline structural schema: the required-property skeleton of SARIF
# 2.1.0 (the full OASIS schema needs network access to fetch; this
# pins the invariants code-scanning consumers actually reject on)
SARIF_MINI_SCHEMA = {
    "type": "object",
    "required": ["version", "runs"],
    "properties": {
        "version": {"const": "2.1.0"},
        "$schema": {"type": "string"},
        "runs": {
            "type": "array",
            "minItems": 1,
            "items": {
                "type": "object",
                "required": ["tool"],
                "properties": {
                    "tool": {
                        "type": "object",
                        "required": ["driver"],
                        "properties": {
                            "driver": {
                                "type": "object",
                                "required": ["name"],
                                "properties": {
                                    "name": {"type": "string"},
                                    "rules": {
                                        "type": "array",
                                        "items": {
                                            "type": "object",
                                            "required": ["id"],
                                        },
                                    },
                                },
                            },
                        },
                    },
                    "results": {
                        "type": "array",
                        "items": {
                            "type": "object",
                            "required": ["message"],
                            "properties": {
                                "ruleId": {"type": "string"},
                                "ruleIndex": {"type": "integer",
                                              "minimum": 0},
                                "level": {"enum": ["none", "note",
                                                   "warning", "error"]},
                                "message": {
                                    "type": "object",
                                    "required": ["text"],
                                },
                                "locations": {
                                    "type": "array",
                                    "items": {
                                        "type": "object",
                                        "properties": {
                                            "physicalLocation": {
                                                "type": "object",
                                                "required":
                                                    ["artifactLocation"],
                                                "properties": {
                                                    "region": {
                                                        "type": "object",
                                                        "properties": {
                                                            "startLine": {
                                                                "type":
                                                                "integer",
                                                                "minimum":
                                                                1},
                                                        },
                                                    },
                                                },
                                            },
                                        },
                                    },
                                },
                            },
                        },
                    },
                },
            },
        },
    },
}


class TestSarif:
    def _validate(self, doc):
        jsonschema = pytest.importorskip("jsonschema")
        jsonschema.validate(doc, SARIF_MINI_SCHEMA)

    def test_report_structure_and_schema(self):
        r = lint(DIRTY_LOCK, "cess_tpu/serve/fixture.py")
        assert r.findings
        doc = analysis.sarif_report(r.findings, analysis.all_rules())
        self._validate(doc)
        run = doc["runs"][0]
        assert run["tool"]["driver"]["name"] == "cesslint"
        assert len(run["results"]) == len(r.findings)
        rule_ids = [m["id"] for m in run["tool"]["driver"]["rules"]]
        assert rule_ids == sorted(set(rule_ids))    # deduped + sorted
        for res, f in zip(run["results"], r.findings):
            assert res["ruleId"] == f.rule
            assert rule_ids[res["ruleIndex"]] == f.rule
            loc = res["locations"][0]["physicalLocation"]
            assert loc["artifactLocation"]["uri"] == f.path
            assert loc["region"]["startLine"] == f.line
            assert res["partialFingerprints"]["cesslint/v1"] \
                == f.fingerprint()
        # driver rules carry the human metadata
        assert all("shortDescription" in m
                   for m in run["tool"]["driver"]["rules"])

    def test_empty_report_is_still_valid(self):
        doc = analysis.sarif_report([])
        self._validate(doc)
        assert doc["runs"][0]["results"] == []
        assert doc["runs"][0]["tool"]["driver"]["rules"] == []

    def test_cli_writes_sarif_log(self, tmp_path):
        bad = tmp_path / "serve" / "bad.py"
        bad.parent.mkdir()
        bad.write_text(textwrap.dedent(DIRTY_LOCK))
        out_path = tmp_path / "out.sarif"
        code, _ = _run_cli(str(bad), "--no-baseline",
                           "--sarif", str(out_path))
        assert code == 1
        with open(out_path, encoding="utf-8") as fh:
            doc = json.load(fh)
        self._validate(doc)
        assert {r["ruleId"] for r in doc["runs"][0]["results"]} == {
            "lock-unguarded-write", "lock-blocking-call",
            "lock-order-cycle"}


# ---------------------------------------------------------------------------
# the repo gate + CLI
# ---------------------------------------------------------------------------
def test_repo_is_clean_and_fast():
    """cess_tpu/ has zero unsuppressed, unbaselined findings — and the
    full scan parses each file once, staying well inside ~10 s."""
    t0 = time.monotonic()
    r = analysis.lint_paths([os.path.join(REPO, "cess_tpu")], root=REPO)
    elapsed = time.monotonic() - t0
    assert r.errors == []
    new, _ = analysis.apply_baseline(r.findings,
                                     analysis.load_baseline(BASELINE))
    assert [f.format() for f in new] == []
    assert r.files > 50          # the scan actually covered the tree
    assert elapsed < 10.0, f"repo scan took {elapsed:.1f}s"


def _run_cli(*argv):
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "cesslint.py"),
         *argv],
        capture_output=True, text=True, cwd=REPO, timeout=120)
    return proc.returncode, proc.stdout


class TestCli:
    def test_clean_repo_exits_zero(self):
        code, out = _run_cli()
        assert code == 0, out
        assert "0 finding(s)" in out

    def test_dirty_file_exits_nonzero_with_json_and_hints(self, tmp_path):
        bad = tmp_path / "serve" / "bad.py"
        bad.parent.mkdir()
        bad.write_text(textwrap.dedent(DIRTY_LOCK))
        code, out = _run_cli(str(bad), "--json", "--no-baseline")
        assert code == 1
        data = json.loads(out)
        assert {f["rule"] for f in data["findings"]} == {
            "lock-unguarded-write", "lock-blocking-call",
            "lock-order-cycle"}
        # --fix-hints prints the per-rule suggested edit
        code, out = _run_cli(str(bad), "--fix-hints", "--no-baseline")
        assert code == 1 and "hint:" in out

    def test_rule_filter(self, tmp_path):
        bad = tmp_path / "serve" / "bad.py"
        bad.parent.mkdir()
        bad.write_text(textwrap.dedent(DIRTY_LOCK))
        code, out = _run_cli(str(bad), "--rule", "lock-blocking-call",
                             "--json", "--no-baseline")
        assert code == 1
        data = json.loads(out)
        assert {f["rule"] for f in data["findings"]} == {
            "lock-blocking-call"}
        code, _ = _run_cli("--rule", "no-such-rule")
        assert code == 2

    def test_unparseable_file_surfaces_as_error_not_silence(self, tmp_path):
        # the scan must report (not skip) a broken file: the CLI
        # returns 2 on errors and refuses --write-baseline from a
        # partial scan, so baselines can never silently shrink
        src_dir = tmp_path / "chain"
        src_dir.mkdir()
        (src_dir / "ok.py").write_text("import time\nT = time.time()\n")
        (src_dir / "broken.py").write_text("def oops(:\n")
        r = analysis.lint_paths([str(src_dir)], root=str(tmp_path))
        assert len(r.errors) == 1 and "broken.py" in r.errors[0]
        assert [f.rule for f in r.findings] == ["consensus-wallclock"]
        code, _ = _run_cli(str(src_dir), "--no-baseline")
        assert code == 2

    def test_write_baseline_refuses_narrowed_scan(self, tmp_path):
        # rewriting the baseline from a filtered run would silently
        # drop every entry outside the filter
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "cesslint.py"),
             "--write-baseline", "--rule", "consensus-float",
             "--baseline", str(tmp_path / "bl.json")],
            capture_output=True, text=True, cwd=REPO, timeout=120)
        assert proc.returncode == 2
        assert "full default scan" in proc.stderr
        assert not (tmp_path / "bl.json").exists()

    def test_list_rules_names_every_family(self):
        code, out = _run_cli("--list-rules")
        assert code == 0
        for rid in ("trace-host-sync", "dtype-overflow",
                    "lock-unguarded-write", "lock-order-cycle",
                    "consensus-unordered-iter", "consensus-wallclock",
                    "consensus-float", "span-balance",
                    "witness-purity", "race", "seam-cost"):
            assert rid in out
