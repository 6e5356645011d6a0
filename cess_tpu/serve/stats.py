"""Engine counters: queue depth, batch occupancy, pad waste, latency.

The serving layer is only tunable if its behavior is visible — the
reference threads a Prometheus registry through every subsystem
(node/src/service.rs:109-151), and the engine exports through the same
surface: ``node/metrics.py`` merges :meth:`EngineStats.metrics` into
the ``/metrics`` exposition when a node has an engine attached, and
the RPC debug endpoint ``cess_engineStats`` serves the raw snapshot.

Everything here is updated under the engine lock by design (the
batcher and submitters already hold it at every recording site), so
the counters need no locking of their own.
"""
from __future__ import annotations

import collections
import threading

from ..obs import flight as _flight
from ..obs import prom
from ..obs.trace import LONG_WAIT_S, LONG_WAITS_KEPT, STAGE_LADDER_S
from . import policy

LATENCY_WINDOW = 512     # per-class sliding window for percentiles

# The stages of one engine batch, in order (serve/engine.py times each
# through obs.trace.stage, once per batch):
#   queue     each member's enqueue -> the batch starts to run (lane
#             wait on the pool path included), summed over members
#   assemble  host-side coalescing: concatenate/pad, or the stacked
#             classes' zero-fill + copy loops
#   dispatch  the program call returns: the program and its implicit
#             host->device copies are ENQUEUED (verify_batch's un-jitted
#             vmap issues op by op here), the result slice with them
#   wait      jax.block_until_ready on the result: the device works,
#             the host waits
#   fetch     np.asarray of the result and the per-request slicing
#             (a byte result [rows, r, n]: the flatten into linear
#             rows, their np.asarray and the host reassembly; prove:
#             the release of the stacked host batch too)
#   resolve   accounting + resolving the members' futures
# These six are the batcher's side of a request and stay six (the
# readers of ``stages`` sum whatever it holds). The caller's side is
# counted per request under keys of its own, CALLER below, and the
# queue's seconds are split under QUEUE_PARTS. For a class whose batches
# hold one request, ``caller.submit`` + the six stages' seconds +
# ``caller.handoff`` sum to the blocking call's own extent (entry of
# ``engine.reconstruct`` / ``prove_aggregate`` / ... -> its return),
# within the clock reads between them: ``queue`` starts at the stamp
# ``_submit`` takes, a few microseconds before ``submit`` ends, and the
# hand-back starts inside ``resolve``.
STAGES = ("queue", "assemble", "dispatch", "wait", "fetch", "resolve")

# The caller's side of one request, on the thread that called the engine
# (serve/engine.py, through the same hook):
#   submit    entry of the public ``submit_*`` method -> the request is
#             in its queue and the method returns: normalising the
#             arguments (np.asarray, _norm_survivors, _check_round,
#             _round_digest), admission, the request's span, the
#             enqueue. Counted for requests that were queued.
#   handoff   how long the caller stayed blocked in ``result()`` after
#             its result existed: return of ``result()`` less the
#             later of its entry and the stamp ``_resolve`` /
#             ``_reject`` took. The wake-up of the caller's thread and
#             its wait for the GIL; 0 for a caller that came late.
#             Counted once a future.
CALLER = ("submit", "handoff")

# The two halves of ``queue``, summed over members like it:
#   coalesce  each member's enqueue -> the instant the drain trigger
#             tripped (DRAIN_TRIGGERS below): the wait policy asks for.
#             Under the default policy, which has no window, a member
#             of an ``idle`` drain waited 0: the trigger trips at the
#             oldest member's enqueue
#   wake      from there until the batch starts to run: the batcher
#             asleep, busy with another batch or waiting for the GIL
#             (lane wait on the pool path)
# ``coalesce + wake == queue``, exactly: the queue stage's total is
# kept as the sum of the two accumulators (ClassStats.add_stages).
QUEUE_PARTS = ("coalesce", "wake")

# What tripped a drain (serve/engine.py _tripped), counted per class and
# drain (``drains``; ``cess_engine_<cls>_drains_<trigger>_total``):
#   idle    no window (``AdmissionPolicy.max_delay`` None, the default):
#           an executor was free, so the class went at once; the instant
#           is the oldest member's enqueue. Batches behind a busy
#           executor count here too, when it comes free: their members
#           gathered while it ran (their wait is ``wake``)
#   window  a numeric ``max_delay``: the oldest member's enqueue + it
#   size    the enqueue of the request that filled the request budget
#           or the row budget
#   forced  the start of the flush or close that forces the drain
# The class drained is the highest-priority non-empty one, which need
# not be the class whose trigger tripped (_ready_class): the count goes
# to the class that was drained.
DRAIN_TRIGGERS = ("idle", "window", "size", "forced")

# A batch's wait for what its callers bring after their submit, a stage
# beside the six and outside all of them (``late`` in a snapshot):
#   proofs    a verify-round batch (serve/engine.py _op_verify_round):
#             from its last fold's enqueue until every request's (mu,
#             sigma) are in the batch's hands. The folds read nothing
#             of the proofs, so a TEE submits the round first and
#             decodes its wire proofs while the device folds; with the
#             proofs handed in at submit the stage is a few
#             microseconds. Counted once a verify-round batch
LATE = ("proofs",)

# Every account above keeps its DISTRIBUTION beside its sum: one
# obs/prom.Histogram over obs.trace.STAGE_LADDER_S (one tuple for the
# whole program) a (class, account), fed where the sums are, once a
# batch or a request — one batch's seconds in a stage (the queue's: its
# members' summed), one request's ``submit`` / ``handoff``. A bucket
# keeps [count, seconds], so the difference of two snapshots gives a
# window's percentile and its seconds above any bound
# (``stages[..]["buckets"]``, ``queue[..]``, ``caller[..]``; the
# 512-sample latency ring cannot be differenced).
LADDERS = STAGES + tuple("queue." + p for p in QUEUE_PARTS) + CALLER \
    + LATE

# The stages of a batch that are waits: an occurrence over
# obs.trace.LONG_WAIT_S is kept with its context (LongWaits).
LONG_WAIT_STAGES = ("wait", "fetch")


class LongWaits:
    """The worst cases beside the ladders: of each stage's occurrences
    that ran over ``LONG_WAIT_S`` the ``LONG_WAITS_KEPT`` longest, each
    ``{"stage", "start" (time.perf_counter()), "seconds", ...context}``,
    and every one of them a ``long_wait`` note in the flight journal
    (obs/flight.py; one global load when no recorder is armed). The
    context is built by the call site's ``context(*args)`` and only
    then: a wait under the threshold costs one comparison."""

    __slots__ = ("channel", "_kept", "_mu")

    def __init__(self, channel: str):
        self.channel = channel        # the journal's subsystem
        self._kept: dict[str, list] = {}
        self._mu = threading.Lock()

    def observe(self, stage: str, start: float, seconds: float,
                context, *args) -> None:
        if seconds > LONG_WAIT_S:
            rec = {"stage": stage, "start": start, "seconds": seconds,
                   **context(*args)}
            with self._mu:
                kept = self._kept.setdefault(stage, [])
                kept.append(rec)
                kept.sort(key=lambda r: -r["seconds"])
                del kept[LONG_WAITS_KEPT:]
            _flight.note(self.channel, "long_wait", **rec)

    def snapshot(self) -> list:
        """The kept records, oldest first."""
        with self._mu:
            kept = [dict(r) for recs in self._kept.values() for r in recs]
        return sorted(kept, key=lambda r: r["start"])


def _account(n: int, s: float, ladder: prom.Histogram) -> dict:
    """One account of a snapshot: the sum as it always read, and the
    ladder's non-empty buckets ``[[le, count, seconds], ...]``."""
    return {"n": n, "s": s, "buckets": ladder.buckets()}


class ClassStats:
    __slots__ = ("submitted", "completed", "failed", "timeouts",
                 "saturated", "shed", "batches", "batched_requests",
                 "rows", "padded_rows", "operand_bytes", "linear_fetches",
                 "linear_puts", "symbol_folds", "patterns_new",
                 "matrix_build_s", "result_bytes", "regroup_s",
                 "regrouped_bytes",
                 "missions", "device_calls", "prf_evals", "late_proofs",
                 "chunks", "gathered_bytes", "gather_seconds",
                 "latencies", "hist", "stage_n", "stage_s",
                 "caller_n", "caller_s", "queue_s", "late_n", "late_s",
                 "drains", "ladders")

    def __init__(self):
        self.submitted = 0          # requests admitted to the queue
        self.completed = 0          # futures resolved with a result
        self.failed = 0             # futures resolved with an op error
        self.timeouts = 0           # cancelled: deadline expired queued
        self.saturated = 0          # rejected at submit: queue full
        self.shed = 0               # rejected by SLO-gated admission
        self.batches = 0            # device batches launched
        self.batched_requests = 0   # requests across those batches
        self.rows = 0               # real rows across those batches
        self.padded_rows = 0        # pad rows added to reach buckets
        # bytes of the arrays handed to the device program, summed over
        # batches — counted by the stacked ops (prove, verify_agg), whose
        # batches are built on the host from what a round reads; the
        # concatenated classes leave it 0
        self.operand_bytes = 0
        # batches whose result left the device as linear pieces, one a
        # batch row: an all-host batch's byte result [rows, r, n]
        # (engine.py _fetch_linear); device submitters' and non-byte
        # results (tags, verdicts) leave it 0
        self.linear_fetches = 0
        # repair class: batches whose survivors went up as linear rows
        # put from the callers' own memory and stacked on the device
        # (engine.py _put_rows): every all-host batch on a device
        # codec; a batch with a device-resident contributor, and one
        # served by a host codec (the breaker's fallback), leave it 0
        self.linear_puts = 0
        # repair class: batches of the ``symbol`` kind, a helper's hop
        # of a regenerating repair (engine.py submit_repair_symbol);
        # the other kinds (reconstruct, decode) leave it 0
        self.symbol_folds = 0
        # repair class, device codec: batches whose erasure pattern the
        # codec held no matrix for (engine.py _counting_matrices), and
        # the host seconds their matrices took to build (GF
        # Gauss-Jordan + table expansion; the put of the operands is in
        # the ``cess:repair.matrix`` span around it, inside dispatch).
        # A program is NOT built for such a pattern: programs_built
        # counts programs, one per shape
        self.patterns_new = 0
        self.matrix_build_s = 0.0
        # what a byte result costs on its way back to host callers
        # (engine.py _split_rows -> _fetch_linear: an all-host batch of
        # encode or repair, fetched as one linear piece a batch row):
        # the bytes handed to the requests as host arrays; and the host
        # seconds and the bytes of the ``np.stack`` that regroups a
        # request's pieces into its own ``[rows, r, n]`` — only where
        # it runs, for a request of several batch rows: a request of
        # one row, however many rows ``r`` its result has, gets a view
        # of its piece and counts nothing there (the
        # ``cess:engine.<cls>.fetch.regroup`` span, inside ``fetch``).
        # ``regrouped_bytes`` / ``result_bytes`` is the share of the
        # results that paid a second host copy
        self.result_bytes = 0
        self.regroup_s = 0.0
        self.regrouped_bytes = 0
        # verify class, aggregated proofs (engine.py _op_verify_agg,
        # _op_verify_round): missions judged, device programs called
        # for them, and PRF evaluations those programs issue (rows on
        # the device x challenged blocks, pad rows included: what the
        # device is asked for; ``rows`` / ``padded_rows`` say how much
        # of it a mission owed)
        self.missions = 0
        self.device_calls = 0
        self.prf_evals = 0
        # verify class, a round (_op_verify_round): batches in which a
        # request's proofs arrived after the batch's folds were
        # enqueued (the decode ran under the folds); a batch whose
        # proofs were all in hand by then leaves it
        self.late_proofs = 0
        # prove class (engine.py _op_prove): device steps its batches
        # took (``device_calls`` counts them too: a step is a program
        # call), the bytes of challenged blocks and tag rows gathered
        # on the host for them, and the host seconds of those gathers
        # (the batches' ``assemble`` stage)
        self.chunks = 0
        self.gathered_bytes = 0
        self.gather_seconds = 0.0
        self.latencies = collections.deque(maxlen=LATENCY_WINDOW)
        # real Prometheus histogram of the same submit->resolve
        # latencies: unlike the sliding-window percentiles above this
        # is mergeable across nodes/scrapes, rendered as cumulative
        # _bucket{le=...}/_sum/_count lines by node/metrics.py
        self.hist = prom.Histogram(prom.LATENCY_BUCKETS_S)
        # per-stage batch counts and raw, unrounded seconds (STAGES):
        # merged from each batch's own sink when the batch is done
        self.stage_n = dict.fromkeys(STAGES, 0)
        self.stage_s = dict.fromkeys(STAGES, 0.0)
        # the caller's side, per request (CALLER), and the queue
        # seconds' two halves, per batch (QUEUE_PARTS)
        self.caller_n = dict.fromkeys(CALLER, 0)
        self.caller_s = dict.fromkeys(CALLER, 0.0)
        self.queue_s = dict.fromkeys(QUEUE_PARTS, 0.0)
        # a batch's waits for late operands (LATE)
        self.late_n = dict.fromkeys(LATE, 0)
        self.late_s = dict.fromkeys(LATE, 0.0)
        # drains of this class by what tripped them (DRAIN_TRIGGERS)
        self.drains = dict.fromkeys(DRAIN_TRIGGERS, 0)
        # each account's distribution (LADDERS)
        self.ladders = {name: prom.Histogram(STAGE_LADDER_S)
                        for name in LADDERS}

    def add_stages(self, sink: dict):
        """Merge one batch's stage sink (``{"engine.<cls>.<stage>":
        [count, seconds]}``, obs.trace.stage's shape; the queue's halves
        are ``engine.<cls>.queue.coalesce`` / ``.wake``, a late
        operand's wait ``engine.<cls>.proofs``): each entry into its
        sum and, as one observation, into its ladder. Returns
        the batch's waits (LONG_WAIT_STAGES) that ran over LONG_WAIT_S
        as ``[(stage, seconds), ...]``, or None: the caller notes them
        outside its lock."""
        long = None
        for name, (n, seconds) in sink.items():
            stage = name.split(".", 2)[2]
            if stage in self.stage_n:
                self.stage_n[stage] += n
                self.stage_s[stage] += seconds
            elif stage in self.late_n:
                self.late_n[stage] += n
                self.late_s[stage] += seconds
            else:
                self.queue_s[stage.rpartition(".")[2]] += seconds
            self.ladders[stage].observe(seconds)
            if seconds > LONG_WAIT_S and stage in LONG_WAIT_STAGES:
                long = (long or []) + [(stage, seconds)]
        # float additions do not associate: the total is the halves'
        self.stage_s["queue"] = self.queue_s["coalesce"] \
            + self.queue_s["wake"]
        return long

    def add_caller(self, account: str, seconds: float) -> None:
        """Count one request's ``submit`` or ``handoff`` (CALLER)."""
        self.caller_n[account] += 1
        self.caller_s[account] += seconds
        self.ladders[account].observe(seconds)

    # -- derived -----------------------------------------------------------
    @property
    def occupancy(self) -> float:
        """Mean requests coalesced per device batch."""
        return self.batched_requests / self.batches if self.batches else 0.0

    @property
    def pad_waste(self) -> float:
        """Fraction of device rows that were padding."""
        total = self.rows + self.padded_rows
        return self.padded_rows / total if total else 0.0

    def percentile(self, q: float) -> float:
        """q in [0, 1] over the sliding submit->resolve latency window."""
        if not self.latencies:
            return 0.0
        xs = sorted(self.latencies)
        return xs[min(len(xs) - 1, int(q * len(xs)))]


class StreamStats:
    """Per-stage counters for the double-buffered streaming driver
    (serve/stream.py). One instance per driver; attach to an engine
    (SubmissionEngine.attach_stream) to export through the same
    ``cess_engine_*`` exposition, prefixed ``cess_engine_stream_``.

    What each clock times, and what it can and cannot tell you:
    - ``stall_s`` is host time spent BLOCKED on device results (the
      in-flight throttle + final drain). A high stall fraction means
      the host runs ahead and the device's queue is full: the stream
      is bound by what the device works through per batch — program
      and transfers alike, which overlap there.
    - ``gate_s`` is host time spent BLOCKED on a transfer's arrival:
      a batch's put waits for the put two before it to have arrived
      (serve/stream.py ``_run``, PR 50), and that wait is no part of
      ``stall_s``. Beside it, it tells a stream the link paces (the
      gate takes most of the waiting) from one the program paces (the
      stall does).
    - ``h2d_s`` and ``dispatch_s`` time an ENQUEUE, never the work:
      ``device_put`` and the program call return once the transfer or
      the program is queued (128 MiB "staged" in 0.65 ms on a v5e,
      PERF.md). Where ``h2d_s`` is a large part of ``wall_s`` the
      stream is therefore NOT transfer-bound; the host-side call
      itself is slow (a synchronous backend such as the CPU's, a
      non-contiguous source being copied, a full transfer queue). The
      last is what a four-lane pool shows: the sharded put of 512 MiB
      over four v5e chips takes 29 ms of the host thread a batch
      (PERF.md); its three calls are ``cess:stream.put.slice`` /
      ``.place`` / ``.assemble`` in a trace (parallel/mesh.py).
    - ``stage_s`` is the host's staging of a batch (the source's next
      chunk, made contiguous, padded, its ids) and ``consumer_s`` the
      time the driver stood suspended at a ``yield`` while its consumer
      held a finished batch: with them a run's seconds add up,
      ``wall_s = stage_s + gate_s + h2d_s + stall_s + dispatch_s +
      consumer_s +`` a remainder (the loop's own bookkeeping), which is
      a number and no longer a guess.
    - ``stages`` keeps each of the five stages' DISTRIBUTION on the
      program's one ladder (``{"n", "s", "buckets"}`` a stage, the
      engine classes' shape), merged once a batch from the sink its
      five ``obs.trace.stage`` blocks write: a window that hung reads
      as seconds in the buckets above ``LONG_WAIT_S`` where a sound one
      reads 0.0, and ``long_waits`` holds those waits themselves with
      what was in flight (LongWaits). ``hist`` (the Prometheus family
      ``cess_engine_stream_batch_seconds``) observes what a batch cost
      the host thread: its gate, its put, the stall that let its
      program into the window, and its dispatch.
    How long the bytes take on the link is in no counter and no host
    span; in a device trace the extents above are the
    ``cess:stream.stage`` / ``.gate`` / ``.put`` / ``.dispatch`` /
    ``.stall`` events, a batch's five under one ``seq``, beside the
    device's own line: a program enqueued (its dispatch ended) and not
    yet started is the device waiting for its operands.
    """

    STAGES = ("stream.stage", "stream.gate", "stream.put",
              "stream.dispatch", "stream.stall")
    _COUNTERS = ("batches", "segments", "padded_segments", "bytes_in",
                 "bytes_out", "linear_puts", "put_arrays", "direct_rows",
                 "h2d_s", "dispatch_s", "stall_s", "gate_s", "wall_s",
                 "stage_s", "consumer_s")
    __slots__ = _COUNTERS + ("lanes", "hist", "ladders", "long_waits")

    def __init__(self):
        self.batches = 0           # device batches dispatched
        self.segments = 0          # real segments ingested
        self.padded_segments = 0   # zero rows added to the ragged tail
        self.bytes_in = 0          # host bytes staged (real, not pad)
        # fragment bytes + tag bytes of every finished batch (real rows,
        # not pad): over ``bytes_in`` and less the tags, the code's
        # stored bytes per user byte as a count ((k + m) / k exactly)
        self.bytes_out = 0
        # batches whose bytes went up as linear 1-D rows (views of the
        # staged chunk, stacked on the device: PERF.md, PR 43), and the
        # host arrays handed to the put for them
        self.linear_puts = 0
        self.put_arrays = 0
        # batches whose program handed the put's rows to the RS kernel
        # unstacked (the program's own ``direct_rows``, models/
        # pipeline.py: PERF.md, PR 51); a program that does not say
        # counts none
        self.direct_rows = 0
        self.h2d_s = 0.0           # host time ENQUEUEING device_put
        self.dispatch_s = 0.0      # host time ENQUEUEING the program
        self.stall_s = 0.0         # host time blocked on device results
        self.gate_s = 0.0          # host time blocked on a put's arrival
        self.wall_s = 0.0          # wall time of completed run() calls
        self.stage_s = 0.0         # host time staging the next batch
        self.consumer_s = 0.0      # suspended at a yield: the consumer's
        # a GAUGE, not a counter: devices the last staged batch was
        # placed over (1 on one device; a DevicePool's lane count, or a
        # mesh's size, once a sharded put has staged a batch)
        self.lanes = 1
        # what a batch cost the host thread (gate + put + the stall
        # ahead of its program + dispatch), as a Prometheus family —
        # the mergeable form beside the aggregate stage clocks above
        self.hist = prom.Histogram(prom.LATENCY_BUCKETS_S)
        # the five stages' distributions, and the waits that hung
        self.ladders = {name: prom.Histogram(STAGE_LADDER_S)
                        for name in self.STAGES}
        self.long_waits = LongWaits("stream")

    def add_stages(self, sink: dict) -> None:
        """Merge one turn's stage sink (obs.trace.stage's shape) and
        empty it: each entry one observation of its stage's ladder."""
        for name, (_, seconds) in sink.items():
            self.ladders[name].observe(seconds)
        sink.clear()

    def scalars(self) -> dict:
        """The counters and the gauge: what adds up across drivers."""
        out = {name: getattr(self, name) for name in self._COUNTERS}
        out["lanes"] = self.lanes
        return out

    def raw(self) -> dict:
        out = self.scalars()
        out["stages"] = {name: _account(h.count, h.sum, h)
                         for name, h in self.ladders.items()}
        out["long_waits"] = self.long_waits.snapshot()
        return out

    def snapshot(self) -> dict:
        return stream_gauges(self.raw())

    def metrics(self) -> dict[str, float]:
        return {f"cess_engine_stream_{k}": float(v)
                for k, v in stream_gauges(self.scalars()).items()}


def stream_gauges(raw: dict) -> dict:
    """Derived per-stage gauges from raw StreamStats counters (shared
    by a single driver's snapshot and the engine's cross-stream sum)."""
    out = dict(raw)
    wall = raw["wall_s"]
    out["stall_frac"] = round(raw["stall_s"] / wall, 4) if wall else 0.0
    for k in ("h2d_s", "dispatch_s", "stall_s", "gate_s", "wall_s",
              "stage_s", "consumer_s"):
        out[k] = round(out[k], 6)
    return out


class EngineStats:
    """One ClassStats per op class + engine-wide program-cache counts
    (+ any attached streaming drivers' stage counters)."""

    def __init__(self):
        self.classes = {c: ClassStats() for c in policy.CLASSES}
        self.programs_built = 0     # program-cache misses (compiles)
        self.programs_reused = 0    # program-cache hits
        self.streams: list[StreamStats] = []   # attached stream drivers
        # the batches' waits that ran over LONG_WAIT_S, with the batch
        # they were of (engine.py _close_stages)
        self.long_waits = LongWaits("engine")
        # ResilienceStats (cess_tpu/resilience/stats.py) when the
        # engine is resilience-configured — duck-typed (snapshot()/
        # metrics()) so this module never imports the package
        self.resilience = None
        # SloBoard (obs/slo.py) / AdaptiveBatchPolicy (serve/
        # adaptive.py) when configured — same duck-typed contract;
        # the board's LABELED families render via the engine's
        # labeled_series()/labeled_histograms(), not these flat dicts
        self.slo = None
        self.adaptive = None
        # DevicePool (serve/pool.py) when the engine serves the
        # multi-chip plane — duck-typed (snapshot()/metrics()) like
        # the attachments above; exports the cess_engine_device_*
        # per-lane family
        self.pool = None
        # ProfilePlane (obs/profile.py) when the engine is profiled —
        # same duck-typed contract; exports the cess_profile_* family
        self.profile = None

    def snapshot(self, queue_depths: dict[str, int] | None = None) -> dict:
        """JSON-shaped dump for the RPC debug endpoint."""
        depths = queue_depths or {}
        out: dict = {"programs_built": self.programs_built,
                     "programs_reused": self.programs_reused,
                     "classes": {}}
        for cls, st in self.classes.items():
            out["classes"][cls] = {
                "queue_depth": depths.get(cls, 0),
                "submitted": st.submitted,
                "completed": st.completed,
                "failed": st.failed,
                "timeouts": st.timeouts,
                "saturated": st.saturated,
                "shed": st.shed,
                "batches": st.batches,
                "batched_requests": st.batched_requests,
                "batch_occupancy": round(st.occupancy, 4),
                "pad_waste": round(st.pad_waste, 4),
                "rows": st.rows,
                "padded_rows": st.padded_rows,
                "operand_bytes": st.operand_bytes,
                "linear_fetches": st.linear_fetches,
                "linear_puts": st.linear_puts,
                "symbol_folds": st.symbol_folds,
                "patterns_new": st.patterns_new,
                "matrix_build_s": st.matrix_build_s,
                "result_bytes": st.result_bytes,
                "regroup_s": st.regroup_s,
                "regrouped_bytes": st.regrouped_bytes,
                "missions": st.missions,
                "device_calls": st.device_calls,
                "prf_evals": st.prf_evals,
                "late_proofs": st.late_proofs,
                "chunks": st.chunks,
                "gathered_bytes": st.gathered_bytes,
                "gather_seconds": st.gather_seconds,
                "latency_p50": round(st.percentile(0.50), 6),
                "latency_p99": round(st.percentile(0.99), 6),
                "stages": {stage: _account(st.stage_n[stage],
                                           st.stage_s[stage],
                                           st.ladders[stage])
                           for stage in STAGES},
                "caller": {acct: _account(st.caller_n[acct],
                                          st.caller_s[acct],
                                          st.ladders[acct])
                           for acct in CALLER},
                "queue": {part: _account(st.stage_n["queue"],
                                         st.queue_s[part],
                                         st.ladders["queue." + part])
                          for part in QUEUE_PARTS},
                "late": {part: _account(st.late_n[part], st.late_s[part],
                                        st.ladders[part])
                         for part in LATE},
                "drains": dict(st.drains),
            }
        out["long_waits"] = self.long_waits.snapshot()
        if self.streams:
            out["streams"] = [s.snapshot() for s in self.streams]
        if self.resilience is not None:
            out["resilience"] = self.resilience.snapshot()
        if self.slo is not None:
            out["slo"] = self.slo.snapshot()
        if self.adaptive is not None:
            out["adaptive"] = self.adaptive.snapshot()
        if self.pool is not None:
            out["devices"] = self.pool.snapshot()
        if self.profile is not None:
            out["profile"] = self.profile.snapshot()
        return out

    def metrics(self, queue_depths: dict[str, int] | None = None
                ) -> dict[str, float]:
        """Flat Prometheus-style gauges (merged by node/metrics.py)."""
        snap = self.snapshot(queue_depths)
        out = {"cess_engine_programs_built": snap["programs_built"],
               "cess_engine_programs_reused": snap["programs_reused"]}
        for cls, st in snap["classes"].items():
            for stage, acc in st.pop("stages").items():
                out[f"cess_engine_{cls}_stage_{stage}_seconds"] = acc["s"]
                out[f"cess_engine_{cls}_stage_{stage}_count"] = acc["n"]
            for acct, acc in st.pop("caller").items():
                out[f"cess_engine_{cls}_caller_{acct}_seconds"] = acc["s"]
                out[f"cess_engine_{cls}_caller_{acct}_count"] = acc["n"]
            for part, acc in st.pop("queue").items():
                out[f"cess_engine_{cls}_queue_{part}_seconds"] = acc["s"]
            for part, acc in st.pop("late").items():
                out[f"cess_engine_{cls}_stage_{part}_seconds"] = acc["s"]
                out[f"cess_engine_{cls}_stage_{part}_count"] = acc["n"]
            for trigger, n in st.pop("drains").items():
                out[f"cess_engine_{cls}_drains_{trigger}_total"] = n
            for name, val in st.items():
                out[f"cess_engine_{cls}_{name}"] = val
        if self.streams:
            # sum RAW counters across attached drivers, then derive —
            # adding per-driver fractions would be meaningless
            totals = self.streams[0].scalars()
            for s in self.streams[1:]:
                for k in StreamStats._COUNTERS:
                    totals[k] += getattr(s, k)
                # the gauge does not add up: the widest placement
                totals["lanes"] = max(totals["lanes"], s.lanes)
            for name, val in stream_gauges(totals).items():
                out[f"cess_engine_stream_{name}"] = float(val)
        if self.resilience is not None:
            # cess_resilience_* rides the same exposition (ISSUE 4:
            # retry/abandon/breaker gauges beside the engine family)
            out.update(self.resilience.metrics())
        if self.adaptive is not None:
            # cess_adaptive_* per-class knob/estimate gauges (ISSUE 6)
            out.update(self.adaptive.metrics())
        if self.pool is not None:
            # cess_engine_device_* per-lane placement/load/breaker
            # gauges (the multi-chip serving plane, serve/pool.py)
            out.update(self.pool.metrics())
        if self.profile is not None:
            # cess_profile_* continuous-profiling gauges (ISSUE 13)
            out.update(self.profile.metrics())
        return out

    def histograms(self) -> dict[str, prom.Histogram]:
        """Histogram families for the text exposition: one
        submit->resolve latency family per op class, plus the summed
        family of what a streamed batch cost its host thread when
        drivers are attached (per-driver histograms share bounds, so
        the merge is exact — node/metrics.py renders these with
        ``# TYPE ... histogram``). The stage ladders are not families:
        130 series a (class, stage) is read from the snapshot."""
        out = {f"cess_engine_{cls}_latency_seconds": st.hist
               for cls, st in self.classes.items()}
        if self.streams:
            merged = prom.Histogram(prom.LATENCY_BUCKETS_S)
            for s in self.streams:
                merged.merge(s.hist)
            out["cess_engine_stream_batch_seconds"] = merged
        return out
