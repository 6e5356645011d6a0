"""The device submission engine: dynamic micro-batching for the
RS/PoDR2 hot paths.

Every off-chain actor in the reference ecosystem hits the device
through its own tiny synchronous call — OSS gateways encode uploads,
miners prove challenges, TEEs tag and verify — leaving the accelerator
idle between calls and recompiling on every new shape. This engine is
the serving layer between all of them and the ``ErasureCodec`` /
``AuditBackend`` gates (ops/rs.py, ops/audit_backend.py):

- callers ``submit_*`` and get a future back; per-op-class bounded
  queues hold the requests (policy.py: explicit backpressure, class
  priority, deadlines);
- one batcher thread drains a class the moment something can run its
  batch — its own thread between batches, a free lane on the pool
  path — or a size budget fills (``_tripped``): an idle engine never
  makes a lone caller wait, and requests coalesce by arriving while
  the executors are busy (a numeric ``AdmissionPolicy.max_delay``
  holds the oldest request that long for companions instead). It
  coalesces coalescible requests (same op, geometry and round
  parameters) into a single device batch, pads the batch to a shape
  bucket (buckets.py: compile-once program cache), launches it, and
  slices results back per request;
- everything observable lands in stats.py (queue depth, batch
  occupancy, pad waste, per-class latency percentiles, the six stages
  of a batch and, per request, the caller's side: its ``submit`` and
  the hand-back of its result), exported via node/metrics.py and the
  ``cess_engineStats`` RPC.

Zero-copy handoff: submits accept ``jax.Array`` payloads and keep
them ON DEVICE — coalescing concatenates resident inputs with
``jnp.concatenate``, padding pads with device zeros, and each
request's result slice comes back as a ``jax.Array``. Host (numpy)
submitters keep getting numpy back, even when a batch mixes both. A
repair batch of host submitters alone crosses the link in the shape
the link is fast in, both ways: the survivors go up as linear ``u8[n]``
rows put from the callers' own memory and are stacked on the device
inside the program (``_put_rows``, PR 32), the result comes down as one
linear piece a batch row (``_fetch_linear``, PR 28, 53); a request of
one segment is copied on the host neither way. So
``StoragePipeline -> engine -> device`` is one H2D copy total for the
concat-coalesced classes (encode / repair / tag / verify), provided
the payloads live on the backend's device. The stacked classes
(prove / verify_agg) assemble their [R, F, ...] mission batches
host-side and run ONE compiled program per batch shape, reused across
rounds: the round (idx, nu) and the PoDR2 key are operands, never
constants. Their callers are host agents: a miner's fragments stay in
host memory — one array, or one buffer a fragment as its store holds
them (``podr2.HeldRows``: a deal's share is 1,000 fragments, 7.8 GiB,
and is never copied) — and ``assemble`` gathers the challenged blocks
there (4.6% of the set at protocol widths), so only what the round
reads travels to the device. A prove ships it in pieces of one shape
(PR 38): ``podr2.PROVE_CHUNK`` fragments a device step ([R, 64, c,
sectors] uint16 blocks, their tag rows and r: 24 MiB at the protocol's
geometry), gathered into two reused host buffers and folded into a
running (mu, sigma) on the device while the next step's gather runs;
up to a chunk that is the one step of the bucket's program, past it
the chunk is the only shape, so the programs do not grow with F, and
(mu, sigma) come back once. Verify ships KiB-scale proofs. A TEE's whole
round (verify_round, PR 33: up to 500 missions, owed sets ragged from
tens to ten thousands of fragments) is not stacked but FLAT: what
ships is a row a owed fragment (its 8-byte id and its mission's
index), the round (idx, nu) and its two aggregation key words — r is
derived on the device — and one program a mission bucket (8, 64, 512)
folds the rows ops/podr2.py ROUND_ROWS at a time into [missions,
limbs]; the proofs [missions, sectors + limbs] are taken LATE, for the
close alone, so their decode runs under the folds (PR 56). Programs
do not grow with the spread of the sizes, pad is the last loop step's,
requests of one round coalesce row-wise (``submit_verify_round``).

Protocol determinism is the hard constraint: engine-mediated results
are bit-identical to the direct calls. That falls out of two facts —
every coalesced op is row-independent (vmap / per-row GF matrix
apply), and padding adds zero rows (or zero aggregation coefficients,
whose terms are exact modular zeros) that are sliced off afterward.
tests/test_serve.py pins both.

The direct synchronous path remains the default everywhere (the
trait-gate philosophy): an engine is used only where one is explicitly
configured (StoragePipeline(engine=...), MinerAgent(engine=...),
TeeAgent(engine=...), ``node.cli --engine``).

Resilience (opt-in, cess_tpu/resilience): constructed with a
``ResilienceConfig`` the engine additionally
- retries saturated blocking submits with deterministic backoff inside
  the request's ONE deadline budget (retry.py);
- isolates batch failures — a device error against a coalesced batch
  re-runs the members individually once, so a poisoned request cannot
  fail its batch-mates (``cess_resilience_batch_requeues``);
- health-gates each backend: a breaker tripped by the error window
  transparently serves batches on the CPU reference codec/audit
  backend (bit-identical results by construction) and probes its way
  back (health.py);
- exposes it all as ``cess_resilience_*`` gauges beside the
  ``cess_engine_*`` family.
The ``engine.dispatch`` fault site (resilience/faults.py) sits on
every non-degraded device attempt, so seeded chaos plans can drive
all of the above deterministically in tier-1.

SLO + adaptive control (opt-in, ISSUE 6): built with an
``obs.SloBoard`` (``slo=``) every resolved/failed/expired request
feeds the board's burn-rate windows and per-tenant accounting (every
submit takes an optional ``tenant=`` tag, threaded down from the
gateway/miner/TEE agents), and the batcher's drain anchor becomes
WEIGHTED-FAIR across tenants (deficit on served device rows) so one
heavy uploader cannot starve another tenant's traffic inside a class.
With an ``AdaptiveBatchPolicy`` (``adaptive=``) the batching knobs
(max_delay / request / row budgets) are read PER CLASS from the live
latency signal instead of the static policy constants, and with an
``AdmissionController`` (``admission=``; auto-built by
:func:`make_engine` when both are present) sheddable submits are
SLO-gated (``EngineShed``) and a burning protected class latches the
codec breaker open (``HealthMonitor.hold_open``) so bulk load
degrades to the CPU reference while the device serves the protected
class. All three attributes default to None and every hook on the
disabled path is one attribute load + a None check — no SLO or
tenant object is allocated (the NOOP_SPAN contract,
tests/test_slo.py pins it).
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import hashlib
import threading
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import flight as _flight
from ..obs import trace
from ..resilience import faults
from ..resilience.retry import Budget
from .buckets import ProgramCache, bucket_rows
from .policy import (CLASSES, AdmissionPolicy, EngineClosed,
                     EngineSaturated, EngineShed, EngineTimeout)
from .stats import STAGES, EngineStats


class EngineFuture:
    """Result handle for a submitted request (threading-based: the
    engine serves plain synchronous agents, not an event loop).

    A future of an engine also times the caller's side of the
    hand-back: ``result()`` runs under the stage
    ``engine.<cls>.result`` (the caller's whole blocked extent, on the
    caller's thread: a ``cess:`` event in a profiler trace, a child of
    the caller's span when it has one), and counts once, under the
    class's ``caller.handoff``, how much of it came after ``_resolve``
    / ``_reject`` stamped the result (stats.py CALLER). A bare
    ``EngineFuture()`` counts nothing."""

    __slots__ = ("_event", "_value", "_exc", "_engine", "_cls",
                 "_resolved_t")

    def __init__(self, engine=None, cls: str = ""):
        self._event = threading.Event()
        self._value: Any = None
        self._exc: BaseException | None = None
        # who counts the hand-back; None once it is counted
        self._engine = engine
        self._cls = cls
        self._resolved_t: float | None = None   # time.perf_counter()

    def done(self) -> bool:
        return self._event.is_set()

    def result(self, timeout: float | None = None):
        """Block until resolved. Raises the request's failure
        (EngineTimeout on deadline cancellation, the op's error on a
        batch failure) or EngineTimeout if ``timeout`` elapses first."""
        engine = self._engine
        if engine is None:
            return self._wait(timeout)
        try:
            with trace.stage(f"engine.{self._cls}.result",
                             parent=trace.current_span()) as stage:
                return self._wait(timeout)
        finally:
            done_t = self._resolved_t
            if done_t is not None:
                # resolved or rejected: the one hand-back this future
                # has. A caller that came after it waited for nothing.
                self._engine = None
                engine._count_caller(
                    self._cls, "handoff",
                    0.0 if done_t <= stage.t0
                    else max(stage.t0 + stage.seconds - done_t, 0.0))

    def _wait(self, timeout: float | None):
        if not self._event.wait(timeout):
            raise EngineTimeout(f"no result within {timeout}s")
        if self._exc is not None:
            raise self._exc
        return self._value

    # engine-internal
    def _resolve(self, value) -> None:
        self._value = value
        self._resolved_t = time.perf_counter()
        self._event.set()

    def _reject(self, exc: BaseException) -> None:
        self._exc = exc
        self._resolved_t = time.perf_counter()
        self._event.set()


@dataclasses.dataclass
class _Request:
    cls: str                 # op class (policy.CLASSES)
    key: tuple               # coalescing key: op + geometry + round aux
    rows: int                # device rows this request contributes
    arrays: dict             # normalized numpy payloads
    aux: dict                # shared parameters (idx/nu/present/...)
    enqueue_t: float
    deadline: float | None
    future: EngineFuture
    # the instant the drain trigger of its batch tripped (_tripped),
    # stamped at the drain: where the queue stage's ``coalesce`` half
    # ends and its ``wake`` half begins (stats.py QUEUE_PARTS)
    trip_t: float = 0.0
    squeeze: bool = False    # 2-D submit: drop the batch axis on return
    device: bool = False     # jax.Array payload: result stays on device
    # request-scoped trace span (cess_tpu/obs): covers queue-wait ->
    # batch membership -> device dispatch -> resolve; the NOOP
    # singleton when no tracer is armed (every touch is then a no-op)
    span: Any = trace.NOOP_SPAN
    # per-tenant accounting tag (obs/slo.py): None when untagged or
    # when no SLO board is configured — a bare field default, nothing
    # allocated on the disabled path
    tenant: str | None = None


def _round_digest(num_blocks: int, idx, nu) -> bytes:
    """Coalescing identity of a challenge round's derived parameters."""
    h = hashlib.sha256(num_blocks.to_bytes(8, "little"))
    h.update(np.asarray(idx).tobytes())
    h.update(np.asarray(nu).tobytes())
    return h.digest()[:16]


def _norm(arr, dtype):
    """Normalize a payload WITHOUT forcing it off its device: jax
    arrays stay jax (dtype-cast on device when needed), everything
    else becomes a contiguous numpy array."""
    if isinstance(arr, jax.Array):
        return arr if arr.dtype == dtype else arr.astype(dtype)
    return np.ascontiguousarray(np.asarray(arr, dtype=dtype))


def _concat_rows(arrs: list):
    """Coalesce request payloads along axis 0 — ON DEVICE when any
    contributor is device-resident (one H2D per host contributor,
    zero for resident ones), plain numpy otherwise."""
    if any(isinstance(a, jax.Array) for a in arrs):
        if len(arrs) == 1:
            return arrs[0]
        return jnp.concatenate([jnp.asarray(a) for a in arrs], axis=0)
    return np.concatenate(arrs, axis=0)


def _pad_axis0(arr, rows: int):
    if arr.shape[0] == rows:
        return arr
    if isinstance(arr, jax.Array):
        pad = jnp.zeros((rows - arr.shape[0],) + arr.shape[1:], arr.dtype)
        return jnp.concatenate([arr, pad], axis=0)
    pad = np.zeros((rows - arr.shape[0],) + arr.shape[1:], arr.dtype)
    return np.concatenate([arr, pad], axis=0)


class _HostRows(tuple):
    """One repair request handed in as its ``q`` linear ``u8[n]`` rows,
    still wherever the caller holds them (a miner's fetched fragments):
    what ``[q, n]`` would have cost a host copy to build. It answers
    ``shape`` / ``nbytes`` as that ``[1, q, n]`` array would."""

    @property
    def shape(self) -> tuple:
        return (1, len(self)) + self[0].shape

    @property
    def nbytes(self) -> int:
        return sum(row.nbytes for row in self)


def _row_views(surv) -> list:
    """A host request's survivors (``[B, q, n]`` or ``_HostRows``) as
    ``B * q`` contiguous 1-D views: no byte is copied."""
    if isinstance(surv, _HostRows):
        return list(surv)
    return [surv[i, j] for i in range(surv.shape[0])
            for j in range(surv.shape[1])]


def _check_round(idx, nu, num_blocks: int) -> tuple:
    """A challenge round's (idx, nu) as host arrays. The indices come
    from outside and gather host memory here and device memory in the
    verifier, where an out-of-range read raises nothing: refuse them
    at submit."""
    idx = np.asarray(idx)
    nu = np.ascontiguousarray(nu, dtype=np.uint32)
    if idx.ndim != 1 or nu.shape != idx.shape \
            or not np.issubdtype(idx.dtype, np.integer):
        raise ValueError("expected idx [c] of integers and nu [c]")
    if idx.size and not 0 <= idx.min() <= idx.max() < num_blocks:
        raise ValueError(f"challenged block outside [0, {num_blocks})")
    return np.ascontiguousarray(idx, dtype=np.int32), nu


def _prove_missions(blocks_i, tags_i, r, nu):
    """The prove class's device program, one row per miner: challenged
    blocks [R, F, c, sectors], their tags [R, F, c, limbs], fold
    coefficients r [R, F] (0 on pad rows: exact modular zeros) and the
    round's nu [c] -> (mu [R, sectors], sigma [R, limbs])."""
    from ..ops import podr2

    return jax.vmap(podr2.prove_aggregate_at,
                    in_axes=(0, 0, None, 0))(blocks_i, tags_i, nu, r)


def _prove_missions_step(mu, sigma, blocks_i, tags_i, r, nu):
    """A later step of a chunked prove, one row per miner: the running
    (mu [R, sectors], sigma [R, limbs]) plus the fold of one more chunk
    of every miner's fragments (``podr2.prove_step_at``)."""
    from ..ops import podr2

    return jax.vmap(podr2.prove_step_at, in_axes=(0, 0, 0, 0, None, 0))(
        mu, sigma, blocks_i, tags_i, nu, r)


def _verify_missions(ids, r, mu, sigma, idx, nu, alpha, prf_key_data, *,
                     num_blocks: int, prf_impl: str):
    """The verify_agg class's device program, one row per mission: ids
    [R, F, 2], r [R, F] (0 on pad rows), proofs mu [R, sectors] and
    sigma [R, limbs], the round (idx, nu) and the PoDR2 key (alpha and
    the PRF key's raw words) -> bool [R]. The key is an operand, so
    one executable serves every key."""
    from ..ops import podr2

    key = podr2.Podr2Key(alpha, jax.random.wrap_key_data(prf_key_data,
                                                         impl=prf_impl))
    return jax.vmap(lambda i, rr, u, s: podr2.verify_aggregate(
        key, i, num_blocks, idx, nu, rr, u, s))(ids, r, mu, sigma)


# jitted once for the process: a round changes operands, never the
# program, so a shape compiles once however many rounds and engines
# run it (the engine's ProgramCache counts the shapes it has met)
_PROVE_PROGRAM = jax.jit(_prove_missions)
_PROVE_STEP = jax.jit(_prove_missions_step)
_VERIFY_PROGRAM = jax.jit(_verify_missions,
                          static_argnames=("num_blocks", "prf_impl"))


@jax.jit
def _linear_rows(out):
    """A byte result ``[rows, r, n]`` as ``rows`` dense 1-D pieces of
    ``r * n`` bytes, the shape in which bytes leave the device: the TPU
    packs four rows of the second-minor dimension into a 32-bit word, so
    ``u8[1, 1, n]`` is 4 n bytes with the fragment strided through it and
    takes 41 ms to fetch at 8 MiB where a dense ``u8[n]`` takes 1.7
    (PERF.md, PR 28). Index forms, and a 1-D concatenate of the rows they
    give: a ``reshape`` that moves bytes between dimensions compiles in
    time proportional to the array (models/pipeline.py split_rows)."""
    if out.shape[1] == 1:
        return tuple(out[i, 0] for i in range(out.shape[0]))
    return tuple(jnp.concatenate([out[i, j] for j in range(out.shape[1])])
                 for i in range(out.shape[0]))


def _caller_submit(cls: str):
    """Decorator of a public ``submit_*`` method of class ``cls``: the
    call runs under the stage ``engine.<cls>.submit`` on the caller's
    thread (entry -> the request is queued and the method returns), and
    a request that was queued counts it under the class's
    ``caller.submit`` (stats.py CALLER). The stage is a child of the
    caller's current span and makes no span where there is none: a root
    of its own would read as a trace of its own. The request's span
    stays a child of the caller's span, not of this stage
    (``_calling``)."""
    name = f"engine.{cls}.submit"

    def wrap(method):
        @functools.wraps(method)
        def submit(self, *args, **kwargs):
            outer = trace.current_span()
            self._calling.span = None if outer is trace.NOOP_SPAN else outer
            try:
                with trace.stage(name, parent=outer) as stage:
                    fut = method(self, *args, **kwargs)
            finally:
                self._calling.span = None     # keep no span alive
            self._count_caller(cls, "submit", stage.seconds)
            return fut
        return submit
    return wrap


class SubmissionEngine:
    """See module docstring. Construct via :func:`make_engine` or pass
    an ``ErasureCodec`` (ops/rs.py gate) and optionally an
    ``AuditBackend`` (ops/audit_backend.py gate) directly."""

    # op class -> which backend's health breaker gates it
    _BACKEND_OF = {"encode": "codec", "repair": "codec", "decode": "codec",
                   "tag": "audit", "verify_batch": "audit",
                   "verify_agg": "audit", "verify_round": "audit",
                   "prove": "audit"}

    def __init__(self, codec=None, audit=None,
                 policy: AdmissionPolicy | None = None,
                 resilience=None, tracer=None, slo=None, adaptive=None,
                 admission=None, pool=None, profile=None):
        if codec is None and audit is None:
            raise ValueError("engine needs a codec and/or audit backend")
        self.codec = codec
        self.audit = audit
        # request-scoped tracing (cess_tpu/obs): an explicitly passed
        # Tracer pins this engine to it; otherwise the process-armed
        # tracer (obs.trace.arm) is consulted per request. None + not
        # armed = every hook is the no-op singleton.
        self.tracer = tracer
        self.policy = policy or AdmissionPolicy()
        self.stats = EngineStats()
        self.programs = ProgramCache(self.stats)
        # SLO + adaptive control (ISSUE 6, see module doc). All three
        # default None: the disabled submit/batch paths are one
        # attribute load + None check each, allocating nothing.
        self.slo = slo                    # obs.SloBoard
        self.adaptive = adaptive          # serve.adaptive.AdaptiveBatchPolicy
        self.admission = admission        # serve.adaptive.AdmissionController
        self.stats.slo = slo
        self.stats.adaptive = adaptive
        # continuous profiling (obs/profile.py, ISSUE 13, opt-in): a
        # ProfilePlane accounts every dispatch's stage breakdown and
        # pad bill and (baseline-anchored) watches for throughput
        # regressions. None = one attribute load + None check on the
        # account path; the program cache times builds into it.
        self.profile = profile
        self.stats.profile = profile
        if profile is not None:
            self.programs.profile = profile
        # per-(class, tenant) served device rows: the weighted-fair
        # drain's deficit counters (engine-lock guarded, only ever
        # populated when a board is configured)
        self._tenant_rows: dict[str, dict[str, int]] = {}
        # resilience (cess_tpu/resilience, opt-in): CPU reference
        # fallbacks compute bit-identical bytes, so a tripped breaker
        # changes WHERE a batch runs, never what it returns
        self.resilience = resilience
        self.monitors: dict[str, Any] = {}
        self._fallback_codec = None
        self._fallback_audit = None
        if resilience is not None:
            self.stats.resilience = resilience.stats
            if codec is not None:
                if hasattr(codec, "fold_symbol"):
                    # regenerating codec: degrade onto ITS reference
                    # twin so the symbol surface survives a breaker
                    # trip (same bytes, host placement)
                    from ..ops.regen import RegenReference

                    self._fallback_codec = RegenReference(codec.k,
                                                          codec.m)
                else:
                    from ..ops import rs as _rs

                    self._fallback_codec = _rs.make_codec(
                        codec.k, codec.m, backend="cpu")
                self.monitors["codec"] = resilience.monitor()
            if audit is not None:
                from ..ops import audit_backend as _ab

                self._fallback_audit = _ab.make_audit_backend(audit.key,
                                                              "cpu")
                self.monitors["audit"] = resilience.monitor()
            for name, mon in self.monitors.items():
                mon.name = name   # black-box journal identity
                resilience.stats.register_monitor(name, mon)
        if admission is not None:
            # after the monitors exist: the controller latches the
            # codec breaker for its degrade response (no resilience =
            # no breaker = shed-only admission)
            admission.bind(self)
        # multi-chip serving plane (serve/pool.py, opt-in): a
        # DevicePool routes drained batches across per-device worker
        # lanes. None = the single-device dispatch path, byte-for-byte
        # the PR-1 behavior (one attribute load + None check per
        # drained batch). Bound after the per-backend monitors exist —
        # bind() builds each lane's per-(backend, device) breakers
        # from the same monitor factory, plus lane-pinned audit views.
        self.pool = pool
        self.stats.pool = pool
        if pool is not None:
            pool.bind(self)
        self._queues: dict[str, collections.deque[_Request]] = {
            c: collections.deque() for c in CLASSES}
        # the batch THIS thread is running (the batcher, or a pool
        # lane's worker): its stage sink and its batch span, for the
        # op runners' stage hooks (_stage)
        self._running = threading.local()
        # the span that was current on THIS thread when it entered a
        # public submit_* (_caller_submit): the request span's parent,
        # which inside the submit stage's span is no longer the current
        # one (None: the stage made no span, the current one serves)
        self._calling = threading.local()
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._closed = False
        self._flushing = 0       # active flush() calls force draining
        # when the flush or close that forces draining now began: a
        # drain trigger's instant like a deadline's (_tripped)
        self._forced_t = 0.0
        self._inflight = 0
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="cess-submission-engine")
        self._thread.start()

    # ------------------------------------------------------------------
    # submission API — each submit_* returns an EngineFuture; the
    # same-named plain method is the blocking convenience form.
    # ------------------------------------------------------------------

    # -- encode (ErasureCodec) ----------------------------------------
    @_caller_submit("encode")
    def submit_encode(self, data, timeout: float | None = None,
                      tenant: str | None = None) -> EngineFuture:
        """data [B, k, n] (or [k, n]) uint8 -> future of [B, k+m, n]."""
        self._need_codec()
        data, squeeze = self._norm_shards(data, self.codec.k)
        key = ("encode", data.shape[1], data.shape[2])
        return self._submit("encode", key, data.shape[0],
                            {"data": data}, {}, timeout, squeeze,
                            tenant=tenant)

    def encode(self, data, timeout: float | None = None,
               tenant: str | None = None) -> np.ndarray:
        return self._blocking("encode", self.submit_encode, data,
                              timeout=timeout, tenant=tenant)

    # -- decode / repair (ErasureCodec) --------------------------------
    @_caller_submit("repair")
    def submit_reconstruct(self, survivors, present, missing=None,
                           timeout: float | None = None,
                           tenant: str | None = None) -> EngineFuture:
        """survivors [B, k, n] (or [k, n]) rows ordered as ``present``
        -> future of the recovered [B, len(missing), n] shards. One
        request may also be a list or tuple of its k 1-D ``u8[n]``
        NumPy rows (answered as ``[k, n]`` is): they go to the device
        from where they lie, never stacked on the host."""
        self._need_codec()
        present = tuple(present)
        if missing is None:
            missing = tuple(i for i in range(self.codec.k + self.codec.m)
                            if i not in present)
        survivors, squeeze = self._norm_survivors(survivors, len(present))
        key = ("repair", "reconstruct", present, tuple(missing),
               survivors.shape[2])
        return self._submit("repair", key, survivors.shape[0],
                            {"survivors": survivors},
                            {"present": present, "missing": tuple(missing)},
                            timeout, squeeze, tenant=tenant)

    def reconstruct(self, survivors, present, missing=None,
                    timeout: float | None = None,
                    tenant: str | None = None) -> np.ndarray:
        return self._blocking("repair", self.submit_reconstruct,
                              survivors, present, missing,
                              timeout=timeout, tenant=tenant)

    @_caller_submit("repair")
    def submit_decode_data(self, survivors, present,
                           timeout: float | None = None,
                           tenant: str | None = None) -> EngineFuture:
        """survivors as for ``submit_reconstruct`` -> future of the k
        data shards [B, k, n]."""
        self._need_codec()
        present = tuple(present)
        survivors, squeeze = self._norm_survivors(survivors, len(present))
        key = ("repair", "decode", present, (), survivors.shape[2])
        return self._submit("repair", key, survivors.shape[0],
                            {"survivors": survivors},
                            {"present": present}, timeout, squeeze,
                            tenant=tenant)

    def decode_data(self, survivors, present,
                    timeout: float | None = None,
                    tenant: str | None = None) -> np.ndarray:
        return self._blocking("repair", self.submit_decode_data,
                              survivors, present, timeout=timeout,
                              tenant=tenant)

    @_caller_submit("repair")
    def submit_repair_symbol(self, pairs, coeff: int,
                             timeout: float | None = None,
                             tenant: str | None = None) -> EngineFuture:
        """pairs [B, 2, n] (or [2, n]) uint8 (accumulator, fragment)
        rows -> future of the folded [B, 1, n] partial sums
        (acc ^ coeff*fragment) — the helper hop of the regenerating
        repair chain (ops/regen.py). One request may also be the list
        of its two 1-D ``u8[n]`` NumPy rows, as for
        ``submit_reconstruct``: a helper's accumulator and its held
        fragment go to the device from where they lie. Needs a codec
        with the symbol surface (``make_engine(...,
        rs_backend="regen")``); a breaker-degraded batch serves from
        the host twin."""
        self._need_codec()
        if not hasattr(self.codec, "fold_symbol"):
            raise ValueError(
                "repair symbols need a regenerating codec; build the "
                "engine with rs_backend='regen'")
        coeff = int(coeff)
        pairs, squeeze = self._norm_survivors(pairs, 2)
        key = ("repair", "symbol", (coeff,), (), pairs.shape[2])
        return self._submit("repair", key, pairs.shape[0],
                            {"survivors": pairs}, {"coeff": coeff},
                            timeout, squeeze, tenant=tenant)

    def repair_symbol(self, pairs, coeff: int,
                      timeout: float | None = None,
                      tenant: str | None = None) -> np.ndarray:
        return self._blocking("repair", self.submit_repair_symbol,
                              pairs, coeff, timeout=timeout,
                              tenant=tenant)

    # -- tag (AuditBackend, TEE role) ----------------------------------
    @_caller_submit("tag")
    def submit_tag(self, fragment_ids, fragments,
                   timeout: float | None = None,
                   tenant: str | None = None) -> EngineFuture:
        """ids [F, 2] uint32, fragments [F, bytes] uint8 -> future of
        tags [F, blocks, limbs]."""
        self._need_audit()
        ids = _norm(fragment_ids, np.uint32)
        frags = _norm(fragments, np.uint8)
        if ids.ndim != 2 or ids.shape[1] != 2 or frags.ndim != 2 \
                or ids.shape[0] != frags.shape[0]:
            raise ValueError("expected ids [F, 2] and fragments [F, bytes]")
        key = ("tag", frags.shape[1])
        return self._submit("tag", key, frags.shape[0],
                            {"ids": ids, "fragments": frags}, {}, timeout,
                            tenant=tenant)

    def tag_fragments(self, fragment_ids, fragments,
                      timeout: float | None = None,
                      tenant: str | None = None) -> np.ndarray:
        return self._blocking("tag", self.submit_tag, fragment_ids,
                              fragments, timeout=timeout, tenant=tenant)

    # -- prove (miner role) --------------------------------------------
    @_caller_submit("prove")
    def submit_prove_aggregate(self, fragments, tags, idx, nu, r,
                               sectors: int | None = None,
                               timeout: float | None = None,
                               tenant: str | None = None) -> EngineFuture:
        """One miner's aggregated proof over its held set: fragments
        [F, bytes], tags [F, blocks, limbs], coefficients r [F] ->
        future of (mu [sectors], sigma [limbs]). Fragments and tags
        may each be handed in as a sequence of F per-fragment arrays
        (views of the ``bytes`` a miner's store holds): they are kept
        so, never stacked. The fragments stay where they are (host
        memory, no copy): the batch gathers the round's challenged
        blocks and their tags from them, ``podr2.PROVE_CHUNK``
        fragments a device step, and only those go to the device.
        Requests from miners answering the SAME round (same idx/nu)
        coalesce into one batch [miners, F-bucket, challenged blocks,
        ...]; r's zero padding contributes exact modular zeros to the
        fold, so results are bit-identical."""
        self._need_audit()
        from ..ops import podr2

        frags = podr2.held_rows(fragments, np.uint8, 2)
        tag_arr = podr2.held_rows(tags, np.uint32, 3)
        r_arr = np.ascontiguousarray(np.asarray(r, dtype=np.uint32))
        sectors = podr2.SECTORS if sectors is None else sectors
        if r_arr.ndim != 1 \
                or not frags.shape[0] == tag_arr.shape[0] == r_arr.shape[0]:
            raise ValueError("expected fragments [F, bytes], tags "
                             "[F, blocks, limbs], r [F]")
        blocks = tag_arr.shape[1]
        if frags.shape[1] != blocks * sectors * podr2.pf.BYTES_PER_ELEM:
            raise ValueError(
                f"fragments of {frags.shape[1]} B are not {blocks} "
                f"blocks of {sectors} sectors")
        idx, nu = _check_round(idx, nu, blocks)
        key = ("prove", frags.shape[1], blocks, tag_arr.shape[2], sectors,
               _round_digest(blocks, idx, nu))
        return self._submit("prove", key, frags.shape[0],
                            {"fragments": frags, "tags": tag_arr,
                             "r": r_arr},
                            {"idx": idx, "nu": nu, "sectors": sectors},
                            timeout, tenant=tenant)

    def prove_aggregate(self, fragments, tags, idx, nu, r,
                        sectors: int | None = None,
                        timeout: float | None = None,
                        tenant: str | None = None):
        return self._blocking("prove", self.submit_prove_aggregate,
                              fragments, tags, idx, nu, r, sectors,
                              timeout=timeout, tenant=tenant)

    # -- verify (TEE role) ---------------------------------------------
    @_caller_submit("verify")
    def submit_verify_batch(self, fragment_ids, num_blocks, idx, nu,
                            mu, sigma,
                            timeout: float | None = None,
                            tenant: str | None = None) -> EngineFuture:
        """Per-fragment checks: ids [F, 2], mu [F, sectors], sigma
        [F, limbs] -> future of bool [F]. Coalesces along F across
        requests of the same round."""
        self._need_audit()
        ids = _norm(fragment_ids, np.uint32)
        mu = _norm(mu, np.uint32)
        sigma = _norm(sigma, np.uint32)
        idx = np.asarray(idx)
        nu = np.asarray(nu)
        if ids.ndim != 2 or mu.ndim != 2 or sigma.ndim != 2 \
                or not ids.shape[0] == mu.shape[0] == sigma.shape[0]:
            raise ValueError("expected ids [F, 2], mu [F, s], sigma "
                             "[F, limbs]")
        key = ("verify_batch", num_blocks, mu.shape[1], sigma.shape[1],
               _round_digest(num_blocks, idx, nu))
        return self._submit("verify", key, ids.shape[0],
                            {"ids": ids, "mu": mu, "sigma": sigma},
                            {"idx": idx, "nu": nu,
                             "num_blocks": num_blocks}, timeout,
                            tenant=tenant)

    def verify_batch(self, fragment_ids, num_blocks, idx, nu, mu, sigma,
                     timeout: float | None = None,
                     tenant: str | None = None) -> np.ndarray:
        return self._blocking("verify", self.submit_verify_batch,
                              fragment_ids, num_blocks, idx, nu, mu,
                              sigma, timeout=timeout, tenant=tenant)

    @_caller_submit("verify")
    def submit_verify_aggregate(self, fragment_ids, num_blocks, idx, nu,
                                r, mu, sigma,
                                timeout: float | None = None,
                                tenant: str | None = None) -> EngineFuture:
        """One aggregated-proof check (TeeAgent's per-mission verify):
        ids [F, 2], r [F], mu [sectors], sigma [limbs] -> future of
        bool. Missions of the same round coalesce: each mission's owed
        set is padded to a shared F bucket with r = 0 rows (exact
        modular zeros in the fold) and the checks run as one compiled
        program over [missions, F-bucket], the round and the key its
        operands."""
        self._need_audit()
        ids = np.ascontiguousarray(np.asarray(fragment_ids,
                                              dtype=np.uint32)).reshape(-1, 2)
        r_arr = np.ascontiguousarray(np.asarray(r, dtype=np.uint32))
        mu = np.ascontiguousarray(np.asarray(mu, dtype=np.uint32))
        sigma = np.ascontiguousarray(np.asarray(sigma, dtype=np.uint32))
        if r_arr.ndim != 1 or ids.shape[0] != r_arr.shape[0] \
                or mu.ndim != 1 or sigma.ndim != 1:
            raise ValueError("expected ids [F, 2], r [F], mu [s], "
                             "sigma [limbs]")
        idx, nu = _check_round(idx, nu, num_blocks)
        key = ("verify_agg", num_blocks, mu.shape[0], sigma.shape[0],
               _round_digest(num_blocks, idx, nu))
        return self._submit("verify", key, ids.shape[0],
                            {"ids": ids, "r": r_arr, "mu": mu,
                             "sigma": sigma},
                            {"idx": idx, "nu": nu,
                             "num_blocks": num_blocks}, timeout,
                            tenant=tenant)

    def verify_aggregate(self, fragment_ids, num_blocks, idx, nu, r, mu,
                         sigma, timeout: float | None = None,
                         tenant: str | None = None) -> bool:
        return bool(self._blocking(
            "verify", self.submit_verify_aggregate, fragment_ids,
            num_blocks, idx, nu, r, mu, sigma, timeout=timeout,
            tenant=tenant))

    @_caller_submit("verify")
    def submit_verify_round(self, fragment_ids, sizes, num_blocks, idx,
                            nu, agg_words, mu=None, sigma=None,
                            timeout: float | None = None,
                            tenant: str | None = None,
                            proofs=None) -> EngineFuture:
        """A round's missions judged together (TeeAgent.verify_round):
        the missions' owed fragments FLAT, ids [T, 2] in mission order
        with sizes [M] (each >= 1, summing to T), the round (idx, nu)
        and its aggregation key words (podr2.aggregate_words: r is
        derived on the device) -> future of bool [M]. The proofs may
        come LATE: the batch dispatches its folds from ids, sizes and
        the round alone and takes mu [M, sectors], sigma [M, limbs]
        only for the close, so a caller passes ``proofs=LateProofs()``
        (end of this module), waits for its ``folds_out()``, decodes
        while the device folds and ``put``s them; (mu, sigma) given
        here are such proofs already put. Proofs that are mis-shaped,
        ``fail``ed or not there by ``timeout`` fail this request alone.
        A mission whose proof turns out undecodable has had its rows
        folded for nothing, as a well-formed wrong proof always has.
        Requests of one round coalesce row-wise: a mission is rows and
        an index, so ragged owed sets cost no pad beyond the last loop
        step's and no program of their own (podr2 ``round_fold``).
        ``classes.verify.late_proofs`` counts the batches that waited
        for proofs, the stage ``engine.verify.proofs`` their wait."""
        self._need_audit()
        sectors, limbs = self.audit.key.alpha.shape
        ids, sizes, words, proofs = _round_operands(
            fragment_ids, sizes, agg_words, mu, sigma, proofs, sectors,
            limbs)
        idx, nu = _check_round(idx, nu, num_blocks)
        key = ("verify_round", num_blocks, sectors, limbs,
               hashlib.sha256(_round_digest(num_blocks, idx, nu)
                              + words.tobytes()).digest()[:16])
        proofs.future = self._submit(
            "verify", key, ids.shape[0], {"ids": ids, "sizes": sizes},
            {"idx": idx, "nu": nu, "agg_words": words, "proofs": proofs},
            timeout, tenant=tenant)
        return proofs.future

    def verify_round(self, fragment_ids, sizes, num_blocks, idx, nu,
                     agg_words, mu, sigma, timeout: float | None = None,
                     tenant: str | None = None) -> np.ndarray:
        return self._blocking(
            "verify", self.submit_verify_round, fragment_ids, sizes,
            num_blocks, idx, nu, agg_words, mu, sigma, timeout=timeout,
            tenant=tenant)

    # ------------------------------------------------------------------
    # lifecycle / introspection
    # ------------------------------------------------------------------
    def warm_repair(self, patterns, n: int, buckets=(1, 2)) -> None:
        """Pre-compile + pre-stage the repair-class programs for the
        SHAPES the given erasure patterns imply, so a restoral-market
        claim pays kernel time, never compile/staging time.

        patterns: iterable of (present, missing) row tuples;
        n: shard byte width; buckets: row-bucket sizes to warm. A
        repair program is compiled per shape — ``(len(present),
        len(missing), n, bucket)`` — with the pattern's matrix as its
        operand, so what is warmed is every pattern of those shapes,
        the ones never named here included: after this call such a
        pattern compiles nothing, builds no program and takes the same
        dispatch path as a named one. The named patterns' matrices are
        built and staged too (the codec keeps the newest
        ``TPUCodec.MATRICES``), so a caller with a few patterns, like
        the protocol's RS(2,1) miner, never builds one under a claim.
        The default buckets cover a solo claim (bucket 1) AND two
        same-pattern claims coalescing in the batching window
        (bucket 2) — wider coalescence pads to a bucket that was never
        warmed and pays one cold compile; pass more buckets when many
        miners race the same restoral order: a warmed bucket is warmed
        for every count of claims that pads to it (the slice and the
        flatten of three claims in a bucket of four as well as four's).

        Populates the engine program cache under the exact keys
        ``_op_repair`` will look up, base and per pool lane, and warms
        what a HOST claim runs, per device, by one run over zero rows:
        the program that stacks the survivors' linear rows and applies
        the matrix (ops/rs.py ``_apply_rows``), then the flatten its
        result leaves the device through (_fetch_linear). A claim
        whose survivors are already on the device runs the codec's
        program for the stacked array, which is NOT warmed here: the
        first such batch of a shape compiles it. A host codec has
        nothing to warm."""
        self._need_codec()
        codec = self.codec
        # the base programs, then every lane's
        lanes = self.pool.lanes if self.pool is not None else ()
        placements = [None, *lanes]
        patterns = [(tuple(p), tuple(mi)) for p, mi in patterns]

        def run(kind, aux, q, bucket, lane):
            """One (kind, shape, bucket, placement): the cache entry,
            and on a device codec one run of a host claim's way over
            zeros."""
            prog, pattern = self._repair_program(codec, kind, aux, n,
                                                 bucket, False, lane)
            if not self._on_device(codec):
                return
            with self._lane_placement(lane, False):
                # the codec's own call: patterns_new counts batches
                out = prog.__wrapped__(self._put_rows([], q, bucket, n),
                                       *pattern)
                # the bucket full, then every count of claims that pads
                # to it: a padded batch's slice and its flatten
                for total in range(bucket, bucket // 2, -1):
                    part = out[:total]
                    jax.block_until_ready(
                        self._linear_rows_program(part.shape, lane)(part))

        for present, missing in patterns:
            for b in buckets:
                aux = {"present": present, "missing": missing}
                # base first, then EVERY lane's slice of the cache and
                # its device's program — a repair storm fans out
                # across lanes without any lane paying compile/staging
                # time (and a program warmed for device 0 is never
                # handed a lane-3 batch)
                for lane in placements:
                    run("reconstruct", aux, len(present), bucket_rows(b),
                        lane)
        # regen leg: when the codec carries the symbol surface
        # (RegenCodec.warm_fold), warm the helper-fold program and
        # stage every coefficient the single-missing patterns can ask
        # for — same base + per-lane key discipline as the
        # reconstructs, so a symbol chain fanned across lanes never
        # pays compile time
        if not hasattr(codec, "warm_fold"):
            return
        from ..ops import regen

        coeffs: set[int] = set()
        for present, missing in patterns:
            if len(missing) != 1:
                continue
            coeffs.update(regen.repair_coeffs(
                self.codec.k, self.codec.m, present, missing))
        coeffs.discard(0)
        for c in sorted(coeffs):
            for b in buckets:
                for lane in placements:
                    run("symbol", {"coeff": c}, 2, bucket_rows(b), lane)

    def warm_verify(self, challenged: int, missions: int = 512) -> None:
        """Load the verify class's round programs for every shape a
        round of up to ``missions`` missions can meet: one fold and one
        close a mission bucket (8, 64, 512...), base and per pool lane,
        run once over zeros. ``challenged`` is the round's block count
        c (a shape; which blocks is an operand). After it a round of
        any sizes compiles nothing."""
        from ..ops import podr2

        self._need_audit()
        sectors, limbs = self.audit.key.alpha.shape
        lanes = self.pool.lanes if self.pool is not None else ()
        for bucket in podr2.mission_buckets(missions):
            for lane in (None, *lanes):
                self._round_program(challenged, sectors, limbs, bucket,
                                    False, lane, warm=True)

    def attach_stream(self, stream_stats) -> None:
        """Register a streaming driver's StreamStats so its per-stage
        occupancy/stall counters ride the ``cess_engine_*`` metrics
        surface (serve/stream.py). Attach ONE long-lived driver per
        stream source and detach it when the source is done — the
        exported stream gauges are summed over every attached driver,
        so abandoned registrations dilute the bound-where signal."""
        with self._lock:
            self.stats.streams.append(stream_stats)

    def detach_stream(self, stream_stats) -> None:
        """Unregister a driver's StreamStats (identity match); its
        counters stop contributing to the merged gauges. Unknown stats
        objects are ignored (idempotent)."""
        with self._lock:
            try:
                self.stats.streams.remove(stream_stats)
            except ValueError:
                pass

    def stats_snapshot(self) -> dict:
        with self._lock:
            return self.stats.snapshot(
                {c: len(q) for c, q in self._queues.items()})

    def stats_metrics(self) -> dict[str, float]:
        with self._lock:
            return self.stats.metrics(
                {c: len(q) for c, q in self._queues.items()})

    def stats_histograms(self) -> dict:
        """Latency histogram families for the /metrics exposition
        (name -> obs.prom.Histogram); rendering snapshots each one
        consistently, so no engine lock is needed here."""
        return self.stats.histograms()

    def labeled_series(self) -> list:
        """Labeled exposition series — ``(family, kind, labels,
        value)`` — from the SLO board (``cess_slo_*`` per-class gauges,
        ``cess_tenant_*`` counters); empty without one. node/metrics.py
        renders these beside the flat gauges with escaped label
        values."""
        return [] if self.slo is None else self.slo.series()

    def labeled_histograms(self) -> list:
        """Labeled histogram families — ``(family, labels,
        Histogram)`` — the per-tenant latency distributions; empty
        without an SLO board."""
        return [] if self.slo is None else self.slo.tenant_histograms()

    def flush(self, timeout: float | None = None) -> bool:
        """Force-drain everything queued and wait until it resolves
        (no waiting out a coalescing window or a busy lane). Returns
        False if the timeout elapses first; queued work keeps draining
        regardless."""
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._cond:
            self._force_drain_locked()
            self._flushing += 1
            self._cond.notify_all()
            try:
                while any(self._queues.values()) or self._inflight:
                    left = None if deadline is None \
                        else deadline - time.monotonic()
                    if left is not None and left <= 0:
                        return False
                    self._cond.wait(left)
            finally:
                self._flushing -= 1
        return True

    def close(self, timeout: float | None = 5.0) -> None:
        """Drain pending requests, then stop the batcher. Subsequent
        submits raise EngineClosed.

        If the drain outlives ``timeout``, every request still QUEUED
        (not yet handed to the device) is rejected with EngineClosed so
        no caller blocks forever on a future that will never fire —
        the no-silent-drops contract extends to shutdown. A batch
        already in flight still resolves if the process lives on."""
        with self._cond:
            self._force_drain_locked()
            self._closed = True
            self._cond.notify_all()
        self._thread.join(timeout)
        if self.pool is not None:
            # the batcher drained what it will drain; the lane workers
            # finish their pending batches, then stop
            self.pool.close(timeout)
        if self._thread.is_alive():
            with self._cond:
                for cls, q in self._queues.items():
                    while q:
                        r = q.popleft()
                        self.stats.classes[cls].failed += 1
                        r.future._reject(EngineClosed(
                            "engine shut down before this request ran"))
                        r.span.set(outcome="closed").finish()

    # ------------------------------------------------------------------
    # internals
    # ------------------------------------------------------------------
    def _force_drain_locked(self) -> None:
        """A flush or a close begins: unless one is forcing the drain
        already, this is the instant from which queued requests wait on
        the batcher and no longer on policy."""
        if not (self._closed or self._flushing):
            self._forced_t = time.monotonic()

    def _count_caller(self, cls: str, account: str, seconds: float) -> None:
        """One request's ``submit`` or ``handoff`` seconds (stats.py
        CALLER), from the caller's thread."""
        with self._lock:
            self.stats.classes[cls].add_caller(account, seconds)

    def _need_codec(self) -> None:
        if self.codec is None:
            raise ValueError("engine has no ErasureCodec configured")

    def _need_audit(self) -> None:
        if self.audit is None:
            raise ValueError("engine has no AuditBackend configured")

    def _blocking(self, cls: str, submit, *args,
                  timeout: float | None = None,
                  tenant: str | None = None):
        """The blocking convenience form behind encode()/tag_fragments()
        /... — without resilience it is submit().result() verbatim.
        With it, EngineSaturated submits retry under the configured
        backoff policy inside ONE deadline budget: every attempt's
        queue deadline and wait are the budget's REMAINING time, so
        retrying can never extend the caller's deadline. EngineShed is
        deliberately NOT retried — shed load must stop offering, not
        back off and re-offer (policy.py)."""
        res = self.resilience
        if res is None:
            return submit(*args, timeout=timeout,
                          tenant=tenant).result()
        if timeout is None:
            timeout = self.policy.default_timeout
        budget = Budget(timeout)

        def attempt(b):
            left = b.remaining()
            return submit(*args, timeout=left, tenant=tenant).result(left)

        return res.retry.call(attempt, retry_on=(EngineSaturated,),
                              budget=budget, token=cls,
                              stats=res.stats, cls=cls)

    @staticmethod
    def _norm_shards(data, rows: int):
        arr = _norm(data, np.uint8)
        squeeze = arr.ndim == 2
        if squeeze:
            arr = arr[None]
        if arr.ndim != 3 or arr.shape[1] != rows:
            raise ValueError(f"expected [B, {rows}, n] shards, got "
                             f"{arr.shape}")
        return arr, squeeze

    @classmethod
    def _norm_survivors(cls, data, rows: int):
        """_norm_shards for the repair class, which also takes one
        request as a sequence of its ``rows`` 1-D NumPy rows and keeps
        it so (``_HostRows``: ``np.asarray`` of such a list would be a
        fresh stacked copy)."""
        if isinstance(data, (list, tuple)) and data and all(
                isinstance(r, np.ndarray) and r.ndim == 1 for r in data):
            if len(data) != rows or len({r.shape for r in data}) != 1:
                raise ValueError(f"expected {rows} rows of one length, "
                                 f"got {[r.shape for r in data]}")
            return _HostRows(_norm(r, np.uint8) for r in data), True
        return cls._norm_shards(data, rows)

    def _tracer_now(self):
        """The tracer serving this call: the engine's pinned one, else
        whatever is process-armed (obs.trace) — None when tracing is
        off, and every span hook then touches the no-op singleton."""
        return self.tracer if self.tracer is not None \
            else trace.armed_tracer()

    def _submit(self, cls: str, key: tuple, rows: int, arrays: dict,
                aux: dict, timeout: float | None,
                squeeze: bool = False,
                tenant: str | None = None) -> EngineFuture:
        if rows < 1:
            raise ValueError(f"empty {cls} request (0 rows)")
        now = time.monotonic()
        if timeout is None:
            timeout = self.policy.default_timeout
        # SLO-gated admission (serve/adaptive.py): consulted BEFORE
        # anything is queued or allocated — a shed is an explicit
        # EngineShed the caller acts on, never a silent drop. One
        # attribute load + None check when no controller is configured.
        adm = self.admission
        if adm is not None:
            reason = adm.admit(cls, timeout, tenant,
                               queued=len(self._queues[cls]))
            if reason is not None:
                with self._lock:
                    self.stats.classes[cls].shed += 1
                # a shed is an anomaly the flight recorder must keep:
                # a marker span (tail-sampling pins on outcome="shed")
                # plus a journal note — both OUTSIDE the engine lock
                # (the shed-storm bundle reads stats_snapshot()).
                tracer = self._tracer_now()
                if tracer is not None:
                    with tracer.start(f"engine.{cls}", sys="engine",
                                      cls=cls, rows=rows, op=key[0],
                                      outcome="shed", reason=reason,
                                      parent=getattr(
                                          self._calling, "span",
                                          None)) as sp:
                        if tenant is not None:
                            sp.set(tenant=tenant)
                _flight.note("engine", "shed", cls=cls, reason=reason,
                             tenant=tenant)
                raise EngineShed(f"{cls} request shed: {reason}")
        fut = EngineFuture(self, cls)
        device = any(isinstance(a, jax.Array) for a in arrays.values())
        req = _Request(cls=cls, key=key, rows=rows, arrays=arrays,
                       aux=aux, enqueue_t=now,
                       deadline=None if timeout is None else now + timeout,
                       future=fut, squeeze=squeeze, device=device,
                       tenant=tenant)
        tracer = self._tracer_now()
        if tracer is not None:
            # the request span outlives this frame (the batcher thread
            # finishes it when the future resolves), so no with-block
            # can own it — every exit path below closes it explicitly
            req.span = tracer.start(  # cesslint: disable=span-balance — finished at resolve/reject/expire/close (cross-thread span)
                f"engine.{cls}", sys="engine", cls=cls, rows=rows,
                op=key[0],
                parent=getattr(self._calling, "span", None))
            if tenant is not None:
                req.span.set(tenant=tenant)
        saturated = False
        with self._cond:
            if self._closed:
                req.span.set(outcome="closed").finish()
                raise EngineClosed("engine is shut down")
            st = self.stats.classes[cls]
            if len(self._queues[cls]) >= self.policy.queue_cap:
                st.saturated += 1
                saturated = True
            else:
                st.submitted += 1
                self._queues[cls].append(req)
                self._cond.notify_all()
        if saturated:
            # span finish + journal note outside the engine lock: the
            # recorder's listeners (incident bundles) read engine
            # snapshots and must never nest under _cond
            req.span.set(outcome="saturated").finish()
            _flight.note("engine", "saturated", cls=cls)
            raise EngineSaturated(
                f"{cls} queue full ({self.policy.queue_cap})")
        return fut

    # -- batcher thread -------------------------------------------------
    def _run(self) -> None:
        while True:
            batch: list[_Request] = []
            breaches: list[tuple] = []
            with self._cond:
                while True:
                    now = time.monotonic()
                    self._expire(now, breaches)
                    if breaches:
                        break
                    ready = self._ready_class(now)
                    if ready is not None:
                        cls, trip, trigger = ready
                        batch = self._drain(cls, trip)
                        self.stats.classes[cls].drains[trigger] += 1
                        self._inflight += 1
                        break
                    if self._closed:
                        self._cond.notify_all()
                        return
                    self._cond.wait(self._wake_timeout(now))
            if breaches:
                # deadline breaches burn the SLO error budget — fed
                # OUTSIDE the engine lock (board listeners may take
                # breaker locks; same discipline as _account_batch).
                # No batch was drained, so re-enter straight away.
                slo = self.slo
                for bcls, lat, tenant, rows in breaches:
                    slo.observe(bcls, lat, ok=False, tenant=tenant,
                                rows=rows)
                continue
            pool = self.pool
            if pool is not None:
                # multi-chip path: hand the drained batch to the
                # device-pool scheduler — the chosen lane's worker
                # runs it and settles the in-flight count via
                # _batch_done. One attribute load + None check is the
                # whole cost of this seam on the single-device path.
                try:
                    pool.dispatch(batch)
                except BaseException as e:
                    _flight.note("engine", "escape", error=repr(e))
                    self._batch_done()
                    raise
                continue
            try:
                if batch:
                    try:
                        self._run_batch(batch)
                    except BaseException as e:
                        # an exception ESCAPING the batch runner (member
                        # failures are isolated inside it) would kill
                        # the batcher thread — exactly the black-box
                        # moment: journal it before the thread dies so
                        # the incident bundle carries the cause
                        _flight.note("engine", "escape", error=repr(e))
                        raise
            finally:
                with self._cond:
                    self._inflight -= 1
                    self._cond.notify_all()

    def _batch_done(self) -> None:
        """Settle one drained batch's in-flight count — the pool path's
        lane workers call this once the batch's futures are resolved
        (the inline path settles in _run's finally)."""
        with self._cond:
            self._inflight -= 1
            self._cond.notify_all()

    def _knobs(self, cls: str) -> tuple[float | None, int, int]:
        """(max_delay, max_batch_requests, max_batch_rows) for this
        class: the live AdaptiveBatchPolicy values when one is
        configured (its window is always a number), else the static
        policy constants — the one seam through which adaptive control
        steers the batcher."""
        ad = self.adaptive
        if ad is not None:
            return ad.knobs(cls)
        pol = self.policy
        return pol.max_delay, pol.max_batch_requests, pol.max_batch_rows

    def _expire(self, now: float, breaches: list | None = None) -> None:
        """Cancel EVERY queued request whose deadline passed, in every
        class (lock held). Running before readiness checks means a dead
        request in a quiet class cancels promptly even while other
        classes carry traffic, never trips a spurious drain trigger,
        and stops counting against its queue's cap. A timed-out
        request IS an SLO breach (the budget burns whether the device
        ran or not), but the board must never be fed under the engine
        lock — breaches are collected into ``breaches`` for the
        caller to observe after releasing it."""
        slo = self.slo
        for cls, q in self._queues.items():
            if not any(r.deadline is not None and r.deadline <= now
                       for r in q):
                continue
            st = self.stats.classes[cls]
            keep = []
            for r in q:
                if r.deadline is not None and r.deadline <= now:
                    st.timeouts += 1
                    r.future._reject(EngineTimeout(
                        f"{cls} request deadline expired before "
                        "batching"))
                    r.span.set(outcome="timeout").finish()
                    if slo is not None and breaches is not None:
                        breaches.append((cls, now - r.enqueue_t,
                                         r.tenant, r.rows))
                else:
                    keep.append(r)
            q.clear()
            q.extend(keep)

    def _ready_class(self, now: float) -> tuple[str, float, str] | None:
        """(class to drain now, the instant its trigger tripped, the
        trigger's name), or None to keep waiting.

        A drain happens when ANY class trips a trigger (_tripped):
        size (requests or rows), an active flush or engine shutdown
        (drain everything), and the class's window — under the default
        policy none: the class goes the moment an executor is free;
        under a numeric ``max_delay``: the oldest waited that long.
        Once the device is going to be fed, the HIGHEST-PRIORITY
        non-empty class goes first regardless of which class tripped:
        a just-arrived challenge verification preempts the bulk encode
        whose delay expired (policy.py). Expired requests are gone
        already (_expire runs first), so deadlines never trigger
        drains."""
        first_nonempty = None
        for cls in CLASSES:               # priority order
            q = self._queues[cls]
            if not q:
                continue
            if first_nonempty is None:
                first_nonempty = cls
            tripped = self._tripped(q, now, *self._knobs(cls))
            if tripped is not None:
                return (first_nonempty, *tripped)
        return None

    def _executor_free(self) -> bool:
        """Can something run a batch drained now (lock held)? What the
        engine observes of itself, not a setting: on the inline path
        the batcher's thread is the one executor, and it asks only
        between batches; on the pool path a lane has nothing placed on
        it while fewer batches are in flight than there are lanes
        (placement is least-loaded, so that lane gets the next one)."""
        pool = self.pool
        return self._inflight < (1 if pool is None else pool.n_devices)

    def _tripped(self, q, now: float, max_delay: float | None,
                 max_reqs: int, max_rows: int
                 ) -> tuple[float, str] | None:
        """(the instant a non-empty queue's drain trigger tripped, the
        trigger: stats.py DRAIN_TRIGGERS), None while none has (lock
        held). The earliest of: ``forced``, the start of the flush or
        close that forces the drain; ``size``, the enqueue of the
        request that filled the request budget or the row budget; and
        the class's window. Without one (``max_delay`` None, the
        default policy) that is ``idle``: the earliest enqueue in the
        queue, as soon as an executor is free (_executor_free) — with every
        executor busy the class gathers companions, and the batch
        whose end frees one re-evaluates it (_run's finally,
        _batch_done: a notify, no timer). With a numeric ``max_delay``
        it is ``window``: the oldest request's enqueue + ``max_delay``,
        whatever the device is doing. Of two triggers at one instant
        the one named first here counts. Up to the instant a member
        waits because policy says so, from it on for the batcher or a
        lane (``_open_stages``: the queue stage's two halves)."""
        trips = []
        if self._closed or self._flushing:
            trips.append((self._forced_t, "forced"))
        if len(q) >= max_reqs:
            trips.append((q[max_reqs - 1].enqueue_t, "size"))
        rows = 0
        for r in q:
            rows += r.rows
            if rows >= max_rows:
                trips.append((r.enqueue_t, "size"))
                break
        if max_delay is None:
            if self._executor_free():
                # the earliest stamp, not q[0]'s: a request is stamped
                # before it takes the lock, so two can queue out of
                # order, and no member of an idle drain waited on policy
                trips.append((min(r.enqueue_t for r in q), "idle"))
        elif q[0].enqueue_t + max_delay <= now:
            trips.append((q[0].enqueue_t + max_delay, "window"))
        # min keeps the first of equals: the order above breaks ties
        return min(trips, key=lambda trip: trip[0], default=None)

    def _wake_timeout(self, now: float) -> float | None:
        """Seconds until the batcher has to look again without being
        notified (lock held), None for never: the earliest request
        deadline and, for a class with a numeric window, the earliest
        enqueue + ``max_delay``. A class without a window sets no
        timer: a submit, a finished batch, a flush or a close notify."""
        times = []
        for cls, q in self._queues.items():
            if not q:
                continue
            max_delay = self._knobs(cls)[0]
            for r in q:
                if max_delay is not None:
                    times.append(r.enqueue_t + max_delay)
                if r.deadline is not None:
                    times.append(r.deadline)
        return max(min(times) - now, 0.0) if times else None

    # ops that pad every request's OWN row axis to the batch-wide
    # bucket (stacked, not concatenated): cap the bucket spread so one
    # huge request cannot multiply the device work of its small peers
    _STACKED_OPS = ("prove", "verify_agg")
    PAD_SPREAD = 4

    def _anchor_index(self, cls: str, q) -> int:
        """Which queued request anchors the next batch. Without tenant
        accounting: the oldest (index 0, the PR-1 behavior). With an
        SLO board: weighted-fair across the tenants present in the
        queue — the anchor is the OLDEST request of the tenant with
        the smallest served-device-rows deficit counter, so a heavy
        uploader's backlog cannot indefinitely pre-empt another
        tenant's differently-keyed work inside the same class (ties
        break lexicographically: deterministic). Lock held."""
        if self.slo is None or len(q) < 2:
            return 0
        served = self._tenant_rows.get(cls, {})
        first_of: dict[str, int] = {}
        for i, r in enumerate(q):
            t = self._fair_key(r.tenant, served)
            if t not in first_of:
                first_of[t] = i
        if len(first_of) < 2:
            return 0
        tenant = min(first_of, key=lambda t: (served.get(t, 0), t))
        return first_of[tenant]

    def _fair_key(self, tenant: "str | None", served: dict) -> str:
        """Deficit-counter key for a tenant: its own name while
        in-cap, the board's shared overflow bucket once the board's
        ``max_tenants`` distinct names exist (same cap and same
        bucket as the ``cess_tenant_*`` exposition, so the scrape can
        explain the scheduler's grouping). The ONE aliasing rule for
        both sides — _account_batch charges served rows under it and
        _anchor_index reads deficits through it; a divergence inverts
        fairness (an over-cap tenant whose charges land in the
        overflow but whose raw name reads 0 anchors every drain)."""
        t = tenant or ""
        if t not in served and len(served) >= self.slo.max_tenants:
            from ..obs.slo import OVERFLOW

            return OVERFLOW
        return t

    def _drain(self, cls: str, trip: float = 0.0) -> list[_Request]:
        """Pop one coalescible batch (lock held): take queued requests
        sharing the ANCHOR request's key up to the size budgets;
        others stay queued in order. The anchor is the oldest request
        (or the fair-queued tenant's oldest — _anchor_index). Expired
        requests are already gone (_expire runs under the same lock
        hold). Every member is stamped with ``trip``, the instant the
        drain's trigger tripped (here and not in ``_run``: a name bound
        to a request in the batcher's own frame would keep that
        request's payload alive until the next drain)."""
        q = self._queues[cls]
        if not q:
            return []
        idx = self._anchor_index(cls, q)
        first = q[idx]
        stacked = first.key[0] in self._STACKED_OPS
        anchor_bucket = bucket_rows(first.rows)
        _, max_reqs, max_rows = self._knobs(cls)
        batch, rest, rows = [first], [], first.rows
        for i, r in enumerate(q):
            if i == idx:
                continue
            fits = (r.key == first.key
                    and len(batch) < max_reqs
                    and rows + r.rows <= max_rows)
            if fits and stacked:
                b = bucket_rows(r.rows)
                fits = (b <= self.PAD_SPREAD * anchor_bucket
                        and anchor_bucket <= self.PAD_SPREAD * b)
            if fits:
                batch.append(r)
                rows += r.rows
            else:
                rest.append(r)
        q.clear()
        q.extend(rest)
        for r in batch:
            r.trip_t = trip
        return batch

    # -- stage clock (obs.trace.stage; stats.STAGES) ----------------------
    def _open_stages(self, batch: list[_Request],
                     span=trace.NOOP_SPAN) -> dict:
        """Start the sink of a batch this thread is about to run. The
        queue stage ends here (each member's enqueue -> now, summed: a
        counter, no span), split where the drain trigger tripped into
        ``coalesce`` / ``wake`` (stats.py QUEUE_PARTS; a member enqueued
        after the trip waited on no policy); _stage fills the rest."""
        now = time.monotonic()
        coalesce = wake = 0.0
        for r in batch:
            trip = min(max(r.trip_t, r.enqueue_t), now)
            coalesce += trip - r.enqueue_t
            wake += now - trip
        name = f"engine.{batch[0].cls}.queue"
        sink = {name: [1, coalesce + wake],
                name + ".coalesce": [1, coalesce],
                name + ".wake": [1, wake]}
        self._running.batch = (sink, span)
        return sink

    def _stage(self, cls: str, stage: str):
        """One stage of the batch this thread is running — once per
        batch, never per row or per request."""
        sink, span = getattr(self._running, "batch",
                             (None, trace.NOOP_SPAN))
        return trace.stage(f"engine.{cls}.{stage}", sink, parent=span)

    def _close_stages(self, cls: str, sink: dict, *of) -> None:
        with self._lock:
            long = self.stats.classes[cls].add_stages(sink)
        if long:                  # a wait over LONG_WAIT_S: rare
            self._long_waits(cls, sink, long, *of)

    def _run_batch(self, batch: list[_Request], lane=None,
                   tried=None) -> bool:
        """Run one coalesced batch: the whole of it is one
        ``cess:engine.<class>.batch`` annotation in a profiler trace
        (its Tracer span is ``engine.batch``, started in
        _serve_batch)."""
        with trace.stage(f"engine.{batch[0].cls}.batch",
                         parent=trace.NOOP_SPAN):
            return self._serve_batch(batch, lane, tried)

    def _serve_batch(self, batch: list[_Request], lane=None,
                     tried=None) -> bool:
        """_run_batch's body. ``lane`` is None on the inline
        single-device path; on the pool path it is the DeviceLane
        whose worker is running this batch — breaker gating then uses
        the lane's per-(backend, device) monitor, dispatch pins to the
        lane's device, and a denied/failed lane DRAINS the batch to a
        healthy sibling (``tried`` carries the lane indices that
        already failed it). Returns True when the batch was handed
        off that way — its futures are then the sibling's to settle."""
        cls = batch[0].cls
        op = batch[0].key[0]
        runner: Callable = getattr(self, f"_op_{op}")
        res = self.resilience
        mons = self.monitors if lane is None else lane.monitors
        mon = mons.get(self._BACKEND_OF.get(op))
        # breaker open (and no probe due): drain to a healthy sibling
        # lane when there is one, else serve on the CPU fallback
        degraded = res is not None and res.fallback \
            and mon is not None and not mon.allow()
        if degraded and lane is not None and self.pool.requeue(
                batch, lane, tried if tried is not None else set()):
            return True
        if degraded:
            res.stats.note_degraded(cls)
        tracer = self._tracer_now()
        bspan = trace.NOOP_SPAN
        if tracer is not None:
            # the coalesced-batch span: parented to its first member's
            # request span (the link that makes occupancy/pad-waste
            # attributable per request); closed on every path below
            bspan = tracer.start(  # cesslint: disable=span-balance — finished on both the success and error paths below
                "engine.batch", sys="engine", parent=batch[0].span,
                op=op, cls=cls, members=len(batch),
                rows=sum(r.rows for r in batch), degraded=degraded)
            for r in batch:
                r.span.event("batched", batch_span=bspan.span_id,
                             members=len(batch))
        stages = self._open_stages(batch, bspan)
        t0 = time.monotonic()
        try:
            # current=True: the device span is the batcher thread's
            # active span for the dispatch, so fault-injection firings
            # (faults.inject below) annotate it via obs.event
            with self._lane_placement(lane, degraded), \
                    (trace.NOOP_SPAN if tracer is None else tracer.start(
                        f"device.{op}", sys="device", parent=bspan,
                        current=True, op=op, degraded=degraded,
                        backend="cpu-fallback" if degraded else "primary",
                        **({} if lane is None
                           else {"device": lane.index}))):
                if not degraded:
                    faults.inject("engine.dispatch")   # chaos seam
                    if lane is not None:
                        # per-lane seam: chaos plans kill ONE lane's
                        # dispatch while its siblings stay healthy
                        faults.inject(f"engine.dispatch.d{lane.index}")
                        # per-class lane seam: a plan can trip ONE
                        # class's dispatches on one lane (the repair
                        # storm trips repair lane 0 mid-storm while
                        # the same lane keeps serving uploads)
                        faults.inject(
                            f"engine.dispatch.{cls}.d{lane.index}")
                # two-arg call off the pool path: the (batch, degraded)
                # runner signature is a public monkeypatch seam
                results, device_rows = (
                    runner(batch, degraded) if lane is None
                    else runner(batch, degraded, lane))
        except Exception as e:        # op failure
            if mon is not None and not degraded:
                mon.record_error()
            bspan.set(error=repr(e)).finish()
            if lane is not None and not degraded and self.pool.requeue(
                    batch, lane, tried if tried is not None else set()):
                # member isolation preserved: the batch moves WHOLE to
                # a healthy sibling; salvage (solo re-runs / CPU
                # degradation) only runs once every sibling failed it
                return True
            if res is not None and self._salvage_batch(runner, batch, e,
                                                       mon, degraded,
                                                       lane):
                return False
            with self._lock:
                self.stats.classes[cls].failed += len(batch)
            fail_t = time.monotonic()
            for r in batch:
                r.future._reject(e)
                r.span.set(outcome="error", error=repr(e)).finish()
                self._observe_failure(r, fail_t)
            return False
        if mon is not None and not degraded:
            mon.record_success(time.monotonic() - t0)
        with self._stage(cls, "resolve"):
            self._account_batch(batch, device_rows, bspan, lane=lane,
                                stages=stages)
            for r, out in zip(batch, results):
                r.future._resolve(out)
        # the request spans are the roots: they close last, over a
        # finished subtree (the flight recorder gathers a trace when
        # its root finishes)
        bspan.finish()
        for r in batch:
            if r.span is not trace.NOOP_SPAN:
                r.span.set(outcome="ok").finish()
        self._close_stages(cls, stages, batch, device_rows, lane)
        return False

    def _observe_failure(self, r: _Request, now: float) -> None:
        """Feed one rejected request into the SLO windows (failures
        burn the error budget). One None check on the disabled path."""
        slo = self.slo
        if slo is not None:
            slo.observe(r.cls, now - r.enqueue_t, ok=False,
                        tenant=r.tenant, rows=r.rows)

    def _account_batch(self, batch: list[_Request], device_rows: int,
                       batch_span=trace.NOOP_SPAN, lane=None,
                       stages: dict | None = None) -> None:
        done = time.monotonic()
        real_rows = sum(r.rows for r in batch)
        cls = batch[0].cls
        with self._lock:
            st = self.stats.classes[cls]
            st.batches += 1
            st.batched_requests += len(batch)
            st.rows += real_rows
            st.padded_rows += max(device_rows - real_rows, 0)
            st.completed += len(batch)
            for r in batch:
                lat = done - r.enqueue_t
                st.latencies.append(lat)
                st.hist.observe(lat)
            if self.slo is not None:
                # the weighted-fair drain's deficit counters (bounded:
                # past the cap a new tenant shares the overflow bucket)
                served = self._tenant_rows.setdefault(cls, {})
                for r in batch:
                    t = self._fair_key(r.tenant, served)
                    served[t] = served.get(t, 0) + r.rows
        # SLO + adaptive feeds OUTSIDE the engine lock (board and
        # policy own their locks; listeners may touch breaker locks) —
        # and only when armed: the disabled path pays one attribute
        # load + None check per batch, allocating nothing (the
        # zero-cost-when-off contract, cess_tpu/obs)
        slo = self.slo
        if slo is not None:
            for r in batch:
                slo.observe(cls, done - r.enqueue_t, ok=True,
                            tenant=r.tenant, rows=r.rows)
        ad = self.adaptive
        if ad is not None:
            occ = len(batch)
            for r in batch:
                ad.note(cls, done - r.enqueue_t, occ)
        prof = self.profile
        if prof is not None:
            # continuous profiling feed (obs/profile.py): the byte
            # count is only computed when armed; the timings are the
            # batch's own stage clock (queue = the members' waits,
            # dispatch = the program call, sync = the wait on it)
            def seconds(stage):
                return (stages or {}).get(f"engine.{cls}.{stage}",
                                          (0, 0.0))[1]

            prof.on_batch(
                cls, device_rows,
                0 if lane is None else lane.index,
                rows=real_rows,
                padded=max(device_rows - real_rows, 0),
                requests=len(batch),
                nbytes=sum(a.nbytes for r in batch
                           for a in r.arrays.values()),
                queue_s=seconds("queue"),
                dispatch_s=seconds("dispatch"),
                sync_s=seconds("wait"))
        # span attribution only when the spans are real: the disabled
        # path must not pay the round()s / kwargs dicts per request
        if batch_span is not trace.NOOP_SPAN:
            pad = max(device_rows - real_rows, 0)
            pad_waste = pad / device_rows if device_rows else 0.0
            batch_span.set(device_rows=device_rows,
                           pad_waste=round(pad_waste, 4))
            for r in batch:
                r.span.set(occupancy=len(batch),
                           pad_waste=round(pad_waste, 4),
                           batch_span=batch_span.span_id,
                           latency_s=round(done - r.enqueue_t, 6))

    def _salvage_batch(self, runner: Callable, batch: list[_Request],
                       primary_exc: BaseException, mon,
                       degraded: bool, lane=None) -> bool:
        """A batch op failed with resilience configured: isolate the
        members — re-run each ALONE once (one poisoned request must
        not fail its batch-mates), then, if the device attempt failed
        and fallback is allowed, serve the member on the CPU reference
        backend. Resolves or rejects every future; returns True (the
        caller is done with the batch)."""
        res = self.resilience
        cls = batch[0].cls
        tracer = self._tracer_now()
        if len(batch) > 1:
            res.stats.note_batch_requeues(len(batch))
        # solo re-runs use the primary backend only while the breaker
        # is closed (or the failed batch was already degraded): when
        # the failure WAS a recovery probe against an open breaker,
        # re-probing the known-bad device once per member would
        # amplify the outage latency by the batch size — members go
        # straight to the fallback instead
        solo = len(batch) > 1 \
            and (degraded or mon is None or mon.state == "closed")
        for r in batch:
            out = None
            exc = primary_exc
            # the member's own stage sink: it is served as a batch of
            # one; what the failed attempt recorded is dropped
            stages = self._open_stages([r])
            if solo:
                r.span.event("salvage.solo")
                try:
                    with self._lane_placement(lane, degraded):
                        if not degraded:
                            faults.inject("engine.dispatch")
                            if lane is not None:
                                faults.inject(
                                    f"engine.dispatch.d{lane.index}")
                        out, rows = (runner([r], degraded)
                                     if lane is None
                                     else runner([r], degraded, lane))
                except Exception as e:  # noqa: BLE001 — per-member isolation
                    exc = e
                    if mon is not None and not degraded:
                        mon.record_error()
                else:
                    if mon is not None and not degraded:
                        mon.record_success(0.0)
            if out is None and not degraded and res.fallback \
                    and mon is not None:
                try:
                    with (trace.NOOP_SPAN if tracer is None
                          else tracer.start("resilience.fallback",
                                            sys="resilience",
                                            parent=r.span,
                                            current=True, cls=cls)):
                        out, rows = (runner([r], True) if lane is None
                                     else runner([r], True, lane))
                    res.stats.note_fallback(cls)
                except Exception as e:  # noqa: BLE001 — fallback is best-effort
                    exc = e
            if out is None:
                with self._lock:
                    self.stats.classes[cls].failed += 1
                r.future._reject(exc)
                r.span.set(outcome="error", error=repr(exc)).finish()
                self._observe_failure(r, time.monotonic())
            else:
                with self._stage(cls, "resolve"):
                    self._account_batch([r], rows, lane=lane,
                                        stages=stages)
                    r.future._resolve(out[0])
                    r.span.set(outcome="ok").finish()
                self._close_stages(cls, stages, [r], rows, lane)
        return True

    # -- op runners (batcher thread only) -------------------------------
    def _split_rows(self, batch: list[_Request], out, lane=None) -> list:
        """Slice a batch result back per request. Device submitters get
        ``jax.Array`` slices (no host materialization anywhere on their
        path); an all-host batch is fetched ONCE and sliced as numpy —
        a byte result ``[rows, r, n]`` as linear pieces (_fetch_linear),
        every other result whole.

        The result is synced BEFORE futures resolve: zero-copy means
        no D2H transfer, not fire-and-forget — a future must mean
        "this batch actually completed", the per-class latency
        percentiles must measure enqueue->completion (not async
        dispatch), and a device-side execution failure must reject the
        batch through _run_batch's error path instead of resolving
        futures with poisoned arrays."""
        cls = batch[0].cls
        with self._stage(cls, "wait"):
            if isinstance(out, jax.Array):
                jax.block_until_ready(out)
        with self._stage(cls, "fetch"):
            pieces = None
            if isinstance(out, jax.Array) \
                    and not any(r.device for r in batch):
                if out.dtype == np.uint8 and out.ndim == 3:
                    pieces = self._fetch_linear(cls, out, lane, batch)
                else:
                    out = np.asarray(out)
            results, off = [], 0
            for i, r in enumerate(batch):
                piece = out[off:off + r.rows] if pieces is None \
                    else pieces[i]
                if r.device and not isinstance(piece, jax.Array):
                    piece = jnp.asarray(piece)
                elif not r.device and isinstance(piece, jax.Array):
                    piece = np.asarray(piece)
                results.append(piece[0] if r.squeeze else piece)
                off += r.rows
        return results

    def _linear_rows_program(self, shape: tuple, lane):
        """The cache entry of the flatten for one result shape (and,
        on the pool path, one lane: jit compiles it per device)."""
        return self.programs.get(
            self._key(("linear_rows",) + shape, False, lane),
            lambda: _linear_rows)

    def _fetch_linear(self, cls: str, out: jax.Array, lane,
                      batch: list[_Request]) -> list[np.ndarray]:
        """Fetch a byte result ``[rows, r, n]`` as ``rows`` linear pieces
        of ``r * n`` bytes (_linear_rows), every piece on its way before
        the first is waited for, and hand each request its own
        ``np.uint8 [rows_i, r, n]``: a request of one batch row its
        piece itself, reshaped (a view: no host copy; a caller that
        keeps one row of it keeps all ``r * n`` bytes alive); a request
        of several (``reconstruct`` of ``[B, k, n]``) one ``memcpy`` a
        piece — the ``regroup``, a stage inside ``fetch``, ``regroup_s``
        / ``regrouped_bytes``; ``result_bytes`` counts either. The batch
        is never rebuilt on the host: a fresh 128 MiB a batch of sixteen
        claims (PERF.md, PR 38: 45 of 57 ms of fetch at five)."""
        sent = self._linear_rows_program(out.shape, lane)(out)
        for piece in sent:
            piece.copy_to_host_async()
        flat, at = [np.asarray(piece) for piece in sent], 0
        pieces, many = [], []
        for i, r in enumerate(batch):
            rows = flat[at:at + r.rows]
            at += r.rows
            pieces.append(rows[0])
            if r.rows > 1:
                many.append((i, rows))
        regroup_s = regrouped = 0
        if many:
            with trace.stage(f"engine.{cls}.fetch.regroup") as stage:
                for i, rows in many:
                    pieces[i] = np.stack(rows)
            regroup_s = stage.seconds
            regrouped = sum(pieces[i].nbytes for i, _ in many)
        with self._lock:
            st = self.stats.classes[cls]
            st.linear_fetches += 1
            st.result_bytes += at * out.shape[1] * out.shape[2]
            st.regroup_s += regroup_s
            st.regrouped_bytes += regrouped
        return [piece.reshape((r.rows,) + out.shape[1:])
                for r, piece in zip(batch, pieces)]

    def _rs_backend(self, degraded: bool):
        """The ErasureCodec serving this batch: the configured device
        gate, or the CPU reference when the breaker degraded it. The
        codec is shared across pool lanes — lane placement comes from
        the _lane_placement default-device scope, not the gate."""
        return self._fallback_codec if degraded else self.codec

    def _audit_backend(self, degraded: bool, lane=None):
        """The AuditBackend serving this batch. Unlike the codec, an
        AuditBackend pins every op to ITS OWN device
        (ops/audit_backend.py ``_on``), so the pool path must use the
        lane's own view — the shared gate would collapse every audit
        batch back onto one chip."""
        if degraded:
            return self._fallback_audit
        if lane is not None and lane.audit is not None:
            return lane.audit
        return self.audit

    @staticmethod
    def _on_device(codec) -> bool:
        """A device codec (ops/rs.py TPUCodec and its kin): it keeps
        its patterns' matrices and takes survivors as ``LinearRows``.
        The host codecs (``rs_backend="cpu"`` / ``"native"``, the
        breaker's fallback) take host arrays."""
        return hasattr(codec, "warm_reconstruct")

    @staticmethod
    def _lane_placement(lane, degraded: bool):
        """Device scope for a batch dispatch: the lane's device on the
        pool path, JAX's default placement otherwise (and always for
        degraded batches — the CPU fallback gates pin themselves)."""
        if lane is None or degraded:
            return contextlib.nullcontext()
        return jax.default_device(lane.device)

    @staticmethod
    def _key(key: tuple, degraded: bool, lane=None) -> tuple:
        """Degraded programs cache under their own keys — a breaker
        flip must never hand a device program a CPU batch or vice
        versa. On the pool path the key grows a device component for
        the same reason: a program compiled (warmed) for lane 0's
        device must never be handed a batch placed on lane 3
        (degraded keys stay device-free — the CPU fallback program is
        one program, shared by every lane)."""
        if degraded:
            return key + ("cpu-fallback",)
        if lane is not None:
            return key + (("device", lane.index),)
        return key

    def _op_encode(self, batch, degraded=False, lane=None):
        codec = self._rs_backend(degraded)
        with self._stage("encode", "assemble"):
            data = _concat_rows([r.arrays["data"] for r in batch])
            total = data.shape[0]
            bucket = bucket_rows(total)
            _, k, n = data.shape
            data = _pad_axis0(data, bucket)
        with self._stage("encode", "dispatch"):
            prog = self.programs.get(self._key(("encode", k, n, bucket),
                                               degraded, lane),
                                     lambda: codec.encode)
            out = prog(data)[:total]
        return self._split_rows(batch, out, lane), bucket

    def _op_repair(self, batch, degraded=False, lane=None):
        """All three kinds (reconstruct, decode, symbol: they differ in
        the matrix). What the batch holds decides the survivors' way to
        the device: with a device-resident contributor they are
        concatenated there (no copy of what is resident); on a host
        codec (``rs_backend="cpu"``, the breaker's fallback) they stay
        a host array; every other batch goes up as its ``total * q``
        linear rows, put from the callers' own memory and stacked on
        the device inside the program (``_put_rows``)."""
        codec = self._rs_backend(degraded)
        kind = batch[0].key[1]
        aux = batch[0].aux
        survs = [r.arrays["survivors"] for r in batch]
        total = sum(r.rows for r in batch)
        bucket = bucket_rows(total)
        _, q, n = survs[0].shape
        as_rows = self._on_device(codec) \
            and not any(r.device for r in batch)
        with self._stage("repair", "assemble"):
            if as_rows:
                surv = [row for a in survs for row in _row_views(a)]
            else:
                surv = _pad_axis0(_concat_rows(
                    [np.stack(a)[None] if isinstance(a, _HostRows) else a
                     for a in survs]), bucket)
        with self._stage("repair", "dispatch"):
            prog, pattern = self._repair_program(codec, kind, aux, n,
                                                 bucket, degraded, lane)
            if as_rows:
                surv = self._put_rows(surv, q, bucket, n)
            with self._lock:
                st = self.stats.classes["repair"]
                st.linear_puts += as_rows
                st.symbol_folds += kind == "symbol"
            out = prog(surv, *pattern)[:total]
        return self._split_rows(batch, out, lane), bucket

    @staticmethod
    def _put_rows(rows: list, q: int, bucket: int, n: int):
        """A host batch's survivors on their way up: each ``u8[n]`` row
        put as it lies (one ``device_put`` of the list, on the device
        of the placement scope the batch runs under), padded to the
        bucket's ``bucket * q`` rows with a zero row made on the device
        (all of them, for warm_repair's run), for the codec to stack
        inside its program (ops/rs.py LinearRows). The callers' rows
        stay referenced by their requests until the batch resolves,
        after ``wait`` has seen the result ready."""
        from ..ops.rs import LinearRows

        placed = jax.device_put(rows)
        if len(placed) < bucket * q:
            placed += [jnp.zeros((n,), jnp.uint8)] * (bucket * q
                                                      - len(placed))
        return LinearRows(tuple(placed), q)

    def _repair_program(self, codec, kind: str, aux: dict, n: int,
                        bucket: int, degraded: bool, lane):
        """The repair class's cached program for one (kind, SHAPE,
        row bucket) and the pattern it is called with: ``program(
        survivors, *pattern)``. The shape is the pattern's row counts
        ``(q, r)``; the pattern itself (which rows, which coefficient)
        is an argument, so a pattern never seen before builds no
        program."""
        if kind == "reconstruct":
            present, missing = aux["present"], aux["missing"]
            key = ("repair", len(present), len(missing), n, bucket)
            call, pattern = codec.reconstruct, (present, missing)
        elif kind == "symbol":
            pattern = (aux["coeff"],)
            call = getattr(codec, "fold_symbol", None)
            if call is None:
                # breaker-degraded (or plain-reference fallback) codec:
                # serve the fold from the host twin — the chain stays
                # bit-identical, only the placement degrades
                from ..ops import regen

                call = regen.fold_symbol_pairs
            key = ("symbol", n, bucket)
        else:
            pattern = (aux["present"],)
            key = ("decode", len(pattern[0]), n, bucket)
            call = codec.decode_data
        prog = self.programs.get(self._key(key, degraded, lane),
                                 lambda: self._counting_matrices(codec,
                                                                 call))
        return prog, pattern

    def _counting_matrices(self, codec, call):
        """``call`` (``codec``'s reconstruct / decode_data /
        fold_symbol) as the repair class's program. A device codec
        builds the matrix of a pattern it does not hold inside the call
        and says so through an obs.trace.stage sink
        (TPUCodec._matrix_for): counted here as ``patterns_new`` /
        ``matrix_build_s``. The host codecs (the CPU fallback) keep no
        matrices and are called as they are."""
        if not self._on_device(codec):
            return call

        @functools.wraps(call)
        def counted(survivors, *pattern):
            sink: dict = {}
            out = call(survivors, *pattern, sink=sink)
            if sink:
                with self._lock:
                    st = self.stats.classes["repair"]
                    st.patterns_new += sink["repair.matrix"][0]
                    st.matrix_build_s += sink["repair.matrix.build"][1]
            return out
        return counted

    def _op_tag(self, batch, degraded=False, lane=None):
        audit = self._audit_backend(degraded, lane)
        with self._stage("tag", "assemble"):
            ids = _concat_rows([r.arrays["ids"] for r in batch])
            frags = _concat_rows([r.arrays["fragments"] for r in batch])
            total = frags.shape[0]
            bucket = bucket_rows(total)
            nbytes = frags.shape[1]
            ids = _pad_axis0(ids, bucket)
            frags = _pad_axis0(frags, bucket)
        with self._stage("tag", "dispatch"):
            # one enqueue: the backend calls ops/podr2.py TAG_PROGRAM
            # (one executable a batch shape and device, the key its
            # operands) under its own fault seam and device scope
            prog = self.programs.get(self._key(("tag", nbytes, bucket),
                                               degraded, lane),
                                     lambda: audit.tag_fragments)
            out = prog(ids, frags)[:total]
        return self._split_rows(batch, out, lane), bucket

    def _op_verify_batch(self, batch, degraded=False, lane=None):
        audit = self._audit_backend(degraded, lane)
        aux = batch[0].aux
        with self._stage("verify", "assemble"):
            ids = _concat_rows([r.arrays["ids"] for r in batch])
            mu = _concat_rows([r.arrays["mu"] for r in batch])
            sigma = _concat_rows([r.arrays["sigma"] for r in batch])
            total = ids.shape[0]
            bucket = bucket_rows(total)
            ids = _pad_axis0(ids, bucket)
            mu = _pad_axis0(mu, bucket)
            sigma = _pad_axis0(sigma, bucket)
        num_blocks, idx, nu = (aux["num_blocks"], aux["idx"], aux["nu"])
        with self._stage("verify", "dispatch"):
            prog = self.programs.get(
                self._key(("verify_batch", batch[0].key, bucket),
                          degraded, lane),
                lambda: (lambda i, u, s: audit.verify_batch(
                    i, num_blocks, idx, nu, u, s)))
            out = prog(ids, mu, sigma)[:total]
        return self._split_rows(batch, out, lane), bucket

    def _stacked_program(self, batch, fb: int, rb: int, degraded: bool,
                         lane, bind, *which):
        """The stacked classes' cached program for one batch shape:
        the request key WITHOUT its round digest (the digest decides
        which requests coalesce, never which program runs) plus the
        challenge length and the (F, miners) buckets — the same entry
        round after round. ``bind(audit)`` gives the process-wide
        jitted program and the operands that are the backend's own
        (its key); the entry adds them to the batch's, counts what it
        hands over (``operand_bytes``) and places the call on the
        audit backend's device, as every other audit op is. ``which``
        names a second program of the same shape (a chunked prove's
        later steps)."""
        cls = batch[0].cls
        audit = self._audit_backend(degraded, lane)

        def build():
            fn, fixed = bind(audit)

            def placed(*operands):
                operands += fixed
                nbytes = sum(a.nbytes for a in operands)
                with self._lock:
                    self.stats.classes[cls].operand_bytes += nbytes
                with jax.default_device(audit.device):
                    return fn(*operands)
            return placed

        key = batch[0].key[:-1] + (len(batch[0].aux["idx"]), fb, rb) \
            + which
        return self.programs.get(self._key(key, degraded, lane), build)

    def _op_verify_agg(self, batch, degraded=False, lane=None):
        aux = batch[0].aux
        with self._stage("verify", "assemble"):
            fb = bucket_rows(max(r.rows for r in batch))
            rb = bucket_rows(len(batch))
            ids = np.zeros((rb, fb, 2), dtype=np.uint32)
            rs = np.zeros((rb, fb), dtype=np.uint32)
            mu = np.zeros((rb,) + batch[0].arrays["mu"].shape, np.uint32)
            sigma = np.zeros((rb,) + batch[0].arrays["sigma"].shape,
                             np.uint32)
            for i, r in enumerate(batch):
                ids[i, :r.rows] = r.arrays["ids"]
                rs[i, :r.rows] = r.arrays["r"]
                mu[i] = r.arrays["mu"]
                sigma[i] = r.arrays["sigma"]
        num_blocks = aux["num_blocks"]

        def bind(audit):
            # the key as host words, read once per cached program: every
            # operand is then a host array, placed with the program
            key = audit.key
            return (functools.partial(
                        _VERIFY_PROGRAM, num_blocks=num_blocks,
                        prf_impl=str(jax.random.key_impl(key.prf_key))),
                    (np.asarray(key.alpha),
                     np.asarray(jax.random.key_data(key.prf_key))))

        with self._stage("verify", "dispatch"):
            prog = self._stacked_program(batch, fb, rb, degraded, lane,
                                         bind)
            out = prog(ids, rs, mu, sigma, aux["idx"], aux["nu"])
            self._count_verify(len(batch), 1, rb * fb * len(aux["idx"]))
        with self._stage("verify", "wait"):
            # the fetch below would block on the device anyway: the
            # wait is named, nothing is added to the path
            jax.block_until_ready(out)
        with self._stage("verify", "fetch"):
            out = np.asarray(out)
            results = [bool(out[i]) for i in range(len(batch))]
        return results, rb * fb

    def _count_verify(self, missions: int, calls: int, evals: int,
                      late: bool = False) -> None:
        with self._lock:
            st = self.stats.classes["verify"]
            st.missions += missions
            st.device_calls += calls
            st.prf_evals += evals
            st.late_proofs += late

    def _round_program(self, challenged: int, sectors: int, limbs: int,
                       bucket: int, degraded: bool, lane,
                       warm: bool = False):
        """The verify class's cached programs for rounds of one mission
        bucket, ``(fold, close)``: ops/podr2.py ``round_folds`` and
        ``round_verdicts`` with the backend's key read once as host
        words, placed on the backend's device. ``warm``: a new entry
        runs both once over zeros as it is built."""
        from ..ops import podr2

        audit = self._audit_backend(degraded, lane)

        def build():
            key_ops = podr2.key_operands(audit.key)

            def fold(rows, idx, nu, agg_words):
                with jax.default_device(audit.device):
                    return podr2.round_folds(key_ops, rows, idx, nu,
                                             agg_words)

            def close(acc, mu, sigma):
                with jax.default_device(audit.device):
                    return podr2.round_verdicts(key_ops, acc, mu, sigma)
            if warm:
                with jax.default_device(audit.device):
                    jax.block_until_ready(
                        podr2.warm_round(key_ops, challenged, bucket))
            return fold, close

        return self.programs.get(
            self._key(("verify_round", challenged, sectors, limbs, bucket),
                      degraded, lane), build)

    def _op_verify_round(self, batch, degraded=False, lane=None):
        """A verify-round batch takes its proofs late: the rows are
        laid out and the folds enqueued from the owed sets and the
        round alone; then (the stage ``proofs``) every request is told
        that its folds are out and its (mu, sigma) are taken, waiting
        up to its deadline; then the close. Proofs in hand at submit
        are there already: the same path, a wait of microseconds. A
        request whose proofs fail it leaves the batch alone
        (``_fail_members``); its missions close against zero proofs and
        their verdicts are dropped."""
        from ..ops import podr2

        aux = batch[0].aux
        sectors, limbs = batch[0].key[2:4]
        # settled before this run (a salvage or a sibling lane's run
        # of a batch that failed): no fold for them
        self._fail_members(batch, {i: r.aux["proofs"].failure()
                                   for i, r in enumerate(batch)})
        with self._stage("verify", "assemble"):
            if len(batch) == 1:
                arrays = batch[0].arrays
            else:
                arrays = {k: np.concatenate([r.arrays[k] for r in batch])
                          for k in ("ids", "sizes")}
            rows = podr2.round_rows(arrays["ids"], arrays["sizes"])
        challenged = len(aux["idx"])
        with self._stage("verify", "dispatch"):
            fold, close = self._round_program(
                challenged, sectors, limbs, rows.bucket, degraded, lane)
            acc = fold(rows, aux["idx"], aux["nu"], aux["agg_words"])
        with self._stage("verify", "proofs"):
            late = not all(r.aux["proofs"].here() for r in batch)
            for r in batch:
                r.aux["proofs"].release()
            mu = np.zeros((rows.missions, sectors), np.uint32)
            sigma = np.zeros((rows.missions, limbs), np.uint32)
            failed, kept, at = {}, [], 0
            for i, r in enumerate(batch):
                n = len(r.arrays["sizes"])
                try:
                    mu[at:at + n], sigma[at:at + n] = r.aux["proofs"].take(
                        r.deadline, (n, sectors), (n, limbs))
                    kept.append(slice(at, at + n))
                except Exception as e:  # noqa: BLE001 — that request's alone
                    failed[i] = e
                at += n
        self._fail_members(batch, failed)
        with self._stage("verify", "dispatch"):
            out = close(acc, mu, sigma)
            self._count_verify(rows.missions, len(rows.steps) + 1,
                               rows.rows_issued * challenged, late)
        with self._stage("verify", "wait"):
            jax.block_until_ready(out)
        self._stages_once()             # dispatch ran twice: one batch
        with self._stage("verify", "fetch"):
            out = np.asarray(out)
            results = [out[at] for at in kept]
        return results, rows.rows_issued

    def _op_prove(self, batch, degraded=False, lane=None):
        """A prove batch, ``podr2.chunk_plan`` fragments a device step:
        a step's challenged blocks and tag rows are gathered where the
        miners hold them into one of two reused host buffers (only what
        the round reads travels; nothing is zero-filled a round: pad
        rows carry r = 0, exact modular zeros, whatever lies under
        them), put with the step's call and folded into the running
        (mu, sigma) on the device; the next step's gather runs
        meanwhile (``podr2.fold_chunks``). Up to PROVE_CHUNK fragments
        that is one step of the bucket's program, as ever; past it the
        chunk is the only shape, so the programs do not grow with F."""
        from ..ops import podr2

        aux = batch[0].aux
        idx, nu, sectors = aux["idx"], aux["nu"], aux["sectors"]
        fb, chunks = podr2.chunk_plan(max(r.rows for r in batch))
        rb = bucket_rows(len(batch))
        limbs = batch[0].arrays["tags"].shape[2]
        bufs = self._prove_buffers((rb, fb, len(idx), sectors, limbs),
                                   min(chunks, 2))
        gathered = 0

        def fill(j, buf):
            nonlocal gathered
            blocks_i, tags_i, rs = buf
            rs[:] = 0
            lo = j * fb
            for i, r in enumerate(batch):
                hi = min(r.rows, lo + fb)
                if hi > lo:
                    gathered += podr2.gather_challenged(
                        r.arrays["fragments"], r.arrays["tags"], lo, hi,
                        idx, blocks_i[i], tags_i[i])
                    rs[i, :hi - lo] = r.arrays["r"][lo:hi]

        first = self._stacked_program(
            batch, fb, rb, degraded, lane,
            lambda audit: (_PROVE_PROGRAM, ()))
        step = first if chunks == 1 else self._stacked_program(
            batch, fb, rb, degraded, lane,
            lambda audit: (_PROVE_STEP, ()), "step")

        def call(acc, buf):
            if acc is None:
                return first(*buf, nu)
            return step(*acc, *buf, nu)

        try:
            mu, sigma = podr2.fold_chunks(
                chunks, bufs, fill, call,
                functools.partial(self._stage, "prove"))
            with self._stage("prove", "wait"):
                # as in _op_verify_agg: the fetch would block anyway
                jax.block_until_ready((mu, sigma))
        except BaseException:
            # a step may still read them: the next batch takes new ones
            self._running.prove_bufs = None
            raise
        sink = self._stages_once()
        with self._lock:
            st = self.stats.classes["prove"]
            st.chunks += chunks
            st.device_calls += chunks
            st.gathered_bytes += gathered
            st.gather_seconds += sink.get("engine.prove.assemble",
                                          (0, 0.0))[1]
        with self._stage("prove", "fetch"):
            mu = np.asarray(mu)
            sigma = np.asarray(sigma)
            results = [(mu[i], sigma[i]) for i in range(len(batch))]
        return results, rb * fb * chunks

    def _prove_buffers(self, shape: tuple, count: int) -> list:
        """This thread's host buffers of a prove step (the batcher's, a
        lane worker's): kept from batch to batch while the step's shape
        stays, so a round touches no fresh pages. Free to fill again:
        a batch ends with its last step waited for."""
        from ..ops import podr2

        held = getattr(self._running, "prove_bufs", None)
        if held is None or held[0] != shape:
            held = (shape, [])
        if len(held[1]) < count:
            held[1].extend(podr2.prove_buffers(shape,
                                               count - len(held[1])))
        self._running.prove_bufs = held
        return held[1][:count]

    def _stages_once(self) -> dict:
        """The sink of the batch this thread is running, each stage
        counted once: a batch that entered stages once a device step
        still counts them once a batch (stats.py: a stage's ``n`` is
        batches); the seconds are every entry's."""
        sink = getattr(self._running, "batch", (None,))[0] or {}
        for acc in sink.values():
            acc[0] = 1
        return sink

    def _long_waits(self, cls: str, sink: dict, long: list, batch=(),
                    bucket: int = 0, lane=None) -> None:
        """Keep a batch's waits that ran over ``LONG_WAIT_S`` (stats.py
        LongWaits; ``stats_snapshot()["long_waits"]``, the flight
        journal) with the batch they were of. A stage's start is read
        back from the sink: a batch's stages run in STAGES' order and
        the last ended a few microseconds ago."""
        end = time.perf_counter()
        for stage, seconds in long:
            later = sum(sink.get(f"engine.{cls}.{name}", (0, 0.0))[1]
                        for name in STAGES[STAGES.index(stage) + 1:])
            self.stats.long_waits.observe(
                f"engine.{cls}.{stage}", end - later - seconds, seconds,
                lambda: {"cls": cls, "bucket": bucket,
                         "rows": sum(r.rows for r in batch),
                         "lane": None if lane is None else lane.index})


    def _fail_members(self, batch: list[_Request], failed: dict) -> None:
        """Fail these members (index -> exception) of the batch this
        thread runs, and no other: each is rejected, counted and taken
        OUT of ``batch``, so the runner's results and _serve_batch's
        accounting are the rest's. Where none would be left the last
        stays and its failure is raised: the batch's own failure. An
        index whose exception is None has not failed."""
        failed = {i: e for i, e in failed.items() if e is not None}
        if not failed:
            return
        gone = sorted(failed, reverse=True)
        last = gone.pop(0) if len(failed) == len(batch) else None
        now = time.monotonic()
        with self._lock:
            self.stats.classes[batch[0].cls].failed += len(gone)
        for i in gone:
            r = batch.pop(i)
            r.future._reject(failed[i])
            r.span.set(outcome="error", error=repr(failed[i])).finish()
            self._observe_failure(r, now)
        if last is not None:
            raise failed[last]

def make_engine(k: int | None = None, m: int | None = None, *,
                rs_backend: str = "cpu", podr2_key=None,
                audit_backend: str = "cpu",
                policy: AdmissionPolicy | None = None,
                resilience=None, tracer=None, slo=None, adaptive=None,
                admission=None, pool=None,
                profile=None) -> SubmissionEngine:
    """Build an engine over the two trait gates.

    k/m select the ErasureCodec geometry (None = no codec: the engine
    serves only audit classes); podr2_key enables the audit classes
    (None = no AuditBackend: tag/prove/verify submits raise).
    resilience: optional cess_tpu.resilience.ResilienceConfig — retry
    on saturation, batch-failure isolation, and health-gated CPU
    degradation (see the module doc's Resilience paragraph).
    tracer: optional cess_tpu.obs.Tracer — request-scoped spans for
    every submit (queue-wait -> batch -> device dispatch -> resolve);
    without one the engine still honors a process-armed tracer
    (obs.trace.arm), and with neither every hook is a no-op.
    slo: optional cess_tpu.obs.SloBoard — burn-rate SLO monitors +
    per-tenant accounting + weighted-fair dequeue (module doc's SLO
    paragraph). adaptive: an AdaptiveBatchPolicy (serve/adaptive.py),
    or True to build one seeded from ``policy`` and steered by the
    board's targets. admission: an AdmissionController; auto-built
    when both ``slo`` and ``adaptive`` are present (pass your own to
    customize the protect/shed classes, or ``False`` to disable).
    pool: the multi-chip serving plane (serve/pool.py) — a built
    DevicePool, or True (all local devices) / a device count N (the
    ``--pool[=N]`` CLI form). None/0/False = the single-device
    dispatch path, unchanged.
    profile: optional cess_tpu.obs.profile.ProfilePlane — continuous
    performance profiling: per-(class, bucket, device) stage
    breakdowns, the unified pad ledger, program-cache compile events
    and (when built with a bench baseline) the perf-regression
    watchdog. None = the account path pays one attribute load + None
    check per batch.
    """
    codec = None
    if k is not None:
        from ..ops import rs

        codec = rs.make_codec(k, m, backend=rs_backend)
    audit = None
    if podr2_key is not None:
        from ..ops import audit_backend as ab

        audit = ab.make_audit_backend(podr2_key, audit_backend)
    if adaptive is True:
        if slo is None:
            # the node.cli refusal, enforced at the API layer too: a
            # tuner with no board has no targets to steer toward and
            # would silently never adjust a knob (pass an explicit
            # AdaptiveBatchPolicy(targets=...) for a board-less tuner)
            raise ValueError("adaptive=True needs an slo= board "
                             "(its targets steer the knob tuner)")
        from .adaptive import AdaptiveBatchPolicy

        adaptive = AdaptiveBatchPolicy(policy, board=slo)
    if admission is None and slo is not None and adaptive is not None:
        from .adaptive import AdmissionController

        admission = AdmissionController(slo, adaptive)
    if pool and not hasattr(pool, "bind"):
        # True = every local device; an int = the first N of them
        from .pool import DevicePool

        pool = DevicePool(n=None if pool is True else int(pool))
    return SubmissionEngine(codec, audit, policy, resilience=resilience,
                            tracer=tracer, slo=slo, adaptive=adaptive,
                            admission=admission or None,
                            pool=pool or None, profile=profile)


def _round_operands(fragment_ids, sizes, agg_words, mu, sigma, proofs,
                    sectors: int, limbs: int) -> tuple:
    """``submit_verify_round``'s arguments as the request holds them:
    (ids [T, 2], sizes [M], the two aggregation key words, the
    request's ``LateProofs``). Proofs given as arrays are checked here
    and put; a ``LateProofs`` is checked when the batch takes it."""
    ids = np.ascontiguousarray(np.asarray(fragment_ids,
                                          dtype=np.uint32)).reshape(-1, 2)
    sizes = np.ascontiguousarray(np.asarray(sizes, dtype=np.int64))
    words = np.ascontiguousarray(np.asarray(agg_words, dtype=np.uint32))
    if sizes.ndim != 1 or words.shape != (2,) \
            or (len(sizes) and sizes.min() < 1) \
            or int(sizes.sum()) != len(ids):
        raise ValueError("expected ids [T, 2], sizes [M] >= 1 summing to "
                         "T and two aggregation key words")
    if (proofs is None) == (mu is None) or (mu is None) != (sigma is None):
        raise ValueError("expected mu [M, sectors] and sigma [M, limbs], "
                         "or proofs=LateProofs() to put them later")
    if proofs is None:
        proofs = LateProofs()
        proofs.put(*proofs.shaped(mu, sigma, (len(sizes), sectors),
                                  (len(sizes), limbs)))
    return ids, sizes, words, proofs


class LateProofs:
    """The proofs of one ``submit_verify_round`` request, handed in
    after the submit: a one-shot slot between the caller's thread and
    the batch's.

    The caller: ``folds_out()`` blocks until the engine has enqueued
    the request's folds (the device is at work and the batcher wants
    the interpreter no more: a thread that decodes in Python while the
    batcher dispatches makes it wait a switch interval a call), then
    ``put(mu, sigma)`` — mu [M, sectors], sigma [M, limbs] uint32 — or
    ``fail(exc)``. The batch: ``take`` waits for them up to the
    request's deadline. The first settlement stands: proofs that are
    mis-shaped, failed, or late for the deadline fail the request for
    good, whatever comes after."""

    __slots__ = ("_out", "_in", "_mu", "_value", "future")

    POLL_S = 0.05       # folds_out's look at the future, a failure's only

    def __init__(self):
        self._out = threading.Event()   # the engine: the folds are out
        self._in = threading.Event()    # settled: proofs or a failure
        self._mu = threading.Lock()
        self._value: Any = None         # (mu, sigma) | BaseException
        self.future: EngineFuture | None = None     # set by the submit

    # -- the caller's side -------------------------------------------------
    def folds_out(self, timeout: float | None = None) -> bool:
        """Block until the request's folds are enqueued: True. False
        when the request is over without them (rejected, expired,
        closed) or ``timeout`` elapses."""
        end = None if timeout is None else time.monotonic() + timeout
        while not self._out.wait(self.POLL_S):
            if self.future is not None and self.future.done() \
                    or end is not None and time.monotonic() >= end:
                return self._out.is_set()
        return True

    def put(self, mu, sigma) -> None:
        self._settle((mu, sigma))

    def fail(self, exc: BaseException) -> None:
        self._settle(exc)

    def _settle(self, value) -> None:
        with self._mu:
            if not self._in.is_set():
                self._value = value
                self._in.set()

    # -- the batch's side --------------------------------------------------
    def here(self) -> bool:
        return self._in.is_set()

    def failure(self) -> BaseException | None:
        value = self._value
        return value if isinstance(value, BaseException) else None

    def release(self) -> None:
        self._out.set()

    @staticmethod
    def shaped(mu, sigma, mu_shape: tuple, sigma_shape: tuple) -> tuple:
        mu = np.asarray(mu, dtype=np.uint32)
        sigma = np.asarray(sigma, dtype=np.uint32)
        if mu.shape != mu_shape or sigma.shape != sigma_shape:
            raise ValueError(f"expected mu {mu_shape} and sigma "
                             f"{sigma_shape}, got {mu.shape} and "
                             f"{sigma.shape}")
        return mu, sigma

    def take(self, deadline: float | None, mu_shape: tuple,
             sigma_shape: tuple) -> tuple:
        """The proofs, waited for until ``deadline`` (time.monotonic();
        None: without limit), as uint32 arrays of these shapes. Raises
        the request's failure, which then stands."""
        if not self._in.wait(None if deadline is None else
                             max(deadline - time.monotonic(), 0.0)):
            self.fail(EngineTimeout("no proofs within the request's "
                                    "timeout"))
        if self.failure() is None:
            try:
                return self.shaped(*self._value, mu_shape, sigma_shape)
            except (TypeError, ValueError) as e:
                with self._mu:
                    self._value = e
        raise self.failure()
