"""Admission control for the device submission engine.

The engine's contract with its callers is explicit about overload
(ISSUE: "no silent drops or unbounded queues"):

- every op class has a bounded queue; a submit against a full queue
  raises :class:`EngineSaturated` immediately (backpressure the caller
  can act on — retry, shed, or route to the direct path);
- every request may carry a deadline; a request still queued when its
  deadline passes is cancelled with :class:`EngineTimeout` (the audit
  flow's challenge_deadline shape: a proof delivered after the round
  closes is worthless, so the engine never spends device time on it);
- classes drain in fixed priority order — challenge verification
  preempts bulk encode, mirroring the reference's audit urgency (a
  missed verify window slashes a miner; a delayed upload just waits).
"""
from __future__ import annotations

import dataclasses


class EngineError(Exception):
    """Base class for submission-engine errors."""


class EngineSaturated(EngineError):
    """The op class's bounded queue is full: explicit backpressure.

    Callers choose the response (retry with jitter, shed load, or fall
    back to the direct synchronous path) — the engine never queues
    unboundedly and never drops silently.
    """


class EngineTimeout(EngineError):
    """The request's deadline expired before its batch ran."""


class EngineShed(EngineError):
    """The request was rejected by SLO-gated admission control
    (serve/adaptive.py): either a protected class's SLO is burning and
    this class is being shed to protect it, or the request's own
    deadline is already below the class's live p99 estimate. Distinct
    from :class:`EngineSaturated` on purpose — a saturated queue wants
    a backoff-retry, shed load wants the caller to STOP offering
    (route to the direct path, or wait for the SLO to recover)."""


class EngineClosed(EngineError):
    """Submit against an engine that has been shut down."""


# Drain order: lower drains first. Verification answers a live audit
# round (missing the window slashes a miner); proving races the same
# challenge_deadline; tagging gates uploads becoming chargeable;
# repair restores redundancy; bulk encode has no deadline at all.
CLASS_PRIORITY: dict[str, int] = {
    "verify": 0,
    "prove": 1,
    "tag": 2,
    "repair": 3,
    "encode": 4,
}

CLASSES = tuple(sorted(CLASS_PRIORITY, key=CLASS_PRIORITY.__getitem__))


@dataclasses.dataclass(frozen=True)
class AdmissionPolicy:
    """Per-class bounds + batching trigger knobs.

    queue_cap:          max queued requests per class (EngineSaturated
                        beyond it).
    max_batch_requests: size trigger — a class with this many queued
                        coalescible requests drains immediately.
    max_batch_rows:     row budget per device batch (padding bucket
                        ceiling; requests beyond it wait for the next
                        batch).
    max_delay:          the coalescing window. ``None`` (the default):
                        no fixed window — a non-empty class drains
                        the moment something can run its batch (the
                        batcher's own thread on the inline path, a
                        lane with nothing placed on it on the pool
                        path) and gathers companions only while
                        every executor is busy, so an idle engine
                        never makes a lone caller wait. A float,
                        seconds: the oldest queued request waits up
                        to it for companions whatever the device is
                        doing, then its batch launches (an operator
                        or a test that wants requests held on an
                        idle engine passes one).
    default_timeout:    deadline applied to requests submitted without
                        one (None = no deadline).
    """

    queue_cap: int = 256
    max_batch_requests: int = 32
    max_batch_rows: int = 512
    max_delay: float | None = None
    default_timeout: float | None = None

    def __post_init__(self):
        if self.queue_cap < 1 or self.max_batch_requests < 1 \
                or self.max_batch_rows < 1 \
                or (self.max_delay is not None and self.max_delay < 0):
            raise ValueError("invalid admission policy bounds")
