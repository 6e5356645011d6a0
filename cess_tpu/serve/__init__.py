"""cess_tpu.serve — the device submission engine.

A dynamic micro-batching service between every off-chain client
(OssGateway encode, MinerAgent proving, TeeAgent tagging/verification)
and the ``ErasureCodec`` / ``AuditBackend`` device gates: bounded
per-class queues, a work-conserving batcher (a class drains the moment
something can run its batch, or a size budget fills) that coalesces
ragged requests into shape-bucketed device programs, explicit backpressure,
and engine counters on the node metrics surface. See engine.py for
the full design; the direct synchronous path stays the default
everywhere an engine is not explicitly configured.

stream.py adds the double-buffered host->device streaming driver for
the fused encode+tag workload (one H2D copy per batch, staging of
batch i+1 overlapped with compute of batch i, ragged tail handled).

adaptive.py closes the observability loop (ISSUE 6): per-class
batching knobs tuned from the live latency signal
(AdaptiveBatchPolicy) and SLO-gated, deadline-aware admission
(AdmissionController) over an obs.SloBoard — opt-in via
``make_engine(slo=..., adaptive=...)`` / ``node.cli --slo --adaptive``.

pool.py is the multi-chip serving plane (ISSUE 10): a DevicePool
routes the batcher's drained batches across per-device worker lanes
(deterministic least-loaded placement, per-(backend, device)
breakers, drain-to-sibling on lane failure) — opt-in via
``make_engine(pool=...)`` / ``node.cli --pool[=N]``.

remediate.py closes the control loop (ISSUE 16): a count-sequenced
RemediationPlane subscribes to the flight recorder's detector edges
and maps each through a declarative Policy table to a journaled,
replayable recovery action (pin-to-reference, lane quarantine,
on-chain offence filing, repair-mode flip) — opt-in via
``node.cli --remediate`` / ``Scenario.remediate=True``.
"""
from .adaptive import AdaptiveBatchPolicy, AdmissionController
from .engine import (EngineFuture, LateProofs, SubmissionEngine,
                     make_engine)
from .policy import (AdmissionPolicy, EngineClosed, EngineError,
                     EngineSaturated, EngineShed, EngineTimeout)
from .pool import DevicePool
from .remediate import Policy, RemediationPlane, default_policies
from .stats import EngineStats, StreamStats
from .stream import StreamingIngest

__all__ = [
    "AdaptiveBatchPolicy",
    "AdmissionController",
    "AdmissionPolicy",
    "DevicePool",
    "EngineClosed",
    "EngineError",
    "EngineFuture",
    "EngineSaturated",
    "EngineShed",
    "EngineStats",
    "EngineTimeout",
    "LateProofs",
    "Policy",
    "RemediationPlane",
    "StreamStats",
    "StreamingIngest",
    "SubmissionEngine",
    "default_policies",
    "make_engine",
]
