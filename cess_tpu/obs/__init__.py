"""cess_tpu.obs — request-scoped tracing + histogram observability +
SLO monitors + the flight-recorder retention layer.

Five modules, one contract (zero-cost when off, deterministic when on):

- trace.py    Tracer/Span core: counter-based span ids, contextvars
              current-span propagation, a bounded ring of finished
              spans, Chrome trace-event export (Perfetto-loadable), and
              the (trace_id, span_id) envelope contract that stitches a
              challenge -> prove -> verify round into ONE distributed
              trace across nodes. With no tracer armed every hook
              returns the NOOP_SPAN singleton (tier-1 pins the
              identity).
- prom.py     real Prometheus histograms (cumulative _bucket{le=...} /
              _sum / _count) for the engine and stream latencies,
              rendered beside the existing gauges by node/metrics.py —
              plus exposition label escaping for the labeled families.
- slo.py      the consumption layer: declarative SloTarget objectives
              evaluated with observation-count multi-window burn-rate
              detection, per-tenant x per-class accounting, and the
              transition listeners serve/adaptive.py's admission
              controller acts on. Gauges ride /metrics as cess_slo_* /
              cess_tenant_*, snapshots serve the cess_sloStatus RPC.
- flight.py   the retention layer: tail-sampled trace pinning (anomaly
              + seeded-baseline, exempt from ring eviction, bounded
              with anomaly-first retention) and the count-sequenced
              black-box journal the subsystems note into.
- incident.py IncidentReporter: turns notable journal entries (SLO
              ok->burning, breaker trip/hold, shed storms, sim
              invariant violations, thread escapes) into rate-limited,
              deduplicated, self-contained postmortem bundles with a
              deterministic replay witness.

Wire-up: ``node.cli --trace[=PATH] --slo[=TARGETS] --flight[=DIR]``,
``serve.make_engine(tracer=..., slo=...)``, and the
``cess_traceDump`` / ``cess_sloStatus`` / ``cess_incidentDump`` RPCs.
"""
from .prom import (LATENCY_BUCKETS_S, Histogram, escape_label,
                   format_labels, format_le, render_histogram)
from .slo import (DEFAULT_TARGETS, SloBoard, SloTarget, parse_targets)
from .trace import (NOOP_SPAN, Span, Tracer, arm, armed, armed_tracer,
                    context, current_span, disarm, event, span, stage)
# flight before incident: incident.py imports from the flight/trace
# layer it listens on
from .flight import FlightRecorder
from .incident import IncidentReporter

__all__ = [
    "DEFAULT_TARGETS",
    "FlightRecorder",
    "Histogram",
    "IncidentReporter",
    "LATENCY_BUCKETS_S",
    "NOOP_SPAN",
    "SloBoard",
    "SloTarget",
    "Span",
    "Tracer",
    "arm",
    "armed",
    "armed_tracer",
    "context",
    "current_span",
    "disarm",
    "escape_label",
    "event",
    "format_labels",
    "format_le",
    "parse_targets",
    "render_histogram",
    "span",
    "stage",
]
