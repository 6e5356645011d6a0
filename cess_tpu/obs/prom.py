"""Prometheus histogram primitives for the /metrics exposition.

The engine and stream stats export latency *percentiles* as gauges —
fine for a glance, wrong for aggregation (you cannot average p99s
across nodes or scrape intervals). A real Prometheus histogram is the
mergeable form: fixed bucket bounds, cumulative ``_bucket{le=...}``
counts, ``_sum`` and ``_count`` — the server derives any quantile over
any window. This module provides the counter (:class:`Histogram`) and
the text-exposition renderer (:func:`render_histogram`);
``node/metrics.py`` emits the families beside the existing gauges with
correct ``# TYPE ... histogram`` declarations.

Thread note: observations come from the engine batcher and stream
driver threads while the RPC thread renders — every access goes
through the histogram's own lock, and rendering works from one
consistent snapshot so the cumulative-bucket invariant (nondecreasing,
``+Inf`` == ``_count``) holds in every scrape (tests/test_metrics.py).
"""
from __future__ import annotations

import bisect
import math
import threading

# engine/stream latency bounds (seconds): sub-ms device dispatches up
# through multi-second degraded/backpressure tails
LATENCY_BUCKETS_S = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                     0.25, 0.5, 1.0, 2.5, 5.0, 10.0)


class Histogram:
    """Fixed-bound histogram: ``observe`` is O(log buckets), snapshots
    are consistent (taken under the lock), and same-bound histograms
    merge (the engine sums per-driver stream histograms into one
    exposition family).

    Each bucket keeps the SUM of what it observed beside the count
    (:meth:`buckets`), so two readings of one histogram difference
    exactly, bucket by bucket: a percentile of the interval between
    them, and "seconds spent in observations above x" for any bound x
    (the stage ladders of serve/stats.py are read that way; a sliding
    sample window cannot be differenced)."""

    __slots__ = ("bounds", "_counts", "_sums", "_sum", "_count", "_mu")

    def __init__(self, bounds=LATENCY_BUCKETS_S):
        # a ladder shared by many histograms stays ONE tuple
        bs = bounds if type(bounds) is tuple \
            and all(type(b) is float for b in bounds) \
            else tuple(float(b) for b in bounds)
        if not bs or any(b2 <= b1 for b1, b2 in zip(bs, bs[1:])) \
                or not all(math.isfinite(b) for b in bs):
            raise ValueError(f"bucket bounds must be finite and "
                             f"strictly increasing, got {bounds!r}")
        self.bounds = bs
        self._counts = [0] * (len(bs) + 1)   # last = above every bound
        self._sums = [0.0] * (len(bs) + 1)
        self._sum = 0.0
        self._count = 0
        self._mu = threading.Lock()

    def observe(self, value: float) -> None:
        value = float(value)
        # Prometheus le is inclusive: first bound >= value
        i = bisect.bisect_left(self.bounds, value)
        with self._mu:
            self._counts[i] += 1
            self._sums[i] += value
            self._sum += value
            self._count += 1

    def merge(self, other: "Histogram") -> "Histogram":
        """Add ``other``'s observations into this histogram (bounds
        must match exactly)."""
        if other.bounds != self.bounds:
            raise ValueError("cannot merge histograms with different "
                             f"bounds: {self.bounds} vs {other.bounds}")
        with other._mu:
            counts, sums = list(other._counts), list(other._sums)
            total_sum, total_n = other._sum, other._count
        with self._mu:
            for i, n in enumerate(counts):
                self._counts[i] += n
                self._sums[i] += sums[i]
            self._sum += total_sum
            self._count += total_n
        return self

    @property
    def count(self) -> int:
        with self._mu:
            return self._count

    @property
    def sum(self) -> float:
        with self._mu:
            return self._sum

    @classmethod
    def from_cumulative(cls, buckets, total_sum: float) -> "Histogram":
        """Rebuild a histogram from its wire form — the CUMULATIVE
        ``[(le_bound, count_le)...]`` list :meth:`snapshot` produces
        (and a federator parses back out of ``_bucket{le=...}``
        samples). The last entry must be the ``+Inf`` bucket; counts
        must be nondecreasing. Inverse of :meth:`snapshot`, so
        cross-node federation can reuse :meth:`merge`. The wire form
        carries no per-bucket sums: :meth:`buckets` of the rebuilt
        histogram reads 0.0 seconds a bucket."""
        pairs = [(float(b), int(n)) for b, n in buckets]
        if len(pairs) < 2 or not math.isinf(pairs[-1][0]):
            raise ValueError("cumulative buckets must end with +Inf")
        if any(n2 < n1 for (_, n1), (_, n2) in zip(pairs, pairs[1:])):
            raise ValueError("cumulative bucket counts must be "
                             "nondecreasing")
        h = cls(tuple(b for b, _ in pairs[:-1]))
        prev = 0
        with h._mu:
            for i, (_, acc) in enumerate(pairs):
                h._counts[i] = acc - prev
                prev = acc
            h._count = pairs[-1][1]
            h._sum = float(total_sum)
        return h

    def quantile(self, q: float) -> float:
        """Estimate the q-quantile (0 <= q <= 1) by linear
        interpolation inside the owning bucket — the same estimate
        ``histogram_quantile`` computes server-side, so a FleetBoard
        reading a federated histogram agrees with the dashboards.
        Observations above the last finite bound clamp to that bound
        (the +Inf bucket has no width to interpolate over); an empty
        histogram reports 0.0."""
        if not 0.0 <= q <= 1.0:
            raise ValueError(f"quantile must be in [0, 1], got {q!r}")
        snap = self.snapshot()
        total = snap["count"]
        if total == 0:
            return 0.0
        target = q * total
        lo, prev_acc = 0.0, 0
        for bound, acc in snap["buckets"]:
            if acc >= target and acc > prev_acc:
                if math.isinf(bound):
                    return lo
                frac = (target - prev_acc) / (acc - prev_acc)
                return lo + (bound - lo) * frac
            if not math.isinf(bound):
                lo = bound
            prev_acc = acc
        return lo

    def buckets(self) -> list:
        """The non-empty buckets, ``[[le, count, sum], ...]`` in ladder
        order, NOT cumulative; ``le`` is the bucket's inclusive upper
        bound, ``None`` for the one above every bound. JSON-shaped, and
        the form two readings are differenced in: counts and sums of
        equal ``le`` subtract."""
        with self._mu:
            counts, sums = list(self._counts), list(self._sums)
        les = self.bounds + (None,)
        return [[les[i], n, sums[i]] for i, n in enumerate(counts) if n]

    def snapshot(self) -> dict:
        """One consistent view: ``buckets`` is the CUMULATIVE
        ``[(le_bound, count_le)...]`` list ending with ``(inf, count)``
        — exactly the wire semantics of ``_bucket{le=...}``."""
        with self._mu:
            counts = list(self._counts)
            total_sum, total_n = self._sum, self._count
        buckets, acc = [], 0
        for bound, n in zip(self.bounds, counts):
            acc += n
            buckets.append((bound, acc))
        buckets.append((math.inf, acc + counts[-1]))
        return {"buckets": buckets, "sum": total_sum, "count": total_n}


def counter_delta(prev: float, cur: float) -> float:
    """The increment between two scrapes of a MONOTONIC counter,
    clamped for restarts: a counter can only move backwards because
    the process restarted and began again at zero, so the true
    increment since the previous scrape is at least ``cur`` (what
    accumulated after the restart) — never the negative difference a
    naive ``cur - prev`` would report. This is the federation-side
    half of Prometheus's ``rate()`` reset handling."""
    prev, cur = float(prev), float(cur)
    if cur >= prev:
        return cur - prev
    return cur


def format_le(bound: float) -> str:
    """Prometheus ``le`` label value: ``+Inf`` for the overflow
    bucket, shortest exact decimal otherwise."""
    if math.isinf(bound):
        return "+Inf"
    return f"{bound:g}"


def escape_label(value: str) -> str:
    """Exposition-format label-value escaping (format 0.0.4): inside
    the double quotes, backslash, double-quote and newline must be
    escaped — tenant names are caller-supplied strings, and an
    unescaped ``"`` would truncate the label and corrupt every sample
    after it on the scrape."""
    return (str(value).replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


def format_labels(labels: dict | None) -> str:
    """``{k="v",...}`` with escaped values (sorted: deterministic
    exposition), or ``""`` for an unlabeled sample."""
    if not labels:
        return ""
    inner = ",".join(f'{k}="{escape_label(v)}"'
                     for k, v in sorted(labels.items()))
    return "{" + inner + "}"


def render_histogram(name: str, hist: Histogram,
                     labels: dict | None = None,
                     type_line: bool = True) -> list[str]:
    """Text-exposition lines for one histogram family: the TYPE
    declaration, cumulative buckets, ``_sum`` and ``_count``.

    labels: extra labels on every sample (the per-tenant families —
    ``le`` is merged in on the bucket lines). type_line=False skips
    the ``# TYPE`` declaration: a labeled family renders one label-set
    per call, but the exposition format allows exactly ONE TYPE line
    per family, so the caller emits it for the first set only."""
    snap = hist.snapshot()
    base = dict(labels or {})
    lines = [f"# TYPE {name} histogram"] if type_line else []
    for bound, n in snap["buckets"]:
        lines.append(f"{name}_bucket"
                     f"{format_labels({**base, 'le': format_le(bound)})}"
                     f" {n}")
    tail = format_labels(base)
    lines.append(f"{name}_sum{tail} {snap['sum']}")
    lines.append(f"{name}_count{tail} {snap['count']}")
    return lines
