"""The caller's side of an engine request and the gateway's workers, as
the per-layer readers take them (PR 35).

Beside the six stages a class (``program_spans.stage_deltas``) the program
counts, per request, what the thread that called the engine paid
(``stats_snapshot()["classes"][cls]["caller"]``: ``submit``, ``handoff``)
and splits the queue stage's seconds (``["queue"]``: ``coalesce``,
``wake``), each ``{"n", "s"}`` with raw seconds (cess_tpu/serve/stats.py
CALLER, QUEUE_PARTS). In a profiler trace the same extents, the gateway's
worker jobs and the PoDR2 challenge are ``cess:`` events
(``program_spans.spans``).

``deltas(view, *classes)``      the window's difference of those accounts,
                                summed over the classes, with the six
                                stages' total beside them
``union_seconds(view, name)``   seconds covered by at least one span of
                                this name

Every function returns ``None`` where there is nothing to read: a program
from before the accounts, a cell that does not drive the class, an
untraced run for the span readers.
"""
from __future__ import annotations

import program_spans
import trace_reduce

ACCOUNTS = (("caller", "submit"), ("caller", "handoff"),
            ("queue", "coalesce"), ("queue", "wake"))


def deltas(view, *classes: str, calls=()):
    """{"completed", "batches", "batches_of", "submit", "handoff",
    "coalesce", "wake", "stages"} — requests, batches (together and by
    class) and seconds of these engine classes, after the window less
    before it (``stages``: the six stages' seconds together). ``calls``
    names the benchmark's shims around the classes' blocking calls: where
    the run kept them, the first reader of a class set prints how much of
    the calls' own seconds the program names."""
    out = dict.fromkeys(("completed", "batches", "stages"), 0)
    out.update((name, 0.0) for _, name in ACCOUNTS)
    out["batches_of"] = {}
    for cls in classes:
        try:
            a = view.counters_before["engine"]["classes"][cls]
            b = view.counters_after["engine"]["classes"][cls]
            for group, name in ACCOUNTS:
                out[name] += b[group][name]["s"] - a[group][name]["s"]
            out["stages"] += sum(acc["s"] - a["stages"][stage]["s"]
                                 for stage, acc in b["stages"].items())
        except (KeyError, TypeError):
            return None
        out["completed"] += b["completed"] - a["completed"]
        out["batches"] += b["batches"] - a["batches"]
        out["batches_of"][cls] = b["batches"] - a["batches"]
    if out["completed"] <= 0 or out["batches"] <= 0:
        return None
    said = view.__dict__.setdefault("_caller_accounts_said", set())
    if classes not in said:
        said.add(classes)
        named = out["submit"] + out["stages"] + out["handoff"]
        line = {"classes": list(classes), "completed": out["completed"],
                "batches": out["batches"],
                "ms_per_request": {k: 1e3 * out[k] / out["completed"]
                                   for k in ("submit", "coalesce", "wake",
                                             "stages", "handoff")},
                "queue_less_halves_s": sum(
                    _queue_gap(view, cls) for cls in classes)}
        called = _called_seconds(view, calls)
        if called > 0:
            # a one-request batch's submit + six stages + hand-back is
            # the blocking call's own extent
            line.update(named_s=named, called_s=called,
                        named_over_called=named / called)
        view.say(info="engine caller accounts", **line)
    return out


def _called_seconds(view, calls) -> float:
    """Seconds of the window inside the benchmark's shims of these names
    (spans.py keeps them in a traced run); 0 without them."""
    try:
        return sum(view.spans.total(c, view.ctx.window_t0) for c in calls)
    except AttributeError:
        return 0.0


def _queue_gap(view, cls: str) -> float:
    """The queue stage's seconds less its two halves', over the window:
    0 but for the rounding of the differences."""
    a = view.counters_before["engine"]["classes"][cls]
    b = view.counters_after["engine"]["classes"][cls]
    return (b["stages"]["queue"]["s"] - a["stages"]["queue"]["s"]) - sum(
        b["queue"][part]["s"] - a["queue"][part]["s"]
        for part in ("coalesce", "wake"))


def per_request_ms(view, name: str, *classes: str, calls=()):
    """One account's mean milliseconds a completed request."""
    d = deltas(view, *classes, calls=calls)
    if d is None:
        return None
    return 1e3 * d[name] / d["completed"]


def union_seconds(view, name: str):
    """Seconds of the window covered by at least one span of this name,
    across threads; None without any."""
    picked = [(a, b) for n, a, b in program_spans.spans(view) or ()
              if n == name and b > a]
    if not picked:
        return None
    return sum(b - a for a, b in trace_reduce._union(picked)) \
        * program_spans.NS
