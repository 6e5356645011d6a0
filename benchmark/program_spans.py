"""The program's own stage spans and stage counters, as the per-layer
readers take them.

Since PR 25 the program writes every stage of an engine batch, a gateway
upload and a streamed batch into any live profiler trace as a ``cess:<name>``
host event (cess_tpu/obs/trace.py ``stage``), on the same clock as the
device's ``XLA Ops`` line, and counts the engine's stages per class in
``stats_snapshot()["classes"][cls]["stages"]`` (``{stage: {"n", "s"}}``,
raw seconds). This file reads both:

``spans(view)``            the run's ``cess:`` events inside the traced
                           window, ``(name, start_ns, end_ns)`` without the
                           prefix; loaded once from ``.bench_trace/<cell>/``
                           and kept on ``view``
``total(view, name)``      summed seconds and count of one span name
``inside(view, inner, outer)``  the same for ``inner`` spans that lie
                           inside a kept ``outer`` span; ``span_share`` is
                           their ratio in percent
``device_inside(view, names)``  the device's busy seconds and event count
                           inside the union of the named spans, and that
                           union's seconds
``idle_by_stage(view)``    the device's idle gaps laid to the innermost
                           ``cess:`` span the program was in, on whichever
                           thread (the span that started last)
``stage_deltas(view, cls)``  the window's difference of one class's stage
                           counters

Every function returns ``None`` where there is nothing to read: an
untraced run, a program from before PR 25 (no ``cess:`` events, no
``stages`` key), a cell that does not drive the engine.

A span counts when it STARTS inside the window (the extent of the
benchmark's own ``bench:`` spans) and is clipped to it: the batcher
thread leaves its last ``resolve`` a few microseconds after the client's
last ``bench:`` span ends.
"""
from __future__ import annotations

import bisect
import os

import trace_reduce

PREFIX = "cess:"
NS = trace_reduce.NS
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUTSIDE = "(outside the program's stages)"
_KEY = "_program_spans"


def from_events(events: list[dict], window_ns) -> list[tuple]:
    """``cess:`` host events that start inside the window, clipped to it,
    sorted by start."""
    w0, w1 = window_ns
    out = []
    for e in events:
        if e["name"].startswith(PREFIX) and w0 <= e["start_ns"] < w1:
            out.append((e["name"][len(PREFIX):], e["start_ns"],
                        min(e["start_ns"] + e["dur_ns"], w1)))
    return sorted(out, key=lambda s: (s[1], -s[2]))


def spans(view):
    """The window's program spans, or None (no trace, or none in it).
    The first call loads the trace and prints the idle line."""
    if hasattr(view, _KEY):
        return getattr(view, _KEY)
    found = None
    if view.trace is not None:
        path = trace_reduce.find_xplane(
            os.path.join(ROOT, ".bench_trace", view.ctx.cell))
        if path is not None:
            found = from_events(trace_reduce.load(path),
                                view.trace["window_ns"]) or None
    setattr(view, _KEY, found)
    if found is not None:
        gaps = idle_by_stage(view)
        view.say(info="idle by program stage",
                 idle_gaps=[[k, v] for k, v in gaps[:12]],
                 spans=len(found))
    return found


def total(view, name: str):
    """(seconds, count) of the spans of this name; None without any."""
    picked = [b - a for n, a, b in spans(view) or () if n == name]
    if not picked:
        return None
    return sum(picked) * NS, len(picked)


def inside(view, inner: str, outer: str):
    """(seconds, count) of ``inner`` spans that lie inside an ``outer``
    span of the window, and (seconds, count) of those ``outer`` spans;
    None without an ``outer``."""
    every = spans(view) or ()
    outers = [(a, b) for n, a, b in every if n == outer]
    if not outers:
        return None
    picked = [b - a for n, a, b in every if n == inner
              and any(oa <= a and b <= ob for oa, ob in outers)]
    return ((sum(picked) * NS, len(picked)),
            (sum(b - a for a, b in outers) * NS, len(outers)))


def span_share(view, inner: str, outer: str):
    """100 x the ``inner`` spans' seconds over the ``outer`` spans' that
    hold them."""
    got = inside(view, inner, outer)
    if got is None or got[1][0] <= 0:
        return None
    (part_s, n), (whole_s, outers) = got
    view.say(info="program spans", inner=inner, count=n, seconds=part_s,
             outer=outer, outer_count=outers, outer_seconds=whole_s)
    return 100.0 * part_s / whole_s


def _clip(intervals, cover):
    """The parts of sorted disjoint ``intervals`` inside sorted disjoint
    ``cover``."""
    out, j = [], 0
    for a, b in intervals:
        while j < len(cover) and cover[j][1] <= a:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < b:
            lo, hi = max(a, cover[k][0]), min(b, cover[k][1])
            if hi > lo:
                out.append((lo, hi))
            k += 1
    return out


def _device_lines(view) -> list:
    """Per chip, the window's device events (trace_reduce kept only those
    wholly inside the window) and the union of their intervals."""
    lines: dict[str, list] = {}
    for e in view.trace["events"]:
        lines.setdefault(e["plane"], []).append(e)
    return [(evs, trace_reduce._union(
        [(e["start_ns"], e["start_ns"] + e["dur_ns"])
         for e in evs if e["dur_ns"] > 0])) for evs in lines.values()]


def device_inside(view, names):
    """{"busy_s", "events", "span_s", "spans"}: the device's busy seconds
    (union of its operations' intervals) and its operations (counted where
    they start) inside the union of the spans of these names, per chip;
    None without such spans."""
    picked = [(a, b) for n, a, b in spans(view) or () if n in names]
    if not picked:
        return None
    cover = trace_reduce._union(picked)
    begins = [a for a, _ in cover]
    busy_ns, events = 0.0, 0
    lines = _device_lines(view)
    for evs, merged in lines:
        busy_ns += sum(b - a for a, b in _clip(merged, cover))
        for e in evs:
            i = bisect.bisect_right(begins, e["start_ns"]) - 1
            events += i >= 0 and e["start_ns"] < cover[i][1]
    n = max(len(lines), 1)
    return {"busy_s": busy_ns * NS / n, "events": events / n,
            "span_s": sum(b - a for a, b in cover) * NS,
            "spans": len(picked)}


def idle_by_stage(view) -> list:
    """[[span name, idle seconds], ...], largest first: every idle gap of
    the device inside the window laid to the innermost ``cess:`` span the
    program was in then, across threads (of the spans open at an instant,
    the one that started last); what no span covers goes to OUTSIDE."""
    by_name: dict[str, list] = {}
    for a, b, name in trace_reduce._self_segments(spans(view) or []):
        by_name.setdefault(name, []).append((a, b))
    w0, w1 = view.trace["window_ns"]
    gaps: dict[str, float] = {}
    lines = _device_lines(view)
    for _, merged in lines:
        edges = [w0] + [t for ab in merged for t in ab] + [w1]
        idle = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        covered = 0.0
        for name, parts in by_name.items():
            part = sum(b - a for a, b in _clip(idle, parts))
            if part > 0:
                gaps[name] = gaps.get(name, 0.0) + part
                covered += part
        rest = sum(b - a for a, b in idle) - covered
        if rest > 0:
            gaps[OUTSIDE] = gaps.get(OUTSIDE, 0.0) + rest
    n = max(len(lines), 1)
    return sorted(([k, v * NS / n] for k, v in gaps.items()),
                  key=lambda kv: -kv[1])


# -- stage counters ----------------------------------------------------------
def stage_deltas(view, *classes: str):
    """{"batches", "completed", "stages": {stage: seconds}} summed over
    these engine classes, after the window less before it; None where the
    program has no stage counters or the classes ran no batch in the
    window."""
    out = {"batches": 0, "completed": 0, "stages": {}}
    for cls in classes:
        try:
            a = view.counters_before["engine"]["classes"][cls]
            b = view.counters_after["engine"]["classes"][cls]
            a_stages, b_stages = a["stages"], b["stages"]
        except (KeyError, TypeError):
            return None
        out["batches"] += b["batches"] - a["batches"]
        out["completed"] += b["completed"] - a["completed"]
        for stage, acc in b_stages.items():
            out["stages"][stage] = out["stages"].get(stage, 0.0) \
                + acc["s"] - a_stages[stage]["s"]
    if out["batches"] <= 0 or out["completed"] <= 0:
        return None
    return out


def stage_share(view, stage: str, *classes: str):
    """100 x one stage's seconds over all stages' seconds of these
    classes in the window."""
    d = stage_deltas(view, *classes)
    if d is None:
        return None
    whole = sum(d["stages"].values())
    if whole <= 0:
        return None
    said = view.__dict__.setdefault("_program_stages_said", set())
    if classes not in said:            # one line for all of its shares
        said.add(classes)
        view.say(info="engine stages", classes=list(classes),
                 batches=d["batches"],
                 ms_per_batch={k: 1e3 * v / d["batches"]
                               for k, v in d["stages"].items()})
    return 100.0 * d["stages"][stage] / whole


def main(argv=None) -> int:
    """``python3 benchmark/program_spans.py <trace dir or file>``: the
    program's spans in a trace (count and seconds by name) and the device's
    idle gaps by program stage, for a cell no reader of which prints them."""
    import argparse
    import json
    import types

    ap = argparse.ArgumentParser(description=main.__doc__)
    ap.add_argument("path")
    ap.add_argument("--chips", type=int, default=1)
    args = ap.parse_args(argv)
    path = args.path if os.path.isfile(args.path) \
        else trace_reduce.find_xplane(args.path)
    events = trace_reduce.load(path)
    summary = trace_reduce.reduce(events, args.chips, allow_host=True)
    if summary is None:
        print(json.dumps({"error": "no device events in the trace"}))
        return 1
    view = types.SimpleNamespace(trace=summary)
    setattr(view, _KEY, from_events(events, summary["window_ns"]))
    by_name: dict[str, list] = {}
    for name, a, b in spans(view):
        acc = by_name.setdefault(name, [0, 0.0])
        acc[0] += 1
        acc[1] += (b - a) * NS
    print(json.dumps({"info": "program spans", "window_s":
                      summary["window_s"], "busy_s": summary["busy_s"],
                      "by_name": by_name}))
    print(json.dumps({"info": "idle by program stage",
                      "idle_gaps": idle_by_stage(view)}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
