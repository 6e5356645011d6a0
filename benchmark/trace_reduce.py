"""From a profiler trace (.xplane.pb) to busy and idle time of the
device, device time by operation, and the idle gaps laid to what the
host was doing.

Two steps, so that the arithmetic is checked on a small recorded trace
(tests/data) without a chip:

``load(path)``      .xplane.pb (or .textproto) -> plain events
``reduce(events)``  events -> the summary run.py reports

What counts as the device: the ``XLA Ops`` line of each ``/device:TPU:<n>``
plane (one event per operation that ran there). Busy is the union of
those intervals inside the traced window (an operation that straddles
an end of it is left out); the window is the extent of the benchmark's
own ``bench:`` host spans in the trace, which tile the closed loop.
Device time by operation is self time: a ``while`` does not count its
body's operations again. The busy seconds are averaged over the chips used.
A trace without a TPU plane reduces only for a rehearsal, which reads
the CPU backend's ``hlo_op`` events instead and reports nothing of it
as a device metric.
"""
from __future__ import annotations

import glob
import os

SPAN_PREFIX = "bench:"
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
NS = 1e-9


def load(path: str) -> list[dict]:
    """Events as dicts: plane, line, name, start_ns, dur_ns, stats."""
    from jax.profiler import ProfileData

    if path.endswith(".textproto"):
        with open(path) as f:
            data = ProfileData.from_serialized_xspace(
                ProfileData.text_proto_to_serialized_xspace(f.read()))
    else:
        data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                out.append({"plane": plane.name, "line": line.name,
                            "name": ev.name, "start_ns": ev.start_ns,
                            "dur_ns": ev.duration_ns,
                            "stats": dict(ev.stats)})
    return out


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _self_segments(spans: list[tuple[str, float, float]]):
    """Nested spans of one thread -> disjoint (a, b, name) pieces, each
    named by the innermost span that covers it."""
    marks = sorted({t for _, a, b in spans for t in (a, b)})
    spans = sorted(spans, key=lambda s: (s[1], -s[2]))
    out, stack, i = [], [], 0
    for a, b in zip(marks, marks[1:]):
        while i < len(spans) and spans[i][1] <= a:
            stack.append(spans[i])
            i += 1
        stack = [s for s in stack if s[2] > a]
        if stack:
            out.append((a, b, stack[-1][0]))
    return out


def op_kind(ev: dict) -> str:
    """The name device time is summed under. On the TPU an event's name
    is the whole HLO line (``%copy.18 = u8[8,12,4194304]{...} copy(...)``):
    keep the operation's own name and the shape it makes."""
    name = ev["name"]
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name
    shape = rest.split("{", 1)[0].split(" ", 1)[0]
    return f"{head.lstrip('%')} {shape}"[:96]


def _self_times(evs: list[dict]) -> list[tuple[dict, float]]:
    """Events of one line, which nest (a ``while`` holds its body's
    operations): each with its duration less that of its children."""
    out, stack = [], []          # stack of [event, end, child_ns]
    for e in sorted(evs, key=lambda e: (e["start_ns"], -e["dur_ns"])):
        a, b = e["start_ns"], e["start_ns"] + e["dur_ns"]
        while stack and stack[-1][1] <= a:
            done = stack.pop()
            out.append((done[0], done[0]["dur_ns"] - done[2]))
        if stack:
            stack[-1][2] += e["dur_ns"]
        stack.append([e, b, 0.0])
    while stack:
        done = stack.pop()
        out.append((done[0], done[0]["dur_ns"] - done[2]))
    return out


def reduce(events: list[dict], n_devices: int = 1,
           allow_host: bool = False) -> dict | None:
    planes = sorted({e["plane"] for e in events
                     if e["plane"].startswith(DEVICE_PLANE)})
    if planes:
        dev = {p: [e for e in events
                   if e["plane"] == p and e["line"] == OPS_LINE]
               for p in planes[:n_devices]}
    elif allow_host:
        dev = {"/host:CPU": [e for e in events if "hlo_op" in e["stats"]]}
    else:
        return None
    spans = [(e["name"][len(SPAN_PREFIX):], e["start_ns"],
              e["start_ns"] + e["dur_ns"])
             for e in events if e["name"].startswith(SPAN_PREFIX)]
    every = [e for evs in dev.values() for e in evs]
    if not every:
        return None
    if spans:
        w0 = min(a for _, a, _ in spans)
        w1 = max(b for _, _, b in spans)
    else:
        w0 = min(e["start_ns"] for e in every)
        w1 = max(e["start_ns"] + e["dur_ns"] for e in every)
    dev = {p: [e for e in evs if e["start_ns"] >= w0
               and e["start_ns"] + e["dur_ns"] <= w1]
           for p, evs in dev.items()}
    every = [e for evs in dev.values() for e in evs]

    busy_ns = 0.0
    by_kind: dict[str, float] = {}
    gaps: dict[str, float] = {}
    segments = _self_segments(spans)
    for evs in dev.values():
        clipped = [(e["start_ns"], e["start_ns"] + e["dur_ns"])
                   for e in evs if e["dur_ns"] > 0]
        for e, self_ns in _self_times(evs):
            k = op_kind(e)
            by_kind[k] = by_kind.get(k, 0.0) + self_ns
        merged = _union(clipped)
        busy_ns += sum(b - a for a, b in merged)
        edges = [w0] + [t for ab in merged for t in ab] + [w1]
        idle = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        j = 0
        for a, b in idle:                  # both lists are sorted
            covered = 0.0
            while j < len(segments) and segments[j][1] <= a:
                j += 1
            k = j
            while k < len(segments) and segments[k][0] < b:
                sa, sb, name = segments[k]
                part = min(b, sb) - max(a, sa)
                if part > 0:
                    gaps[name] = gaps.get(name, 0.0) + part
                    covered += part
                k += 1
            if b - a - covered > 0:
                gaps["(outside the benchmark's spans)"] = gaps.get(
                    "(outside the benchmark's spans)", 0.0) \
                    + (b - a - covered)
    n = len(dev)
    rank = lambda d: sorted(([k, v * NS / n] for k, v in d.items()),
                            key=lambda kv: -kv[1])
    return {"planes": sorted(dev), "n_events": len(every),
            "busy_s": busy_ns * NS / n, "window_s": (w1 - w0) * NS,
            "device_ops": rank(by_kind), "idle_gaps": rank(gaps),
            "events": every, "window_ns": (w0, w1)}


def kernel_seconds(summary: dict, match) -> tuple[float, int]:
    """Summed device time and count of the events ``match(ev)`` accepts
    (inside the window), per chip."""
    total, count = 0.0, 0
    for e in summary["events"]:
        if match(e):
            total += e["dur_ns"]
            count += 1
    n = len(summary["planes"])
    return total * NS / n, count // n


def find_xplane(trace_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def reduce_dir(trace_dir: str, n_devices: int = 1,
               allow_host: bool = False) -> dict | None:
    path = find_xplane(trace_dir)
    if path is None:
        return None
    return reduce(load(path), n_devices, allow_host)


def main(argv=None) -> int:
    """``python3 benchmark/trace_reduce.py <trace dir or file>``: what a
    trace holds, for a look by hand before code is written against it."""
    import argparse
    import collections
    import json

    ap = argparse.ArgumentParser(description=main.__doc__)
    ap.add_argument("path")
    ap.add_argument("--top", type=int, default=25)
    args = ap.parse_args(argv)
    path = args.path if os.path.isfile(args.path) else find_xplane(args.path)
    events = load(path)
    lines = collections.Counter((e["plane"], e["line"]) for e in events)
    for (plane, line), n in sorted(lines.items()):
        evs = [e for e in events if (e["plane"], e["line"]) == (plane, line)]
        total, count, first = collections.Counter(), collections.Counter(), {}
        for e in evs:
            total[e["name"]] += e["dur_ns"]
            count[e["name"]] += 1
            first.setdefault(e["name"], e)
        print(json.dumps({"plane": plane, "line": line, "events": n,
                          "first_ns": min(e["start_ns"] for e in evs),
                          "last_ns": max(e["start_ns"] + e["dur_ns"]
                                         for e in evs)}))
        for name, ns in total.most_common(args.top):
            print("   ", json.dumps(
                {"name": name, "s": ns * NS, "n": count[name],
                 "stats": {k: str(v)[:120]
                           for k, v in first[name]["stats"].items()}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
