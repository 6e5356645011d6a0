"""A streamed batch's host spans paired with its program's run on the
device, as ``stream_operand_wait_share.ingest`` takes them (PR 54).

Since PR 54 the five ``cess:stream.*`` events of one batch carry its
``seq`` as metadata (an event's ``stats``). The programs of one stream run
in the order they were enqueued, so a batch's run is found in one of two
ways, the first that the trace allows:

``run_id``   the runtime's own identifier of one execution: where a host
             event that carries one lies inside a batch's
             ``cess:stream.dispatch`` (the executable's launch) and the
             device's events carry the same, the pairing is exact
the order    anchored where the host really waited (longer than
             ``WAITED_NS``): a ``cess:stream.stall`` ends when its
             batch's program ended, a ``cess:stream.gate`` when the rows
             of the put two before had arrived, which is where a device
             that stood waiting starts that batch's run; the offset
             between run order and ``seq`` most anchors agree on serves
             every run (``_pair_by_order``)

With the pairs, the device's idle time splits in two: while a batch's
program was enqueued (its dispatch had returned) and had not started, the
device was waiting for operands — on one chip, for the link; idle with
nothing enqueued is the host being late.

``reduce(events, summary)`` is the arithmetic on plain events
(``trace_reduce.load``'s) and a summary (``trace_reduce.reduce``'s), so a
small recorded trace checks it without a chip; ``operand_wait(view)`` is
it for a run's own trace, loaded once and kept on ``view``. ``None`` where
there is nothing to read: no trace, no ``seq`` in it (the parent of PR
54), no pair.
"""
from __future__ import annotations

import bisect
import collections
import os

import program_spans
import trace_reduce

NS = trace_reduce.NS
MODULES_LINE = "XLA Modules"
WAITED_NS = 200_000           # a wait this long waited for something
ARRIVAL_NS = 300_000          # host wake-up behind a put's arrival
GATE_BACK = 2                 # the gate waits for the put two before
_KEY = "_stream_pairing"


def _batch_spans(events, name: str) -> dict:
    """{seq: (start_ns, end_ns)} of the ``cess:<name>`` events that say
    which batch they are of."""
    out = {}
    for e in events:
        if e["name"] == program_spans.PREFIX + name and "seq" in e["stats"]:
            out[int(e["stats"]["seq"])] = (
                e["start_ns"], e["start_ns"] + e["dur_ns"])
    return out


def _runs(events, summary) -> list:
    """The first device's program executions, ``[(run_id or None,
    start_ns, end_ns)]`` by start: its events grouped by the ``run_id``
    they carry, else the plane's ``XLA Modules`` events."""
    plane = summary["planes"][0]
    by_run: dict = {}
    for e in summary["events"]:
        rid = e["stats"].get("run_id")
        if rid is not None and e["plane"] == plane and e["dur_ns"] > 0:
            a, b = e["start_ns"], e["start_ns"] + e["dur_ns"]
            was = by_run.get(rid)
            by_run[rid] = (a, b) if was is None \
                else (min(a, was[0]), max(b, was[1]))
    if by_run:
        runs = [(rid, a, b) for rid, (a, b) in by_run.items()]
    else:
        w0, w1 = summary["window_ns"]
        runs = [(e["stats"].get("run_id"), e["start_ns"],
                 e["start_ns"] + e["dur_ns"]) for e in events
                if e["plane"] == plane and e["line"] == MODULES_LINE
                and w0 <= e["start_ns"] and e["dur_ns"] > 0]
    return sorted(runs, key=lambda r: r[1])


def _pair_by_run_id(events, runs, dispatches) -> dict:
    """{seq: run} through the host's launch event: one that carries a
    ``run_id`` and lies inside a batch's dispatch on the dispatch's own
    thread. Events of the runtime's other threads carry run ids too and
    fall inside some batch's dispatch by chance (31 of 417 runs in the
    first chip trace): the pairs count only where they cover the runs."""
    spans = {}
    for e in events:
        if e["name"] == program_spans.PREFIX + "stream.dispatch" \
                and "seq" in e["stats"]:
            spans.setdefault((e["plane"], e["line"]), []).append(
                (e["start_ns"], e["start_ns"] + e["dur_ns"],
                 int(e["stats"]["seq"])))
    seq_of = {}
    for e in events:
        rid = e["stats"].get("run_id")
        mine = spans.get((e["plane"], e["line"]))
        if rid is None or mine is None or "hlo_op" in e["stats"]:
            continue
        a, b = e["start_ns"], e["start_ns"] + e["dur_ns"]
        for da, db, seq in mine:
            if da <= a and b <= db:
                seq_of.setdefault(rid, seq)
                break
    paired = {seq_of[r[0]]: r for r in runs if r[0] in seq_of}
    whole = min(len(runs), len(dispatches))
    return paired if len(paired) >= 0.8 * whole else {}


def _pair_by_order(runs, dispatches, stalls, gates):
    """({seq: run}, how) through the order. The runs of one stream are in
    ``seq`` order, so one offset between run index and ``seq`` serves
    them all; it is the one most anchors agree on:

    - a ``stall`` of batch s that waited ends when run s ended: the last
      run to end inside the wait is batch s's;
    - a ``gate`` of batch i that waited ends when the rows of put
      i - ``GATE_BACK`` had arrived; where the device stood waiting for
      them, that batch's run starts there (within ``ARRIVAL_NS``).

    Without any anchor the host never blocked, so it is the late side
    and a run starts right behind its own dispatch: the largest offset
    under which no run starts before its dispatch began."""
    by_end = sorted(range(len(runs)), key=lambda j: runs[j][2])
    ends = [runs[j][2] for j in by_end]
    starts = [r[1] for r in runs]
    votes = collections.Counter()
    for seq, (a, b) in stalls.items():
        i = bisect.bisect_right(ends, b) - 1
        if b - a >= WAITED_NS and i >= 0 and ends[i] >= a:
            votes[seq - by_end[i], "stall"] += 1
    for seq, (a, b) in gates.items():
        i = bisect.bisect_left(starts, b - ARRIVAL_NS)
        if b - a >= WAITED_NS and i < len(starts) and starts[i] <= b:
            votes[seq - GATE_BACK - i, "gate"] += 1
    by_offset = collections.Counter()
    for (offset, _), n in votes.items():
        by_offset[offset] += n
    if by_offset:
        offset = by_offset.most_common(1)[0][0]
        how = "+".join(sorted(kind for (o, kind) in votes if o == offset))
    else:
        offset, how = None, "order"
        for o in range(min(dispatches) - len(runs), max(dispatches) + 1):
            held = [j for j in range(len(runs)) if j + o in dispatches]
            if held and all(dispatches[j + o][0] <= runs[j][1]
                            for j in held):
                offset = o
        if offset is None:
            return {}, how
    return {j + offset: r for j, r in enumerate(runs)
            if j + offset in dispatches}, how


def reduce(events, summary):
    """{"operand_wait_s", "host_late_s", "idle_s", "paired", "runs",
    "pairing"} of one trace, or None."""
    dispatches = _batch_spans(events, "stream.dispatch")
    if not dispatches or summary is None:
        return None
    runs = _runs(events, summary)
    paired, how = _pair_by_run_id(events, runs, dispatches), "run_id"
    if not paired:
        paired, how = _pair_by_order(
            runs, dispatches, _batch_spans(events, "stream.stall"),
            _batch_spans(events, "stream.gate"))
    if not paired:
        return None
    plane = summary["planes"][0]
    w0, w1 = summary["window_ns"]
    busy = trace_reduce._union(
        [(e["start_ns"], e["start_ns"] + e["dur_ns"])
         for e in summary["events"]
         if e["plane"] == plane and e["dur_ns"] > 0])
    edges = [w0] + [t for ab in busy for t in ab] + [w1]
    idle = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    # enqueued and not started: the dispatch returned, the run not begun
    pending = trace_reduce._union(
        [(dispatches[seq][1], run[1]) for seq, run in paired.items()
         if run[1] > dispatches[seq][1]])
    waiting = sum(b - a for a, b in program_spans._clip(idle, pending))
    idle_ns = sum(b - a for a, b in idle)
    if idle_ns <= 0:
        return None
    return {"operand_wait_s": waiting * NS,
            "host_late_s": (idle_ns - waiting) * NS,
            "idle_s": idle_ns * NS, "paired": len(paired),
            "runs": len(runs), "pairing": how}


def operand_wait(view):
    if hasattr(view, _KEY):
        return getattr(view, _KEY)
    found = None
    if view.trace is not None:
        path = trace_reduce.find_xplane(os.path.join(
            program_spans.ROOT, ".bench_trace", view.ctx.cell))
        if path is not None:
            found = reduce(trace_reduce.load(path), view.trace)
    setattr(view, _KEY, found)
    return found
