"""A stage's distribution, as the per-layer readers take it (PR 54).

Beside each stage's ``{"n", "s"}`` the program keeps the stage's
occurrences on one ladder of buckets (cess_tpu/obs/trace.py
``STAGE_LADDER_S``: 50 us to 10 s, ratio under 1.1, a bound at 0.25 s)
and hands out the non-empty ones as ``"buckets": [[le, count, seconds],
...]`` — ``le`` the bucket's upper bound, ``None`` above the last; NOT
cumulative. Counts and seconds of equal ``le`` subtract, so the window's
own distribution is the difference of the two snapshots ``run.py`` takes
(``counters_before`` / ``counters_after``):

``window(before, after)``     that difference, in ladder order
``percentile_s(buckets, q)``  the mean of the occurrences in the bucket
                              that holds rank ``ceil(q * count)``: the
                              bucket is exact, the value inside it is its
                              occurrences' own mean (within the ladder's
                              ratio of every one of them)
``seconds_over(buckets, x)``  seconds inside occurrences longer than a
                              bound ``x`` of the ladder
``engine_percentile_ms(view, cls, group, name, q)``
                              the same for one account of one engine
                              class (``stages`` / ``queue`` / ``caller``),
                              printing the window's distribution once
``stream_stages(view)``       {stage: window buckets} of the stream
                              driver's five stages

Every function returns ``None`` where there is nothing to read: a program
without the ladders (the parent of PR 54), a cell that does not drive the
class, a window without an occurrence.
"""
from __future__ import annotations

import math


def _key(le):
    return math.inf if le is None else le


def window(before, after) -> list:
    """``after`` less ``before``, bucket by bucket; empty ones left out."""
    was = {_key(le): (n, s) for le, n, s in before}
    out = []
    for le, n, s in after:
        n0, s0 = was.get(_key(le), (0, 0.0))
        if n - n0 > 0:
            out.append([le, n - n0, s - s0])
    return sorted(out, key=lambda b: _key(b[0]))


def count(buckets) -> int:
    return sum(n for _, n, _ in buckets)


def percentile_s(buckets, q: float):
    total = count(buckets)
    if total <= 0:
        return None
    rank, seen = max(1, math.ceil(q * total)), 0
    for _, n, s in buckets:
        seen += n
        if seen >= rank:
            return s / n
    return None


def seconds_over(buckets, bound: float) -> float:
    return sum((s for le, _, s in buckets if _key(le) > bound), 0.0)


def summary_ms(buckets) -> dict:
    """What a reader's line prints of one window's distribution."""
    total = count(buckets)
    return {"n": total,
            "mean_ms": 1e3 * sum(s for _, _, s in buckets) / total,
            **{f"p{int(100 * q)}_ms": 1e3 * percentile_s(buckets, q)
               for q in (0.5, 0.95, 0.99)},
            "longest_bucket_ms": 1e3 * buckets[-1][2] / buckets[-1][1]}


def engine_percentile_ms(view, cls: str, group: str, name: str, q: float):
    try:
        a = view.counters_before["engine"]["classes"][cls][group][name]
        b = view.counters_after["engine"]["classes"][cls][group][name]
        got = window(a["buckets"], b["buckets"])
    except (KeyError, TypeError):
        return None
    if not got:
        return None
    view.say(info="stage ladder", cls=cls, account=f"{group}.{name}",
             **summary_ms(got))
    return 1e3 * percentile_s(got, q)


def stream_stages(view):
    try:
        a = view.counters_before["stream"]["stages"]
        b = view.counters_after["stream"]["stages"]
    except (KeyError, TypeError):
        return None
    return {stage: window(a[stage]["buckets"], acc["buckets"])
            for stage, acc in b.items()}
