"""Submission engine, repair class, bursts of per-segment requests:
requests a device batch — Δ``batched_requests`` / Δ``batches`` over the
window. 8 were every burst of eight one batch; about 4 is the split the
idle rule allows (the first request goes alone at bucket 1 the moment the
batcher wakes, the other seven gather behind it and go at bucket 8 with a
pad row). Its line prints the window's drains by trigger and, a batch
being a burst of one loss pattern here, what the burst's matrix cost:
Δ``patterns_new`` and 10^3 x Δ``matrix_build_s`` over Δ``batches`` (the
495 patterns against the codec's LRU of 64). A program without
``batched_requests`` in its snapshot: nothing to read."""


def read(view):
    try:
        a = view.counters_before["engine"]["classes"]["repair"]
        b = view.counters_after["engine"]["classes"]["repair"]
        requests = b["batched_requests"] - a["batched_requests"]
        batches = b["batches"] - a["batches"]
    except (KeyError, TypeError):
        return None
    if batches <= 0:
        return None
    view.say(info="repair batches", batches=batches, requests=requests,
             drains={k: n - a.get("drains", {}).get(k, 0)
                     for k, n in b.get("drains", {}).items()},
             new_patterns_per_batch=(b.get("patterns_new", 0)
                                     - a.get("patterns_new", 0)) / batches,
             matrix_build_ms_per_batch=1e3 * (b.get("matrix_build_s", 0.0)
                                              - a.get("matrix_build_s", 0.0))
             / batches)
    return requests / batches
