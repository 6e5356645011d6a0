"""Submission engine, repair class, a result of many rows: mean host time
a batch in the regrouping of a request's linear rows into its own
``[rows, r, n]`` (``np.stack`` into fresh pages, ``_fetch_linear``; the
``cess:engine.repair.fetch.regroup`` span inside ``fetch``) — 10^3 x
Δ``regroup_s`` / Δ``batches``, to be read beside ``engine_fetch_ms.repair``,
which holds it. Its line prints Δ``regrouped_bytes`` over
Δ``result_bytes``: the share of the results that was copied once more on
its way to the caller. A program without the counters: nothing to read."""


def read(view):
    try:
        a = view.counters_before["engine"]["classes"]["repair"]
        b = view.counters_after["engine"]["classes"]["repair"]
        seconds = b["regroup_s"] - a["regroup_s"]
        regrouped = b["regrouped_bytes"] - a["regrouped_bytes"]
        handed = b["result_bytes"] - a["result_bytes"]
        batches = b["batches"] - a["batches"]
    except (KeyError, TypeError):
        return None
    if batches <= 0:
        return None
    view.say(info="fetch regroup", batches=batches, regroup_s=seconds,
             regrouped_bytes=regrouped, result_bytes=handed,
             regrouped_share=regrouped / handed if handed else None)
    return 1e3 * seconds / batches
