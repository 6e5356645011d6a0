"""Submission engine: the repair class's own median, submit -> resolve, from
stats_snapshot() after the window (a sliding window of the last 512
operations, so warm-up's few are long gone)."""


def read(view):
    cls = view.counters_after["engine"]["classes"]["repair"]
    if cls["completed"] == view.counters_before["engine"]["classes"][
            "repair"]["completed"]:
        return None
    return 1e3 * cls["latency_p50"]
