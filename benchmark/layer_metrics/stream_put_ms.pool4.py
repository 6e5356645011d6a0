"""Stream driver: host milliseconds a batch spends inside the two staging
calls (the sharded ``device_put`` of the rows over every lane and that of
the ids; StreamStats ``h2d_s`` over ``batches``, differenced over the
window). On one chip the calls are an enqueue (1.58 ms for 128 MiB); this
says whether four shards of 128 MiB from one thread still are."""


def read(view):
    a, b = view.counters_before["stream"], view.counters_after["stream"]
    batches = b["batches"] - a["batches"]
    if batches <= 0:
        return None
    return 1e3 * (b["h2d_s"] - a["h2d_s"]) / batches
