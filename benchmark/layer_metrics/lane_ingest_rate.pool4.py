"""Device pool (serve/pool.py, parallel/mesh.py): one lane's ingest rate in
GiB/s, to set beside the one-chip cell's ``ingest_rate``: user bytes the
stream driver staged over the wall time of its run, differenced over the
window (StreamStats ``bytes_in`` / ``wall_s``), divided by the lanes a
staged batch is placed over (StreamStats ``lanes``; a program without that
counter: the lanes the traffic driver built its pool with)."""

GIB = float(1 << 30)


def read(view):
    a, b = view.counters_before["stream"], view.counters_after["stream"]
    wall = b["wall_s"] - a["wall_s"]
    lanes = b.get("lanes") or view.ctx.lanes
    if wall <= 0 or lanes <= 0:
        return None
    view.say(info="lanes", lanes=lanes, counted="lanes" in b)
    return (b["bytes_in"] - a["bytes_in"]) / wall / lanes / GIB
