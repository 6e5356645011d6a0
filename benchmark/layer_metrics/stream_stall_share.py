"""Stream driver: the share of the streamed run's wall time the host spent
blocked on device results (StreamStats.stall_s / wall_s, differenced over
the window's run)."""


def read(view):
    a, b = view.counters_before["stream"], view.counters_after["stream"]
    wall = b["wall_s"] - a["wall_s"]
    if wall <= 0:
        return None
    return 100.0 * (b["stall_s"] - a["stall_s"]) / wall
