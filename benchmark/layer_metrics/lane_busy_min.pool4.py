"""Device pool (serve/pool.py, parallel/mesh.py): the busy share of the
least busy of the cell's device planes over the traced window (the union
of each plane's ``XLA Ops`` intervals over the window's length). The
``device`` object's ``busy_s`` is the mean over the planes; a lane that the
one sharded put or the one program serves last shows here. One plane in a
CPU rehearsal."""
import trace_reduce


def read(view):
    if view.trace is None:
        return None
    w0, w1 = view.trace["window_ns"]
    if w1 <= w0:
        return None
    busy = {}
    for plane in view.trace["planes"]:
        spans = [(e["start_ns"], e["start_ns"] + e["dur_ns"])
                 for e in view.trace["events"]
                 if e["plane"] == plane and e["dur_ns"] > 0]
        busy[plane] = 100.0 * sum(
            b - a for a, b in trace_reduce._union(spans)) / (w1 - w0)
    if not busy:
        return None
    view.say(info="busy share by plane", **busy)
    return min(busy.values())
