"""Submission engine, repair class, a chained repair's folds: the share of
the time between a fold's program call and its result being ready (the
``cess:engine.repair.dispatch`` and ``cess:engine.repair.wait`` spans of
the trace, one of each a hop) in which the device was busy, as
``wait_device_share.repair`` reads it for a whole-fragment repair. Low: a
hop waits on its 16 MiB going up and on dispatch latency, not on the
kernel."""
import program_spans

SPANS = ("engine.repair.dispatch", "engine.repair.wait")


def read(view):
    d = program_spans.device_inside(view, SPANS)
    if d is None or d["span_s"] <= 0:
        return None
    view.say(info="device inside spans", spans=list(SPANS),
             count=d["spans"], span_s=d["span_s"], busy_s=d["busy_s"],
             events=d["events"])
    return 100.0 * d["busy_s"] / d["span_s"]
