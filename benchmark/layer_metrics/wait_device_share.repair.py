"""Submission engine, repair class: the share of the time between the
program call and its result being ready (``cess:engine.repair.dispatch`` and
``cess:engine.repair.wait`` spans in the trace) in which the device was
busy. Low: the host waits on transfers or on dispatch latency, not on the
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
