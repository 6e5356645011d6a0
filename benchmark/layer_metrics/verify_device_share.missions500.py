"""Submission engine, verify class: the share of the time between the
first program call of a batch and its verdicts being ready (the
``cess:engine.verify.dispatch`` and ``cess:engine.verify.wait`` spans of
the trace) in which the device was busy, as ``wait_device_share.repair``
reads it for the repair class. High: the round waits on the PRF; low: on
dispatch and transfers."""
import program_spans

SPANS = ("engine.verify.dispatch", "engine.verify.wait")


def read(view):
    d = program_spans.device_inside(view, SPANS)
    if d is None or d["span_s"] <= 0:
        return None
    view.say(info="device inside spans", spans=list(SPANS),
             count=d["spans"], span_s=d["span_s"], busy_s=d["busy_s"],
             events=d["events"])
    return 100.0 * d["busy_s"] / d["span_s"]
