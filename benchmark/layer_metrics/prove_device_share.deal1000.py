"""Submission engine, prove class: the share of the time between a step's
program call and its result being waited for (the
``cess:engine.prove.dispatch`` and ``cess:engine.prove.wait`` spans of the
trace, one of each a step) in which the device was busy, as
``wait_device_share.repair`` reads it for the repair class. Low: the round
waits on the link and on dispatch, not on the fold."""
import program_spans

SPANS = ("engine.prove.dispatch", "engine.prove.wait")


def read(view):
    d = program_spans.device_inside(view, SPANS)
    if d is None or d["span_s"] <= 0:
        return None
    view.say(info="device inside spans", spans=list(SPANS),
             count=d["spans"], span_s=d["span_s"], busy_s=d["busy_s"],
             events=d["events"])
    return 100.0 * d["busy_s"] / d["span_s"]
