"""Submission engine, audit classes: device operations started inside the
``cess:engine.prove.batch`` and ``cess:engine.verify.batch`` spans of the
trace, per prove batch in the window (one prove and one verify batch make a
round). Every event of the device's ``XLA Ops`` line counts."""
import program_spans

SPANS = ("engine.prove.batch", "engine.verify.batch")


def read(view):
    rounds = program_spans.total(view, "engine.prove.batch")
    d = program_spans.device_inside(view, SPANS)
    if rounds is None or d is None:
        return None
    view.say(info="device inside spans", spans=list(SPANS),
             count=d["spans"], span_s=d["span_s"], busy_s=d["busy_s"],
             events=d["events"], prove_batches=rounds[1])
    return d["events"] / rounds[1]
