"""Fused program (models/pipeline.py): milliseconds of device work a
streamed batch — the traced window's device-busy seconds (the ``device``
object's ``busy_s``) over the batches that ran in it. A batch is one call
of the fused encode+tag program, which holds exactly one ``%_tags_3d``
custom call (tests/test_tpu_compile.py), so the batches are the trace's
``%_tags_3d`` events. Everything the program does to a batch besides its
two kernels (regrouping rows, the tag kernel's view of the bytes, the PRF)
is in this number and in no kernel's roofline share;
``stream_stall_share`` cannot stand in for it, because a host blocked on a
transfer stalls as one blocked on a kernel does.

A CPU rehearsal's trace holds no kernel event (the Pallas interpreter
inlines the kernel): there the batches are the program's own
``cess:stream.dispatch`` spans, one a batch, and only the name is
reported. No trace, or neither: nothing to read."""
import program_spans
import trace_reduce


def read(view):
    if view.trace is None or view.trace["busy_s"] <= 0:
        return None
    _, batches = trace_reduce.kernel_seconds(
        view.trace, lambda e: e["name"].startswith("%_tags_3d"))
    if not batches:
        batches = (program_spans.total(view, "stream.dispatch")
                   or (0.0, 0))[1]
    if not batches:
        return None
    view.say(info="fused program", batches=batches,
             busy_s=view.trace["busy_s"])
    return 1e3 * view.trace["busy_s"] / batches
