"""Submission engine, repair class: mean time a request waits from submit
until its batch starts to run (the engine's ``queue`` stage counter,
summed over members, over the requests completed in the window). With one
closed-loop client this is the batcher's coalescing delay
(AdmissionPolicy.max_delay) plus its wake-up."""
import program_spans


def read(view):
    d = program_spans.stage_deltas(view, "repair")
    if d is None:
        return None
    return 1e3 * d["stages"]["queue"] / d["completed"]
