"""Submission engine, audit classes (prove + verify): the share of the
batches' stage time spent inside the program call (the ``dispatch`` stage
counter over all six): the un-jitted vmaps issue their operations one by
one here, and the host batch is copied to the device."""
import program_spans


def read(view):
    return program_spans.stage_share(view, "dispatch", "prove", "verify")
