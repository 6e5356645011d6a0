"""Submission engine, audit classes (prove + verify): the share of the
batches' stage time spent assembling the stacked host batch (``np.zeros``
and the copy loops; the ``assemble`` stage counter over all six)."""
import program_spans


def read(view):
    return program_spans.stage_share(view, "assemble", "prove", "verify")
