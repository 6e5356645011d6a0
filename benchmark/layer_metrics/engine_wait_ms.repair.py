"""Submission engine, repair class: mean time per batch from the program
call to its result being ready on the device (the engine's ``dispatch`` +
``wait`` stage counters): the enqueue of the program and of its host->device
copies, then the host blocked while the device copies in and computes."""
import program_spans


def read(view):
    d = program_spans.stage_deltas(view, "repair")
    if d is None:
        return None
    return 1e3 * (d["stages"]["dispatch"] + d["stages"]["wait"]) \
        / d["batches"]
