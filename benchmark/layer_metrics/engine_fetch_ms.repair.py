"""Submission engine, repair class: mean time per batch in ``np.asarray``
of the finished result and the per-request slicing (the engine's ``fetch``
stage counter): the device->host copy of the repaired rows."""
import program_spans


def read(view):
    d = program_spans.stage_deltas(view, "repair")
    if d is None:
        return None
    return 1e3 * d["stages"]["fetch"] / d["batches"]
