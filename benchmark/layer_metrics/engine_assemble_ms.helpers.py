"""Submission engine, repair class, archival tier: mean time per batch in
the host-side coalescing of the survivors (the engine's ``assemble`` stage
counter): the concatenate and the pad to the row bucket."""
import program_spans


def read(view):
    d = program_spans.stage_deltas(view, "repair")
    if d is None:
        return None
    return 1e3 * d["stages"]["assemble"] / d["batches"]
