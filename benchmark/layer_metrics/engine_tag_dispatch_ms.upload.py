"""Submission engine, tag class: mean time per batch until the tag
program's call returns (the engine's ``dispatch`` stage counter): one
enqueue where the batch is one compiled program, the host's issue of
every PoDR2 operation where it is not."""
import program_spans


def read(view):
    d = program_spans.stage_deltas(view, "tag")
    if d is None:
        return None
    return 1e3 * d["stages"]["dispatch"] / d["batches"]
