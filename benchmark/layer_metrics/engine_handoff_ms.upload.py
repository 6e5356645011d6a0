"""Submission engine, an upload's two classes (encode + tag): how long an
upload's thread stays blocked in ``result()`` after the batcher has
resolved its future, an upload (the program's ``caller.handoff`` counters
of both classes, over the tag batches of the window: one an upload). What
an upload pays for waking up behind the gateway's workers, which hold the
GIL while they copy. A program without the counter: nothing to read."""
import caller_accounts


def read(view):
    d = caller_accounts.deltas(
        view, "encode", "tag",
        calls=("pipeline.encode_step", "pipeline.tag_step"))
    if d is None or d["batches_of"]["tag"] <= 0:
        return None
    return 1e3 * d["handoff"] / d["batches_of"]["tag"]
