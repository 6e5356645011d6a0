"""Submission engine, repair class: the window's 95th percentile of a
request's ``caller.handoff`` — how long the caller stayed blocked in
``result()`` after its result existed: the wake-up of its thread and its
wait for the GIL (``engine_handoff_ms.repair`` is its mean). Read from the
difference of the two snapshots' ``classes.repair.caller.handoff.buckets``
(stage_ladders.py). A program without the ladders: nothing to read."""
import stage_ladders


def read(view):
    return stage_ladders.engine_percentile_ms(
        view, "repair", "caller", "handoff", 0.95)
