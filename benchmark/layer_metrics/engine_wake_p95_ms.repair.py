"""Submission engine, repair class: the window's 95th percentile of a
batch's ``queue.wake`` — from the instant the drain trigger tripped until
the batch starts to run (the batcher asleep, busy or waiting for the GIL),
summed over the batch's members: one, in every repair cell but the
burst's (``engine_wake_ms.repair`` is its mean a request). Read from the
difference of the two snapshots' ``classes.repair.queue.wake.buckets``
(stage_ladders.py). A program without the ladders: nothing to read."""
import stage_ladders


def read(view):
    return stage_ladders.engine_percentile_ms(
        view, "repair", "queue", "wake", 0.95)
