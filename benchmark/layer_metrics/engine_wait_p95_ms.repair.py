"""Submission engine, repair class: the window's 95th percentile of a
batch's ``wait`` stage — ``jax.block_until_ready`` on the result: the
device copies in and computes, the host waits. ``engine_wait_ms.repair``
is the mean of ``dispatch`` + ``wait``; this is the tail of the wait
alone, the part of a repair that can hang. Read from the difference of the
two snapshots' ``classes.repair.stages.wait.buckets`` (stage_ladders.py):
the engine's 512-sample ring cannot be differenced, a ladder can. With its
three siblings the first per-layer readings of ``repair_p95_ms``. A program
without the ladders: nothing to read."""
import stage_ladders


def read(view):
    return stage_ladders.engine_percentile_ms(
        view, "repair", "stages", "wait", 0.95)
