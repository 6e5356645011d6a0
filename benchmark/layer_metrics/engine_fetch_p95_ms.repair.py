"""Submission engine, repair class: the window's 95th percentile of a
batch's ``fetch`` stage — the result's way down as linear pieces and the
hand-out to the requests (``engine_fetch_ms.repair`` is its mean). Read
from the difference of the two snapshots'
``classes.repair.stages.fetch.buckets`` (stage_ladders.py). A program
without the ladders: nothing to read."""
import stage_ladders


def read(view):
    return stage_ladders.engine_percentile_ms(
        view, "repair", "stages", "fetch", 0.95)
