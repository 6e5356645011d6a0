"""Stream driver: seconds of the window's run the host spent inside waits
of more than 0.25 s for a result (``stream.stall``) or for a put's arrival
(``stream.gate``) — 0.0 in a sound window, 3.8-5.5 in one that hangs (one
streamed run in eleven, PERF.md). The difference of the two snapshots'
ladders, summed above the ladder's bound at 0.25 s (stage_ladders.py); the
line prints the waits themselves as the program kept them
(``StreamStats.raw()["long_waits"]``: the batch's ``seq``, results and puts
in flight, and over the wait the process's context switches, faults and
system seconds and the machine's stall totals), those that started inside
the window. A program without the ladders: nothing to read."""
import stage_ladders

LONG_WAIT_S = 0.25          # cess_tpu/obs/trace.py LONG_WAIT_S


def read(view):
    stages = stage_ladders.stream_stages(view)
    if stages is None:
        return None
    over = {stage: stage_ladders.seconds_over(stages[stage], LONG_WAIT_S)
            for stage in ("stream.stall", "stream.gate")}
    t0 = view.ctx.window_t0
    view.say(info="stream long waits", seconds_over=over,
             long_waits=[w for w in
                         view.counters_after["stream"]["long_waits"]
                         if w["start"] >= t0])
    return sum(over.values())
