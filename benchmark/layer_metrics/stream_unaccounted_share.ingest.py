"""Stream driver: the share of the window's run that no counter names —
100 x (wall_s less stage_s + gate_s + h2d_s + stall_s + dispatch_s +
consumer_s) / wall_s of ``StreamStats``, differenced over the window: the
loop's own bookkeeping between its five stages and its two yields. The line
prints every part's share, ``consumer_s`` (the benchmark's own time between
two batches) among them, so where the host's half of a streamed batch goes
is read, not summed by hand from a trace. A program without ``stage_s`` /
``consumer_s``: nothing to read."""

PARTS = ("stage_s", "gate_s", "h2d_s", "stall_s", "dispatch_s",
         "consumer_s")


def read(view):
    a, b = view.counters_before["stream"], view.counters_after["stream"]
    if "consumer_s" not in b or "stage_s" not in b:
        return None
    wall = b["wall_s"] - a["wall_s"]
    if wall <= 0:
        return None
    share = {k: 100.0 * (b[k] - a[k]) / wall for k in PARTS}
    batches = b["batches"] - a["batches"]
    view.say(info="stream wall by part", wall_s=wall, batches=batches,
             share=share, ms_per_batch={
                 k: 1e3 * (b[k] - a[k]) / batches for k in PARTS}
             if batches else None)
    return 100.0 - sum(share.values())
