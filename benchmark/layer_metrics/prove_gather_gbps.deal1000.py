"""Submission engine, prove class: the rate of the host gather — bytes of
challenged blocks and tag rows read out of the miner's store into a step's
buffer (``gathered_bytes``) over the host seconds those gathers took
(``gather_seconds``, the batches' ``assemble`` stage), differenced over the
window, in GB/s. 512-byte pieces out of 7.8 GiB: the host's memory latency,
not its bandwidth, sets it. A program without the counters: nothing to
read."""


def read(view):
    try:
        a = view.counters_before["engine"]["classes"]["prove"]
        b = view.counters_after["engine"]["classes"]["prove"]
        nbytes = b["gathered_bytes"] - a["gathered_bytes"]
        seconds = b["gather_seconds"] - a["gather_seconds"]
    except (KeyError, TypeError):
        return None
    if nbytes <= 0 or seconds <= 0:
        return None
    return nbytes / seconds / 1e9
