"""Stream driver: the share of the window's streamed batches whose bytes
went up in a linear layout — 1-D ``uint8`` row views of the staged chunk
handed to the put, stacked to ``u8[B, k, n]`` on the device (StreamStats
``linear_puts`` over ``batches``, differenced over the window). 100 where
the driver stages every batch linear; a batch that went up as one packed
``u8[B, k*n]`` array (packed four rows to a word on the host first, at
5 GiB/s on one chip) is missing from it. A program without the counter
(before PR 43) gives nothing to read."""


def read(view):
    a, b = view.counters_before["stream"], view.counters_after["stream"]
    if "linear_puts" not in b:
        return None
    batches = b["batches"] - a["batches"]
    if batches <= 0:
        return None
    linear = b["linear_puts"] - a["linear_puts"]
    view.say(info="linear puts", batches=batches, linear_puts=linear,
             put_arrays=b["put_arrays"] - a["put_arrays"])
    return 100.0 * linear / batches
