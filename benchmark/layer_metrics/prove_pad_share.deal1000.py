"""Submission engine, prove class: the share of the fragment rows put on
the device in the window that no miner held (``padded_rows`` of ``rows +
padded_rows``, differenced over the window): the last step's pad, 24 rows
of 1,024 at 1,000 fragments 64 a step. They carry r = 0 and cost their
bytes on the link."""


def read(view):
    try:
        a = view.counters_before["engine"]["classes"]["prove"]
        b = view.counters_after["engine"]["classes"]["prove"]
        pad = b["padded_rows"] - a["padded_rows"]
        real = b["rows"] - a["rows"]
        b["chunks"]                     # this PR's prove class
    except (KeyError, TypeError):
        return None
    if pad + real <= 0:
        return None
    return 100.0 * pad / (pad + real)
