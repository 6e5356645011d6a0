"""Submission engine, repair class: the share of the rows the device was
given that were pad rows — Δ``padded_rows`` / Δ(``rows`` +
``padded_rows``) over the window, in percent. A burst of eight in one
batch pads nothing; split 1 + 7 it pads one row of nine (11.1%). A
program without the counters: nothing to read."""


def read(view):
    try:
        a = view.counters_before["engine"]["classes"]["repair"]
        b = view.counters_after["engine"]["classes"]["repair"]
        pad = b["padded_rows"] - a["padded_rows"]
        real = b["rows"] - a["rows"]
    except (KeyError, TypeError):
        return None
    if pad + real <= 0:
        return None
    return 100.0 * pad / (pad + real)
