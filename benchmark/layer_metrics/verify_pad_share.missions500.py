"""Submission engine, verify class: the share of the rows the device was
handed in the window that no mission owed (``padded_rows`` of ``rows +
padded_rows``, differenced over the window). The flat layout pads a
round's last loop step only, whatever the spread of the missions' sizes;
stacked [missions, F-bucket] the same round would read far higher."""


def read(view):
    try:
        a = view.counters_before["engine"]["classes"]["verify"]
        b = view.counters_after["engine"]["classes"]["verify"]
        pad = b["padded_rows"] - a["padded_rows"]
        real = b["rows"] - a["rows"]
    except (KeyError, TypeError):
        return None
    if pad + real <= 0:
        return None
    return 100.0 * pad / (pad + real)
