"""Submission engine, verify class: device programs called per batch (one
round of the cell is one batch): the folds, one a 16,384 flat rows, and
the close. From the program's ``device_calls`` counter, differenced over
the window; a program without it: nothing to read."""


def read(view):
    try:
        a = view.counters_before["engine"]["classes"]["verify"]
        b = view.counters_after["engine"]["classes"]["verify"]
        calls = b["device_calls"] - a["device_calls"]
        batches = b["batches"] - a["batches"]
    except (KeyError, TypeError):
        return None
    if batches <= 0:
        return None
    return calls / batches
