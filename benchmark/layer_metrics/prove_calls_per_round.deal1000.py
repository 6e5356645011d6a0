"""Submission engine, prove class: device programs called per batch (one
round of the cell is one batch): the steps of the chunked fold, one a
``podr2.PROVE_CHUNK`` fragments. From the program's ``device_calls``
counter, differenced over the window; a program whose prove class does not
count them: nothing to read."""


def read(view):
    try:
        a = view.counters_before["engine"]["classes"]["prove"]
        b = view.counters_after["engine"]["classes"]["prove"]
        calls = b["device_calls"] - a["device_calls"]
        batches = b["batches"] - a["batches"]
    except (KeyError, TypeError):
        return None
    if batches <= 0 or calls <= 0:
        return None
    return calls / batches
