"""Submission engine, verify class: the share of the window's batches (one
round of the cell is one batch) whose proofs arrived after the batch's
folds were enqueued, so that the decode of the wire proofs ran under the
folds (PR 56): 100 where every round overlapped, 0 where the proofs were
in hand at the submit. From the program's ``late_proofs`` counter,
differenced over the window as ``verify_calls_per_round.missions500``
differences ``device_calls``; a program without it: nothing to read."""


def read(view):
    try:
        a = view.counters_before["engine"]["classes"]["verify"]
        b = view.counters_after["engine"]["classes"]["verify"]
        late = b["late_proofs"] - a["late_proofs"]
        batches = b["batches"] - a["batches"]
    except (KeyError, TypeError):
        return None
    if batches <= 0:
        return None
    return 100.0 * late / batches
