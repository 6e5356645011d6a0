"""Submission engine + codec, repair class, archival tier: the share of the
window's repairs whose erasure pattern (helpers, lost rows) was one the
codec held no matrix for (the program's ``patterns_new`` counter
differenced over the window). With ten helpers drawn of 13 nearly every
pattern is new; a high share with no program built is the design's point.
A program from before the counter: nothing to read."""


def read(view):
    try:
        a = view.counters_before["engine"]["classes"]["repair"]
        b = view.counters_after["engine"]["classes"]["repair"]
        new = b["patterns_new"] - a["patterns_new"]
        done = b["completed"] - a["completed"]
    except (KeyError, TypeError):
        return None
    if done <= 0:
        return None
    return 100.0 * new / done
