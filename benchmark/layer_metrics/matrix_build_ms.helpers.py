"""Submission engine + codec, repair class, archival tier: host time per
repair spent building the matrix of an erasure pattern the codec did not
hold (GF(2^8) Gauss-Jordan + table expansion; the program's
``matrix_build_s`` counter differenced over the window, over the repairs
completed in it). A program from before the counter: nothing to read."""


def read(view):
    try:
        a = view.counters_before["engine"]["classes"]["repair"]
        b = view.counters_after["engine"]["classes"]["repair"]
        built_s = b["matrix_build_s"] - a["matrix_build_s"]
        done = b["completed"] - a["completed"]
    except (KeyError, TypeError):
        return None
    if done <= 0:
        return None
    return 1e3 * built_s / done
