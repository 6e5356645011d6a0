"""Submission engine, archival tier: programs the engine's ProgramCache
built inside the window per repair completed in it (``programs_built``
differenced). 0 is what one program per shape gives; about 1 is a program
per erasure pattern."""


def read(view):
    try:
        a, b = view.counters_before["engine"], view.counters_after["engine"]
        built = b["programs_built"] - a["programs_built"]
        done = b["classes"]["repair"]["completed"] \
            - a["classes"]["repair"]["completed"]
    except (KeyError, TypeError):
        return None
    if done <= 0:
        return None
    return built / done
