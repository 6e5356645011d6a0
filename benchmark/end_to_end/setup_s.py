"""Process start -> first timed operation: data from the seed, building
the system, warm-up, compile-or-load. The output check is not in it."""


def read(view):
    return view.ctx.setup_s
