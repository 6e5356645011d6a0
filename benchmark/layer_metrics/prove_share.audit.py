"""Submission engine, audit classes: the share of the rounds' time spent
inside engine.prove_aggregate (the benchmark's spans)."""


def read(view):
    whole = view.spans.total("audit.round", view.ctx.window_t0)
    if whole <= 0:
        return None
    return 100.0 * view.spans.total("engine.prove_aggregate",
                                    view.ctx.window_t0) / whole
