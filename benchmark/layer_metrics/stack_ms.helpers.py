"""The client (a miner's repair loop), archival tier: mean time of the
``np.stack`` that puts the ten helpers' rows into one ``[k, n]`` host array
before the engine is called (the benchmark's ``repair.stack_survivors``
span, over the window's operations): one host copy of every survivor byte."""


def read(view):
    picked = [t1 - t0 for name, t0, t1 in view.spans.records
              if name == "repair.stack_survivors"
              and t0 >= view.ctx.window_t0]
    if not picked:
        return None
    return 1e3 * sum(picked) / len(picked)
