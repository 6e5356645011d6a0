"""Kernels (ops/rs_pallas.py), the decode shape of RS(4,8) with four rows
lost: the ``%_apply_3d`` calls' share of their roofline with EACH CALL's
work reckoned from the shape in its own event name (``... = u8[bucket, r,
n]{...} custom-call(...)``: ``kernel_work.rs_apply(k, r, n, bucket)``, pad
rows included, the kernel moves them), summed over the shapes the window
ran. A burst may run as two batches of two bucket sizes (1 and 8), so one
work figure for all calls would read a bucket-1 call eight times too
high."""
import re

import kernel_work

PREFIX = "%_apply_3d"
_SHAPE = re.compile(r" = u8\[(\d+),(\d+),(\d+)\]")


def read(view):
    if view.trace is None:
        return None
    q = view.ctx.config["k"]
    least = seconds = 0.0
    by_shape: dict[str, list] = {}
    for e in view.trace["events"]:
        if not e["name"].startswith(PREFIX):
            continue
        m = _SHAPE.search(e["name"])
        if m is None:
            continue
        bucket, r, n = (int(x) for x in m.groups())
        work = kernel_work.rs_apply(q, r, n, bucket)
        t, bound = kernel_work.least_seconds(work, view.ctx.device_kind)
        least += t
        seconds += e["dur_ns"] * 1e-9
        acc = by_shape.setdefault(f"u8[{bucket},{r},{n}]", [0, 0.0, t, bound])
        acc[0] += 1
        acc[1] += e["dur_ns"] * 1e-9
    if seconds <= 0:
        return None
    view.say(info="roofline", kernel=PREFIX, device_s=seconds,
             least_s=least,
             by_shape={k: {"calls": c, "device_s": s, "least_s_per_call": t,
                           "bound": b}
                       for k, (c, s, t, b) in sorted(by_shape.items())})
    return 100.0 * least / seconds
