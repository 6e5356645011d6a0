"""Kernels (ops/rs_pallas.py), pooled ingest shapes: the RS encode kernel's
share of its roofline on one lane. Under the pool each chip's call sees
``batch / lanes`` segments ([batch / lanes, k, n] -> [batch / lanes, m, n]),
and ``trace_reduce.kernel_seconds`` gives seconds and calls per chip, so the
work is one lane's. The kernel's events are the trace's ``%_apply_3d``
custom calls."""
import kernel_work


def read(view):
    c, t = view.ctx.config, view.ctx.traffic
    work = kernel_work.rs_apply(c["k"], c["m"], c["fragment_size"],
                                t["batch"] // view.ctx.lanes)
    return kernel_work.roofline_share(view, "%_apply_3d", work)
