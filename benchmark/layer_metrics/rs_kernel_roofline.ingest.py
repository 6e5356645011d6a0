"""Kernels (ops/rs_pallas.py), ingest shapes: the RS encode kernel's share
of its roofline, one call per streamed batch ([batch, k, n] -> [batch, m, n]).
The kernel's events are the trace's ``%_apply_3d`` custom calls (the name of
the jitted function around the pallas_call; a stable ``name=`` is for the
tracing issue)."""
import kernel_work


def read(view):
    c, t = view.ctx.config, view.ctx.traffic
    work = kernel_work.rs_apply(c["k"], c["m"], c["fragment_size"],
                                t["batch"])
    return kernel_work.roofline_share(view, "%_apply_3d", work)
