"""Kernels (ops/podr2_pallas.py), pooled ingest shapes: the PoDR2 tag
kernel's share of its roofline on one lane, one pass over the
``batch / lanes`` segments' fragment bytes per call (seconds and calls are
per chip, so the work is one lane's). Its events are the trace's
``%_tags_3d`` custom calls; a program whose sharded step tags without the
kernel has none, and this reads None."""
import kernel_work


def read(view):
    c, t = view.ctx.config, view.ctx.traffic
    work = kernel_work.tag(
        t["batch"] // view.ctx.lanes * (c["k"] + c["m"]),
        c["fragment_size"], c["podr2_block_bytes"], c["podr2_limbs"])
    return kernel_work.roofline_share(view, "%_tags_3d", work)
