"""Kernels (ops/podr2_pallas.py): the PoDR2 tag kernel's share of its
roofline, one pass over the batch's fragment bytes per call. Its events are
the trace's ``%_tags_3d`` custom calls."""
import kernel_work


def read(view):
    c, t = view.ctx.config, view.ctx.traffic
    work = kernel_work.tag(t["batch"] * (c["k"] + c["m"]),
                           c["fragment_size"], c["podr2_block_bytes"],
                           c["podr2_limbs"])
    return kernel_work.roofline_share(view, "%_tags_3d", work)
