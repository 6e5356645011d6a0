"""Kernels (ops/rs_pallas.py), repair shapes: one lost fragment rebuilt from
k survivors ([1, k, n] -> [1, 1, n]) per call."""
import kernel_work


def read(view):
    c = view.ctx.config
    work = kernel_work.rs_apply(c["k"], 1, c["fragment_size"], 1)
    return kernel_work.roofline_share(view, "%_apply_3d", work)
