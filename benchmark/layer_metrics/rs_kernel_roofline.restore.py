"""Kernels (ops/rs_pallas.py), the restoral cells: the RS kernel's share
of its HBM roofline with the work reckoned from the cell's ``mode`` — in
``symbols`` a call is one hop's fold, (accumulator, fragment) -> the next
aggregate ([1, 2, n] -> [1, 1, n]); in ``fragments`` one lost row from the
configuration's k survivors ([1, k, n] -> [1, 1, n])."""
import kernel_work


def read(view):
    c = view.ctx.config
    q = 2 if view.ctx.traffic["mode"] == "symbols" else c["k"]
    work = kernel_work.rs_apply(q, 1, c["fragment_size"], 1)
    return kernel_work.roofline_share(view, "%_apply_3d", work)
