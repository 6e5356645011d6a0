"""Bytes and operations a kernel call needs, from its shapes alone, and
the least time the chip could take for them.

RS codec kernel (cess_tpu/ops/rs_pallas.py): a GF(2^8) (r x q) matrix
apply lowered to an (8r x 8q) GF(2) bit-matrix product per byte column,
run as an int8 matmul on the MXU (``use_int8=True`` is the kernel's
default; read there, PR 24). Needed: 2 * 8r * 8q int8 operations per byte
column; bytes: the uint8 input rows once and the uint8 output rows once.
The kernel's own block-diagonal grouping (g = 2) and its second, packing
matmul are its choice and are not counted as needed work.

PoDR2 tag kernel (cess_tpu/ops/podr2_pallas.py): one pass over the
fragment bytes plus the PRF values in and the tags out (uint32
[F, limbs, blocks] each). Its arithmetic runs on the VPU, for which no
peak is published, so it is reckoned on bytes alone.
"""
from __future__ import annotations

import json
import os

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def peaks(device_kind: str) -> dict:
    with open(_PEAKS) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json: add it with its source")
    return table[device_kind]


def rs_apply(q: int, r: int, n: int, batch: int) -> dict:
    """[batch, q, n] uint8 -> [batch, r, n] uint8."""
    return {"bytes": batch * (q + r) * n,
            "ops": 2 * (8 * r) * (8 * q) * n * batch,
            "ops_peak": "int8_ops_per_s"}


def tag(fragments: int, nbytes: int, block_bytes: int, limbs: int) -> dict:
    """[F, nbytes] uint8 -> [F, blocks, limbs] uint32 tags."""
    words = fragments * (nbytes // block_bytes) * limbs
    return {"bytes": fragments * nbytes + 2 * 4 * words, "ops": 0,
            "ops_peak": None}


def least_seconds(work: dict, device_kind: str) -> tuple[float, str]:
    """max(bytes / HBM peak, ops / the dtype's peak), and which bound."""
    pk = peaks(device_kind)
    t_mem = work["bytes"] / pk["hbm_bytes_per_s"]
    t_ops = work["ops"] / pk[work["ops_peak"]] if work["ops"] else 0.0
    return (t_mem, "hbm") if t_mem >= t_ops else (t_ops, work["ops_peak"])


def roofline_share(view, prefix: str, work: dict):
    """100 x (calls x least seconds) / summed device seconds of the
    trace's events whose name starts with ``prefix``; None where the
    trace holds none. The bound it is reckoned on goes on an earlier
    line."""
    import trace_reduce

    if view.trace is None:
        return None
    seconds, calls = trace_reduce.kernel_seconds(
        view.trace, lambda e: e["name"].startswith(prefix))
    if not calls:
        return None
    least, bound = least_seconds(work, view.ctx.device_kind)
    view.say(info="roofline", kernel=prefix, calls=calls,
             device_s=seconds, least_s_per_call=least, bound=bound,
             bytes_per_call=work["bytes"], ops_per_call=work["ops"])
    return 100.0 * calls * least / seconds
