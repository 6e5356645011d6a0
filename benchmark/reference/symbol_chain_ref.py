"""A repair by partial sums folded at the helpers, hop by hop (PR 40).

The deployment ``archival-restoral``: one lost row of an RS(k, m) stripe
is rebuilt from k survivors without any of them leaving its holder whole.
With G the systematic generator (``gf.systematic_generator``), P the
survivors' rows and l the lost row, the repair row is

    c = G[l] * inverse(G[P])                              (1 x k over GF(2^8))

and the lost fragment is  XOR_j c_j * fragment_{P_j}  by linearity. The
helpers are asked in the order of P; helper j is handed the aggregate so
far and hands on

    acc_j = acc_{j-1} ^ c_j * fragment_{P_j},      acc_{-1} = 0

so acc_{k-1} is the lost fragment (Mitra et al., Partial-Parallel-Repair,
EuroSys 2016: partial results combined at the helpers; here along a
line, as Li et al., Repair Pipelining, ATC 2017, lay them, unsliced).

The inverse is this package's own Gauss-Jordan elimination
(``gf.gf_mat_inv``), not the program's closed Cauchy form; a product
c_j * x is a lookup in the row of the multiplication table. Nothing here
imports the program. ``chain`` returns EVERY hop's aggregate, so a run is
held to each of them and not only to the last.
"""
from __future__ import annotations

import numpy as np

from . import gf


def repair_row(k: int, m: int, present, lost: int) -> np.ndarray:
    """c [k] uint8: the coefficients of the survivors ``present`` (k row
    indices, the order the helpers are asked in) that rebuild row
    ``lost``."""
    present = tuple(int(j) for j in present)
    if len(present) != k or len(set(present)) != k or lost in present \
            or not all(0 <= j < k + m for j in (*present, lost)):
        raise ValueError(f"{present} cannot rebuild row {lost} of "
                         f"RS({k},{m})")
    g = gf.systematic_generator(k, m)
    inv = gf.gf_mat_inv(g[list(present)])
    return gf.gf_matmul(g[[lost]], inv)[0]


def chain(k: int, m: int, present, lost: int, fragments) -> list:
    """The k aggregates the helpers hand on, in order: ``fragments[j]``
    is the row ``present[j]`` as uint8 (or ``bytes``). The last one is
    the lost row."""
    mt = gf.mul_table()
    acc, out = None, []
    for c, frag in zip(repair_row(k, m, present, lost), fragments):
        term = mt[int(c)][np.frombuffer(frag, dtype=np.uint8)
                          if isinstance(frag, (bytes, bytearray))
                          else np.asarray(frag, dtype=np.uint8)]
        acc = term if acc is None else acc ^ term
        out.append(acc)
    return out
