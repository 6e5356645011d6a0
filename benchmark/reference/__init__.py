"""Frozen plain references the benchmark compares the program with.

Copies taken at PR 24 of cess_tpu/ops/gf.py, rs_ref.py and pfield.py
(NumPy) and of the plain-jnp PoDR2 equations of cess_tpu/ops/podr2.py
(no Pallas dispatch). Nothing here imports the program, and nothing
here is handed anything the program made: data and keys come from the
seed. Later PRs may change the program; they may not change these.
"""
