"""Where a Pallas kernel traced right now will run, and hence whether
it is lowered for the TPU or interpreted.

One function decides for both kernels (rs_pallas, podr2_pallas):
they are compiled by Mosaic when the dispatch lands on a TPU and run
in Pallas interpret mode everywhere else (the CPU test mesh, an
AuditBackend("cpu") pinned to the host device on a TPU box).
"""
from __future__ import annotations

import jax


def platform() -> str:
    """Platform of the device a dispatch issued now lands on: the
    active ``jax.default_device`` pin when there is one (a pool lane,
    an AuditBackend pinned to the host CPU while
    ``jax.default_backend()`` still says "tpu"), else the default
    backend."""
    dev = jax.config.jax_default_device
    if dev is not None:
        return getattr(dev, "platform", dev)
    return jax.default_backend()


def interpret() -> bool:
    """True when a pallas_call traced now must run interpreted."""
    return platform() != "tpu"
