"""The ``AuditBackend`` gate — the PoDR2 half of the north-star trait
pair (BASELINE.json: "gated behind a new ErasureCodec + AuditBackend
trait pair ... so the existing CPU path stays the default").

``make_audit_backend(backend)`` mirrors rs.make_codec: "cpu" (default)
pins every op to the host CPU device, "tpu"/"jax" runs on the default
accelerator, "auto" picks TPU when present. The math is identical —
cess_tpu/ops/podr2.py is platform-deterministic (threefry PRF + M31
lane arithmetic), a protocol invariant tested in tests/test_podr2.py —
so the gate chooses WHERE the batch runs, never WHAT it computes.
"""
from __future__ import annotations

import functools

import jax

from ..resilience import faults
from . import podr2


class AuditBackend:
    """Batched PoDR2 surface bound to one device: tag generation
    (TEE role), challenge derivation, proving (miner role, aggregated
    constant-size proofs), verification (TEE role).

    Fault seams (cess_tpu/resilience): ``podr2.<op>.<platform>`` —
    the platform suffix lets a chaos plan fail the accelerator-bound
    gate while the CPU instance (the resilience layer's degradation
    target) stays clean."""

    def __init__(self, key: podr2.Podr2Key, device):
        self.key = key
        self.device = device
        self._site = f"podr2.{{}}.{device.platform}"

    def _on(self, op: str, fn, *args):
        faults.inject(self._site.format(op))
        with jax.default_device(self.device):
            return fn(*args)

    # -- TEE: tag generation ------------------------------------------------
    @functools.cached_property
    def _tag_operands(self) -> tuple:
        return podr2.tag_operands(self.key)

    def tag_fragments(self, fragment_ids, fragments):
        """One compiled program a batch shape (podr2.TAG_PROGRAM), the
        key its operands: a call is one enqueue on this device."""
        return self._on("tag", podr2.tag_dispatch, self._tag_operands,
                        fragment_ids, fragments)

    # -- round: challenge derivation ----------------------------------------
    def gen_challenge(self, seed: bytes, num_blocks: int,
                      count: int | None = None):
        with jax.default_device(self.device):
            return podr2.gen_challenge(seed, num_blocks, count)

    # -- miner: proving ------------------------------------------------------
    def prove_batch(self, fragments, tags, idx, nu):
        return self._on("prove", podr2.prove_batch, fragments, tags,
                        idx, nu)

    def prove_aggregate(self, fragments, tags, idx, nu, r):
        return self._on("prove", podr2.prove_aggregate, fragments, tags,
                        idx, nu, r)

    def aggregate_coeffs(self, seed: bytes, fragment_ids):
        return self._on("prove", podr2.aggregate_coeffs, seed,
                        fragment_ids)

    # -- TEE: verification ---------------------------------------------------
    def verify_batch(self, fragment_ids, num_blocks, idx, nu, mu, sigma):
        return self._on("verify", podr2.verify_batch, self.key,
                        fragment_ids, num_blocks, idx, nu, mu, sigma)

    def verify_aggregate(self, fragment_ids, num_blocks, idx, nu, r, mu,
                         sigma):
        return self._on("verify", podr2.verify_aggregate, self.key,
                        fragment_ids, num_blocks, idx, nu, r, mu, sigma)


@functools.lru_cache(maxsize=None)
def _device_for(backend: str):
    if backend == "auto":
        backend = "tpu" if jax.default_backend() != "cpu" else "cpu"
    if backend == "cpu":
        return jax.devices("cpu")[0]
    if backend in ("tpu", "jax"):
        if jax.default_backend() == "cpu":
            # an EXPLICIT accelerator request must fail loudly, not
            # silently run the audit batch on CPU
            raise RuntimeError(
                "AuditBackend 'tpu' requested but no accelerator is "
                "present; use 'cpu' or 'auto'")
        return jax.devices()[0]
    raise ValueError(f"unknown AuditBackend {backend!r}")


def make_audit_backend(key: podr2.Podr2Key,
                       backend: str = "cpu") -> AuditBackend:
    """backend: "cpu" (default) | "tpu"/"jax" | "auto"."""
    return AuditBackend(key, _device_for(backend))
