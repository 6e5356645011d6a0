"""The storage pipeline: segment -> RS fragments (-> PoDR2 tags).

This is the flagship end-to-end workload ("model") of the framework:
the batched device program that replaces the reference's off-chain
OSS-gateway chunk/encode step and TEE tag computation
(SURVEY.md §3.2: user -> OSS chunks file into 16 MiB segments,
RS-encodes each into fragments; §3.3: TEE computes PoDR2 tags).

Everything here is jit-able and batch-first: segments [B, segment_size]
uint8 -> fragments [B, k+m, fragment_size] uint8 (+ per-fragment tags
once the audit backend is wired in).

The direct (engine-less) ``forward`` is ONE jitted device program —
encode and tag fused. The double-buffered streaming driver
(cess_tpu/serve/stream.py) is built on exactly this program. The
segment buffer is not donated: no output has its shape, so XLA
cannot alias it (the chip reports such a donation as unusable).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from .. import constants
from ..obs import trace
from ..ops import gf, podr2, rs
from ..ops.rs import default_strategy, _MatrixApply, _stack_rows


# The two row regroupings of the data plane, written WITHOUT
# ``reshape``. On the TPU a uint8 array is tiled over its last two
# dimensions, so moving bytes between the row and the batch dimension
# is a real relayout, and the TPU compiler's reshape lowering takes
# compile time proportional to the array for it: [8, 16 MiB] ->
# [8, 4, 4 MiB] took over eight minutes and [4, 3, 8 MiB] ->
# [12, 8 MiB] 168 s for a described v5e (PR 22 rehearsals), against
# under two seconds for the slice/concatenate forms below. Same bytes.
#
# Who still regroups (PR 51): ``split_rows`` the ``[B, segment_size]``
# input form (``forward``, ``encode_step``); ``merge_rows`` the engine
# path's ``tag_step`` and the byte-sharded mesh steps (parallel/mesh.py);
# ``stack_rows`` those mesh steps and a streamed batch of other than 8
# or 16 segments. The fused step over the stream's rows does none: the
# RS kernel reads the rows as put and writes the codeword fragment-major,
# and the tag kernel takes that batch as it is (``fused_step``).


@functools.partial(jax.jit, static_argnums=(1,))
def split_rows(segments: jax.Array, k: int) -> jax.Array:
    """[B, k*n] -> [B, k, n]: row j of a segment is its j-th slice."""
    n = segments.shape[1] // k
    return jnp.stack([segments[:, j * n:(j + 1) * n] for j in range(k)],
                     axis=1)


# A batch of host segments crosses the link as LINEAR rows (PR 43; a
# step that wants ``u8[B, k, n]`` stacks them on the device, the fused
# step does not: PR 51): the TPU packs the second-minor dimension of a
# uint8 array four rows to a 32-bit word, so a host -> device put of
# ``u8[B, k*n]`` (or of any ``u8[.., r, n]``) is packed on the host
# before it crosses, at 5.1-5.7 GiB/s for the stream cells' 128 MiB,
# which capped every one-chip stream cell; a 1-D ``u8[n]`` crosses as it
# lies (ops/rs.py LinearRows, PERF.md section 5). The form's two halves:


def linear_rows(chunk: np.ndarray, k: int) -> tuple:
    """The host half: a C-contiguous ``[B, k*n]`` chunk as its ``B*k``
    rows ``u8[n]``, row ``j`` of segment ``i`` at ``i*k + j`` — views,
    no byte is copied. ``jax.device_put`` takes the tuple in one call."""
    b, size = chunk.shape
    return tuple(chunk.reshape(b * k, size // k))


def stack_rows(segments, k: int) -> jax.Array:
    """The device half where a step wants ``[B, k, n]`` (traced; the
    byte-sharded mesh steps): what :func:`linear_rows` put, stacked in
    ``split_rows``' own style (ops/rs.py ``_stack_rows``); an array goes
    through ``split_rows``. The fused step takes the rows unstacked."""
    if isinstance(segments, (tuple, list)):
        return _stack_rows(segments, k)
    return split_rows(segments, k)


@jax.jit
def merge_rows(shards: jax.Array) -> jax.Array:
    """[B, rows, n] -> [B*rows, n], one segment's rows at a time."""
    b, rows, n = shards.shape

    def body(i, out):
        return jax.lax.dynamic_update_slice(out, shards[i], (i * rows, 0))

    # seeded with segment 0 so that under shard_map the carry varies
    # over the same mesh axes as the shards
    init = jnp.concatenate(
        [shards[0], jnp.zeros(((b - 1) * rows, n), shards.dtype)])
    return jax.lax.fori_loop(1, b, body, init)


# jax.named_scope of the fused encode+tag step (fused_program): its
# operations read "…/cess_fused_step/…" in a device trace's op_name
FUSED_SCOPE = "cess_fused_step"


@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    k: int = constants.REF_K
    m: int = constants.REF_M
    segment_size: int = constants.SEGMENT_SIZE
    # None -> rs.default_strategy(), the platform's; a test names the
    # chip's lowering on a CPU box (ops/rs.py TPUCodec)
    strategy: str | None = None
    sectors: int = podr2.SECTORS  # PoDR2 block geometry

    @property
    def fragment_size(self) -> int:
        assert self.segment_size % self.k == 0
        return self.segment_size // self.k

    @property
    def blocks_per_fragment(self) -> int:
        return podr2.Podr2Params(self.sectors).blocks_for(self.fragment_size)


class StoragePipeline:
    """Batched segment->fragment encode + PoDR2 tag program.

    Unlike TPUCodec (a generic codec front for any erasure pattern),
    this is a single fused forward step meant to be jitted/pjitted as
    one program over a segment batch. The tag step plays the
    reference's TEE role (SURVEY.md §3.2 step "TEE worker computes
    PoDR2 tags for fragments").
    """

    def __init__(self, config: PipelineConfig,
                 podr2_key: podr2.Podr2Key | None = None, engine=None):
        self.config = config
        self.podr2_key = podr2_key or podr2.Podr2Key.generate(0, podr2.Podr2Params(config.sectors))
        strategy = config.strategy or default_strategy()
        self._parity = _MatrixApply(
            gf.cauchy_parity_matrix(config.k, config.m), strategy
        )
        self._fused = None   # lazily-built fused encode+tag program
        # optional submission engine (cess_tpu/serve): when configured,
        # encode/tag submit through its batched queues so concurrent
        # callers coalesce into shared device batches. The direct
        # synchronous path below stays the default (trait-gate
        # philosophy), and engine results are bit-identical to it.
        self.engine = engine
        if engine is not None and engine.codec is not None \
                and (engine.codec.k, engine.codec.m) != (config.k, config.m):
            raise ValueError(
                f"engine codec RS({engine.codec.k},{engine.codec.m}) != "
                f"pipeline RS({config.k},{config.m})")
        if engine is not None and engine.audit is not None \
                and not podr2.keys_equal(engine.audit.key,
                                         self.podr2_key):
            # a mismatched key would tag with DIFFERENT secrets than
            # the direct path — silent protocol divergence
            raise ValueError("engine AuditBackend key differs from "
                             "the pipeline's PoDR2 key")

    def encode_step(self, segments: jnp.ndarray,
                    tenant: str | None = None) -> jnp.ndarray:
        """[B, segment_size] uint8 -> [B, k+m, fragment_size] uint8.

        Data fragments are the k row-slices of the segment (systematic
        code: fragment bytes == segment bytes, hash-stable), parity
        fragments follow. ``tenant`` rides into the engine submit for
        per-tenant accounting (obs/slo.py) — ignored on the direct
        path and free when the engine has no SLO board.
        """
        cfg = self.config
        segments = jnp.asarray(segments)
        b = segments.shape[0]
        data = split_rows(segments, cfg.k)
        with trace.span("pipeline.encode", sys="pipeline", segments=b):
            if self.engine is not None and self.engine.codec is not None:
                # zero-copy handoff: the engine accepts and returns
                # jax.Array, so an already-device-resident batch never
                # round-trips through the host on its way to the codec
                return jnp.asarray(self.engine.encode(data,
                                                      tenant=tenant))
            parity = self._parity(data)
            return jnp.concatenate([data, parity], axis=-2)

    def tag_step(self, fragments: jnp.ndarray,
                 fragment_ids: jnp.ndarray | None = None,
                 tenant: str | None = None) -> jnp.ndarray:
        """[B, k+m, fragment_size] -> PoDR2 tags [B, k+m, blocks, limbs].

        fragment_ids: unique-per-key ids ([B, k+m] or [B, k+m, 2] hash
        word pairs, see podr2.fragment_id_from_hash). The arange default
        is for benches/demos ONLY — production must pass hash-derived
        ids, since id reuse across different data breaks unforgeability.
        """
        fragments = jnp.asarray(fragments)
        b, rows, n = fragments.shape
        flat = merge_rows(fragments)
        if fragment_ids is None:
            fragment_ids = jnp.arange(b * rows, dtype=jnp.int32)
        else:
            fragment_ids = jnp.asarray(fragment_ids)
            fragment_ids = fragment_ids.reshape(
                (b * rows, 2) if fragment_ids.ndim == 3 else (b * rows,))
        with trace.span("pipeline.tag", sys="pipeline", fragments=b * rows):
            if self.engine is not None and self.engine.audit is not None \
                    and fragment_ids.ndim == 2:
                # engine tag class takes (lo, hi) id pairs; the arange
                # bench default stays on the direct path. Device arrays
                # hand off zero-copy (engine returns jax.Array back).
                tags = jnp.asarray(self.engine.tag_fragments(
                    fragment_ids, flat, tenant=tenant))
            else:
                tags = podr2.tag_fragments(self.podr2_key, fragment_ids,
                                           flat)
        return tags.reshape(b, rows, *tags.shape[1:])

    def fused_step(self, data, fragment_ids):
        """The body of the fused encode+tag step: data, the batch as its
        B*k linear rows u8[n] (a tuple: ``linear_rows``, what the
        streaming driver puts) or fragment-major [B, k, n] u8, + ids
        ([B*(k+m)] | [B, k+m] | [B, k+m, 2]) -> {"fragments":
        [B, k+m, n], "tags": [B, k+m, blocks, limbs]}. What the input
        is decides the RS kernel's entry: rows go to it as they lie and
        it writes the codeword fragment-major, in the layout the chip
        keeps the ``"fragments"`` result in (ops/rs.py
        ``codeword_rows``: since PR 51 no stack in front of the kernel
        and no copy behind it; a batch that entry does not take, other
        than 8 or 16 segments, stacks by its shape); an array goes through
        ``codeword`` as before. Either way the kernel writes the data
        rows too, and the tag kernel takes the batch as it is
        (ops/podr2_pallas.py ``tag_fragments_fused``): nothing in
        between regroups rows. Traced by exactly two callers, each
        under ``jax.named_scope(FUSED_SCOPE)``: :meth:`fused_program`
        (one chip) and parallel/mesh.py's sharded stream step on a
        (lanes, 1) mesh (per device, on the rows the host staged for
        it) — one body, so the one-chip and the pooled program cannot
        drift apart."""
        if isinstance(data, (tuple, list)):
            shards = rs.codeword_rows(self._parity, data, self.config.k)
        else:
            shards = self._parity.codeword(data)
        b, rows, _ = shards.shape
        ids = fragment_ids.reshape(
            (b * rows, 2) if fragment_ids.ndim == 3 else (b * rows,))
        tags = podr2.tag_fragments(self.podr2_key, ids, shards)
        return {"fragments": shards,
                "tags": tags.reshape(b, rows, *tags.shape[1:])}

    def rows_direct(self, batch: int, n: int) -> bool:
        """Whether the fused step hands a batch of ``batch`` segments
        held as linear rows ``u8[n]`` to the RS kernel unstacked
        (ops/rs.py ``rows_direct``). The programs built over the step
        (:meth:`fused_program`, parallel/mesh.py's) carry it as their
        ``direct_rows(staged)``, and ``StreamStats.direct_rows`` counts
        by that."""
        return rs.rows_direct(self._parity, batch, n)

    def fused_program(self):
        """The fused encode+tag device program: ONE jitted call, one
        call of each kernel, results bit-identical to encode_step ->
        tag_step. jit caches
        per batch/id shape, so the streaming driver reuses one
        compiled program per bucket.

        Signature: (segments: the batch's B*k linear rows u8[frag]
                    (``linear_rows``, what the streaming driver puts)
                    | [B, segment_size] u8,
                    fragment_ids [B*(k+m)] | [B, k+m] | [B, k+m, 2])
                 -> {"fragments": [B, k+m, frag], "tags": [B, k+m, blocks, limbs]}
        One body: jit traces it once per input form. The rows go to the
        step as they are; the array is split into ``[B, k, n]`` first.
        """
        if self._fused is None:
            cfg = self.config

            def run(segments, fragment_ids):
                # every operation of the step carries the scope in its
                # op_name metadata, so a device trace can tell the
                # fused step's relayouts from anything else's
                with jax.named_scope(FUSED_SCOPE):
                    if not isinstance(segments, (tuple, list)):
                        segments = split_rows(segments, cfg.k)
                    return self.fused_step(segments, fragment_ids)

            self._fused = jax.jit(run)
            self._fused.direct_rows = lambda staged: \
                isinstance(staged, (tuple, list)) and self.rows_direct(
                    len(staged) // cfg.k, staged[0].shape[0])
        return self._fused

    def forward(self, segments: jnp.ndarray,
                fragment_ids: jnp.ndarray | None = None,
                tenant: str | None = None) -> dict[str, jnp.ndarray]:
        """The full pipeline step: encode + tag (the reference's
        OSS-encode + TEE-tag off-chain compute as one device program).

        Without an engine this is the FUSED path: one jitted call, no
        intermediate materialization between encode and tag. With an
        engine the two steps submit through its queues (still
        zero-copy for device-resident inputs), carrying the optional
        per-tenant accounting tag."""
        segments = jnp.asarray(segments)
        with trace.span("pipeline.forward", sys="pipeline",
                        segments=int(segments.shape[0])):
            if self.engine is not None:
                shards = self.encode_step(segments, tenant=tenant)
                tags = self.tag_step(shards, fragment_ids,
                                     tenant=tenant)
                return {"fragments": shards, "tags": tags}
            b = segments.shape[0]
            if fragment_ids is None:
                rows = self.config.k + self.config.m
                fragment_ids = jnp.arange(b * rows, dtype=jnp.int32)
            else:
                fragment_ids = jnp.asarray(fragment_ids)
            return self.fused_program()(segments, fragment_ids)
