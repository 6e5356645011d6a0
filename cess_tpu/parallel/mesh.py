"""Device mesh + sharded storage-pipeline steps.

Axes:
- ``seg``  — the segment batch axis (data parallel; the reference's
  "embarrassingly parallel along the segment axis" structure,
  SURVEY.md §5 long-context note).
- ``byte`` — the intra-fragment byte/block axis. GF column operations
  are columnwise-independent so encode shards cleanly; PoDR2 proof
  aggregation (mu, sigma) reduces over this axis with ``psum`` — the
  audit-path collective.

The data plane runs under ``shard_map`` so the per-device program is
exactly the single-chip program (including Pallas kernels), with
explicit collectives where the math needs them — the idiomatic
JAX/TPU framing of the reference's work-distribution parallelism.

Topology invariance: PoDR2 PRF values are always generated for the
full block range and sliced locally, so tags/proofs are bit-identical
on any mesh shape (protocol invariant).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.pipeline import FUSED_SCOPE, StoragePipeline, merge_rows, \
    stack_rows
from ..obs import trace
from ..ops import pfield as pf, podr2


def make_mesh(devices=None, seg: int | None = None, byte: int = 1) -> Mesh:
    """Build a (seg, byte) mesh over the given (default: all) devices."""
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if seg is None:
        seg = n // byte
    if seg * byte != n:
        raise ValueError(f"mesh {seg}x{byte} != {n} devices")
    arr = np.array(devices).reshape(seg, byte)
    return Mesh(arr, axis_names=("seg", "byte"))


def sharded_pipeline_step(pipeline: StoragePipeline, mesh: Mesh):
    """jit the FULL pipeline step sharded over (seg, byte).

    Per step: RS-encode the segment batch, PoDR2-tag every fragment,
    build an aggregated challenge proof (mu, sigma) per fragment with
    cross-device psum over the sharded block axis, and TEE-verify it.

    Inputs: segments [B, k, n] uint8 (fragment-major; B % mesh.seg == 0,
            n % (byte * BLOCK_BYTES) == 0); fragment ids [B, k+m] int32
            (protocol-level identifiers, sharded over 'seg'); challenge
            (idx [c], nu [c]) from podr2.gen_challenge — a fresh one per
            audit round (replicated traced inputs, NOT baked into the
            program: a fixed challenge would let a prover store only the
            challenged blocks).
    Output: fragments [B, k+m, n] (sharded same as input),
            tags [B, k+m, blocks, 2] (block axis sharded over 'byte';
            trailing axis = the two F_p^2 MAC limbs, replicated),
            ok [B, k+m] bool verification verdicts (replicated).
    """
    cfg = pipeline.config
    key = pipeline.podr2_key
    sectors = key.alpha.shape[0]
    byte_shards = mesh.shape["byte"]
    blocks_total = cfg.blocks_per_fragment
    assert blocks_total % byte_shards == 0, (
        f"{blocks_total} blocks not divisible by byte axis {byte_shards}")
    blocks_local = blocks_total // byte_shards

    def step(data, ids2d, idx, nu):
        b, k, n_local = data.shape
        parity = pipeline._parity(data)
        shards = jnp.concatenate([data, parity], axis=-2)      # [b, k+m, n_local]
        rows = shards.shape[-2]
        frag_ids = ids2d.reshape(b * rows)

        # --- tag: global PRF, local slice --------------------------------
        off = jax.lax.axis_index("byte") * blocks_local
        m = podr2.fragment_to_elems(merge_rows(shards),
                                    sectors)                   # [F, bl_local, s]
        f_all = jax.vmap(
            lambda i: podr2.prf_elems(key.prf_key, i, blocks_total,
                                      key.limbs))(frag_ids)
        f_loc = jax.lax.dynamic_slice_in_dim(f_all, off, blocks_local, axis=1)
        tags = jax.vmap(podr2.tag_from_elems, in_axes=(None, 0, 0))(
            key.alpha, f_loc, m)                               # [F, bl_local, 2]

        # --- prove: masked local partials, psum over 'byte' ---------------
        in_range = (idx >= off) & (idx < off + blocks_local)
        local_idx = jnp.clip(idx - off, 0, blocks_local - 1)
        w = jnp.where(in_range, nu, 0).astype(jnp.uint32)      # [c]
        m_c = jnp.take(m, local_idx, axis=1)                   # [F, c, s]
        t_c = jnp.take(tags, local_idx, axis=1)                # [F, c, 2]
        mu_part = pf.summod(pf.mulmod(w[None, :, None], m_c), axis=1)   # [F, s]
        sg_part = pf.summod(pf.mulmod(w[None, :, None], t_c), axis=1)   # [F, 2]
        mu = pf.psum_mod(mu_part, "byte")
        sigma = pf.psum_mod(sg_part, "byte")

        # --- verify (TEE role) -------------------------------------------
        ok = jax.vmap(
            lambda fa, u, s: podr2.verify_from_f(key.alpha, fa, idx, nu, u, s)
        )(f_all, mu, sigma)

        return (shards, tags.reshape(b, rows, blocks_local, 2),
                ok.reshape(b, rows))

    mapped = jax.shard_map(
        step,
        mesh=mesh,
        in_specs=(P("seg", None, "byte"), P("seg", None), P(), P()),
        out_specs=(P("seg", None, "byte"), P("seg", None, "byte", None),
                   P("seg", None)),
    )
    return jax.jit(mapped)


def sharded_stream_step(pipeline: StoragePipeline, mesh: Mesh,
                        pair_ids: bool = False):
    """The fused encode+tag step (no prove/verify) as ONE shard_map
    program over (seg, byte) — the multi-chip program behind
    :func:`stream_entry`. Tags are bit-identical to the single-device
    fused forward on any mesh shape.

    On a (lanes, 1) mesh — the one a DevicePool builds, the only one a
    user path reaches — each device runs the one-chip step's own body
    (``StoragePipeline.fused_step``: Pallas RS and tag kernels) on its
    segments. With the byte axis sharded, a device holds a slice of
    every fragment: PRF values are generated for the full block range
    and sliced locally (the topology-invariance contract of
    sharded_pipeline_step), and the tags go through the plain jnp MAC.

    In: the batch as linear rows (:func:`stream_entry`'s ``put``): a
    tuple of ``(B / seg) * k`` arrays ``u8[seg * n]`` sharded over
    (seg, byte) together, so that a device holds, as 1-D rows, its own
    byte slice of every fragment of its own segments. On a (lanes, 1)
    mesh a lane hands them to the fused step as they are (since PR 51
    its RS kernel reads them unstacked, 8 or 16 segments a lane; other
    counts stack by their shape); with the byte axis sharded a device
    stacks them to ``[B / seg, k, n / byte]`` itself (models/pipeline.py
    ``stack_rows``). ids [B, k+m] int32 (or [B, k+m, 2] uint32 hash
    word pairs when ``pair_ids``).
    Out: {"fragments" [B, k+m, n], "tags" [B, k+m, blocks, limbs]} —
    the StoragePipeline.forward shape contract.
    """
    cfg = pipeline.config
    key = pipeline.podr2_key
    sectors = key.alpha.shape[0]
    byte_shards = mesh.shape["byte"]
    blocks_total = cfg.blocks_per_fragment
    assert blocks_total % byte_shards == 0, (
        f"{blocks_total} blocks not divisible by byte axis {byte_shards}")
    blocks_local = blocks_total // byte_shards

    def whole_fragments(rows, ids):
        # a lane's rows as they were put: the step's RS kernel reads
        # them unstacked and writes the lane's codeword fragment-major
        # (models/pipeline.py fused_step, PR 51); the result's varying
        # axes follow the rows'
        with jax.named_scope(FUSED_SCOPE):
            out = pipeline.fused_step(rows, ids)
        return out["fragments"], out["tags"]

    def sliced_fragments(rows, ids):
        data = stack_rows(rows, cfg.k)
        b, k, n_local = data.shape
        parity = pipeline._parity(data)
        shards = jnp.concatenate([data, parity], axis=-2)
        rows = shards.shape[-2]
        frag_ids = ids.reshape((b * rows, 2) if pair_ids else (b * rows,))
        off = jax.lax.axis_index("byte") * blocks_local
        m = podr2.fragment_to_elems(merge_rows(shards), sectors)
        f_all = jax.vmap(
            lambda i: podr2.prf_elems(key.prf_key, i, blocks_total,
                                      key.limbs))(frag_ids)
        f_loc = jax.lax.dynamic_slice_in_dim(f_all, off, blocks_local,
                                             axis=1)
        tags = jax.vmap(podr2.tag_from_elems, in_axes=(None, 0, 0))(
            key.alpha, f_loc, m)
        return shards, tags.reshape(b, rows, blocks_local, key.limbs)

    step = whole_fragments if byte_shards == 1 else sliced_fragments
    ids_spec = P("seg", None, None) if pair_ids else P("seg", None)
    mapped = jax.shard_map(
        step,
        mesh=mesh,
        in_specs=(P(("seg", "byte")), ids_spec),   # every row alike
        out_specs=(P("seg", None, "byte"), P("seg", None, "byte", None)),
        # the whole-fragment step has no collective and nothing
        # replicated, so the varying-axes check has nothing to prove
        # there — and Pallas' HLO interpreter (the CPU test mesh)
        # slices a kernel's varying blocks by unvarying grid indices,
        # which that check refuses
        check_vma=byte_shards > 1,
    )
    jitted = jax.jit(mapped)

    def run(rows, ids):
        shards, tags = jitted(rows, ids)
        return {"fragments": shards, "tags": tags}

    # what StreamStats.direct_rows counts by, as fused_program's: a
    # lane holds the n / byte bytes of each of its row slots
    seg = mesh.shape["seg"]
    run.direct_rows = lambda staged: byte_shards == 1 \
        and pipeline.rows_direct(len(staged) // cfg.k,
                                 staged[0].shape[0] // seg)
    return run


def stream_entry(pipeline: StoragePipeline, mesh: Mesh, batch: int,
                 pair_ids: bool = False):
    """Build the (program, put, put_ids) kwargs that point a
    StreamingIngest (cess_tpu/serve/stream.py) at a device mesh:

        ing = StreamingIngest(pipe, batch,
                              **stream_entry(pipe, mesh, batch))

    ``put`` takes the batch as the driver stages it, its ``batch * k``
    linear rows (1-D uint8 views of the staged chunk), and gives every
    device the byte slice of the rows of its own ``batch / seg``
    segments, still linear (a view of a view: no host copy, and no
    packing of a ``u8[.., k, n]`` array on the host before it crosses,
    PERF.md PR 43), in ONE device_put; row slot ``t`` of every device
    together is one global ``u8[seg * n]`` array, the program's
    argument ``t``. ``put_ids`` places the id batch sharded over
    'seg'. The driver itself stays topology-agnostic.
    """
    cfg = pipeline.config
    rows = cfg.k + cfg.m
    program = sharded_stream_step(pipeline, mesh, pair_ids)
    seg, byte = mesh.shape["seg"], mesh.shape["byte"]
    slots = batch // seg * cfg.k           # rows a device holds
    n_local = cfg.fragment_size // byte
    rows_sh = NamedSharding(mesh, P(("seg", "byte")))
    devices = list(mesh.devices.reshape(-1))   # (seg, byte), seg-major
    d = len(devices)
    ids_sh = NamedSharding(
        mesh, P("seg", None, None) if pair_ids else P("seg", None))

    def put(rows_up):
        # the call's three statements, a stage each (children of the
        # driver's ``stream.put``; three a batch, never one a row): on
        # four chips the call is 29 of a batch's 30.6 ms (PERF.md)
        with trace.stage("stream.put.slice"):
            pieces = [rows_up[s * slots + t][b * n_local:(b + 1) * n_local]
                      for t in range(slots)
                      for s in range(seg) for b in range(byte)]
        with trace.stage("stream.put.place"):
            placed = jax.device_put(pieces, devices * slots)
        with trace.stage("stream.put.assemble"):
            return tuple(jax.make_array_from_single_device_arrays(
                (d * n_local,), rows_sh, placed[t * d:(t + 1) * d])
                for t in range(slots))

    def put_ids(ids):
        ids = np.asarray(ids)
        if pair_ids and ids.size != batch * rows * 2:
            # the driver's default (None) ids are a flat scalar arange
            # — there is no sensible pair-shaped default, so demand
            # explicit ids at the layer whose contract is violated
            raise ValueError(
                "stream_entry(pair_ids=True) requires explicit "
                "[N, k+m, 2] fragment_ids passed to run()/ingest()")
        ids = ids.reshape((batch, rows, 2) if pair_ids
                          else (batch, rows))
        return jax.device_put(ids, ids_sh)

    return {"program": program, "put": put, "put_ids": put_ids}


def pool_stream_entry(pipeline: StoragePipeline, devices, batch: int,
                      pair_ids: bool = False):
    """:func:`stream_entry` against a DevicePool's lane devices
    (cess_tpu/serve/pool.py ``stream_entry`` delegates here): an
    (n_lanes, 1) mesh over exactly the pool's devices in lane order,
    so each staged batch fans its segment axis across every lane.
    ``batch`` must be divisible by the lane count (the seg-axis
    sharding constraint); byte axis stays 1 so any
    ``blocks_per_fragment`` divides it. Tags remain bit-identical to
    the single-device fused program — the topology-invariance
    contract above."""
    devices = list(devices)
    if batch % len(devices) != 0:
        raise ValueError(
            f"stream batch {batch} not divisible by the pool's "
            f"{len(devices)} lanes")
    mesh = make_mesh(devices, seg=len(devices), byte=1)
    return stream_entry(pipeline, mesh, batch, pair_ids)

