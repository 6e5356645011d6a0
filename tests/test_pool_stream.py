"""The pooled streamed ingest at RS(4,8) — ``StreamingIngest(pipe, batch,
pool=DevicePool of 4 lanes)``, the four-chip tee-worker host's path
(benchmark cell ``stream-4p8.pool4``) — on four of the suite's virtual
CPU devices:

- against the PLAIN reference: ``ReferenceCodec`` for every fragment and
  the per-fragment jnp ``podr2.tag_fragment`` for every tag, on both MAC
  limb widths, default / scalar / (lo, hi) pair ids, ragged tail;
- the sharded step on a (lanes, 1) mesh runs the one-chip fused step's
  own body (``StoragePipeline.fused_step``) and is bit-identical to
  ``fused_program`` on the same rows; with the byte axis sharded it keeps
  the sliced-PRF jnp body and is still bit-identical;
- ``StreamStats.lanes``: 4 with the pool, 1 without, a gauge that
  attached streams do not sum;
- a pooled stream long enough for the driver's gate (PR 50: a put waits
  for the sharded rows of the put two before it): the one-chip stream's
  bits, and the counters a batch counts, as before;
- the Pallas tag kernel types its output over the mesh axes its data
  varies over, so it traces under a checked ``shard_map``.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from cess_tpu.models.pipeline import PipelineConfig, StoragePipeline, \
    linear_rows
from cess_tpu.ops import podr2, podr2_pallas, target
from cess_tpu.ops.rs_ref import ReferenceCodec
from cess_tpu.parallel.mesh import make_mesh, stream_entry
from cess_tpu.serve import make_engine
from cess_tpu.serve.pool import DevicePool
from cess_tpu.serve.stream import StreamingIngest

K, M = 4, 8
ROWS = K + M
FRAG = 2048                 # 4 PoDR2 blocks of 512 B per fragment
SEG = K * FRAG
LANES = 4
BATCH = 8                   # 2 segments a lane


def rnd(shape, seed=0, dtype=np.uint8):
    rng = np.random.default_rng(seed)
    return rng.integers(0, np.iinfo(dtype).max, shape, dtype=dtype)


def make_pipe(limbs=2, strategy=None):
    key = podr2.Podr2Key.generate(27, podr2.Podr2Params(limbs=limbs))
    return StoragePipeline(PipelineConfig(k=K, m=M, segment_size=SEG,
                                          strategy=strategy),
                           podr2_key=key)


def sharded_step(pipe, mesh, segs, ids, pair=False):
    """One batch through a mesh's stream entry as the driver stages it:
    the chunk's linear row views through the entry's ``put``, then its
    program (parallel/mesh.py sharded_stream_step)."""
    entry = stream_entry(pipe, mesh, len(segs), pair_ids=pair)
    return entry["program"](entry["put"](linear_rows(segs, K)),
                            entry["put_ids"](ids))


def plain_reference(pipe, segs, ids):
    """Fragments by the NumPy codec, tags one fragment at a time through
    the plain jnp MAC (no kernel, no batching, no mesh), at the
    pipeline's own geometry (tests/test_stream.py holds the one-chip
    fused program to it too)."""
    k, rows = pipe.config.k, pipe.config.k + pipe.config.m
    codec = ReferenceCodec(k, pipe.config.m)
    frags = np.stack([codec.encode(s.reshape(k, -1)) for s in segs])
    flat_ids = ids.reshape(len(segs) * rows, *ids.shape[2:])
    tags = np.stack([
        np.asarray(podr2.tag_fragment(pipe.podr2_key, fid, frag))
        for fid, frag in zip(flat_ids, frags.reshape(len(flat_ids), -1))])
    return frags, tags.reshape(len(segs), rows, *tags.shape[1:])


@pytest.mark.parametrize("n_segments", [16, 11], ids=["even", "ragged"])
@pytest.mark.parametrize("id_kind", ["default", "scalar", "pair"])
@pytest.mark.parametrize("limbs", [2, 3])
def test_pooled_stream_matches_plain_reference(limbs, id_kind, n_segments):
    pipe = make_pipe(limbs)
    segs = rnd((n_segments, SEG), 100 + limbs)
    pool = DevicePool(n=LANES)
    if id_kind == "pair":
        # (lo, hi) hash words: the pool's entry built for pair ids
        ids = rnd((n_segments, ROWS, 2), 7, np.uint32)
        ing = StreamingIngest(pipe, BATCH, **pool.stream_entry(
            pipe, BATCH, pair_ids=True))
    else:
        ing = StreamingIngest(pipe, BATCH, pool=pool)
        ids = np.arange(n_segments * ROWS, dtype=np.int32).reshape(
            n_segments, ROWS)               # the driver's default
        if id_kind == "scalar":
            ids = ids[::-1] * 3 + 1
    out = ing.ingest(segs, None if id_kind == "default" else ids)
    want_frags, want_tags = plain_reference(pipe, segs, ids)
    assert out["tags"].shape == (n_segments, ROWS, FRAG // 512, limbs)
    assert np.array_equal(np.asarray(out["fragments"]), want_frags)
    assert np.array_equal(np.asarray(out["tags"]), want_tags)
    assert ing.stats.lanes == LANES
    assert ing.stats.padded_segments == -n_segments % BATCH


@pytest.mark.parametrize("depth", [1, 2])
def test_pooled_stream_past_the_gate_counts_as_one_chip_does(depth):
    """Five batches, the last ragged: from the third put on (depth 2)
    a put waits for the sharded rows of the put two before it. Results
    and every counter of
    a batch are the one-chip stream's; only ``lanes`` differs."""
    pipe = make_pipe()
    segs = rnd((4 * BATCH + 3, SEG), 14)
    one = StreamingIngest(pipe, BATCH, depth=depth)
    pooled = StreamingIngest(pipe, BATCH, depth=depth,
                             pool=DevicePool(n=LANES))
    want, got = one.ingest(segs), pooled.ingest(segs)
    for name in ("fragments", "tags"):
        assert np.array_equal(np.asarray(got[name]),
                              np.asarray(want[name])), name
    a, b = one.stats.raw(), pooled.stats.raw()
    counted = ("batches", "segments", "padded_segments", "bytes_in",
               "bytes_out", "linear_puts", "put_arrays")
    assert {k: b[k] for k in counted} == {k: a[k] for k in counted}
    assert b["linear_puts"] == b["batches"] == 5
    assert b["put_arrays"] == 5 * BATCH * K
    assert (a["lanes"], b["lanes"]) == (1, LANES)
    # at depth 1 the put two before is out with its result: no gate
    assert (b["gate_s"] > 0) == (depth > 1) and b["stall_s"] > 0


@pytest.mark.parametrize("seg,byte,strategy", [
    (4, 1, None), (2, 2, None), (1, 4, None),
    # the chip's lowering (interpret mode) where the pool's lanes run it:
    # per device the RS kernel writes the codeword and the tag kernel
    # takes the lane's fragments in their batch's shape (PR 44), batch
    # 2 a lane and batch 1 (the group degrades to 1)
    (4, 1, "pallas"), (8, 1, "pallas")])
@pytest.mark.parametrize("pair", [False, True], ids=["scalar", "pair"])
def test_sharded_step_bit_identical_to_fused_program(seg, byte, strategy,
                                                     pair):
    """byte == 1 traces the fused step's own body per device; byte > 1
    keeps the sliced-PRF jnp body. Same bits either way, and the same
    as the plain reference."""
    pipe = make_pipe(strategy=strategy)
    mesh = make_mesh(jax.devices()[:seg * byte], seg=seg, byte=byte)
    segs = rnd((BATCH, SEG), 5)
    ids = rnd((BATCH, ROWS, 2), 6, np.uint32) if pair else \
        rnd((BATCH, ROWS), 6, np.uint32).astype(np.int32)
    want = pipe.fused_program()(jnp.asarray(segs), jnp.asarray(ids))
    got = sharded_step(pipe, mesh, segs, ids, pair)
    for name in ("fragments", "tags"):
        assert np.array_equal(np.asarray(got[name]),
                              np.asarray(want[name])), name
    want_frags, want_tags = plain_reference(pipe, segs, ids)
    assert np.array_equal(np.asarray(got["fragments"]), want_frags)
    assert np.array_equal(np.asarray(got["tags"]), want_tags)


def test_pooled_step_traces_the_fused_steps_body(monkeypatch):
    """One body for one chip and for the pool: the (lanes, 1) step calls
    StoragePipeline.fused_step (as fused_program does), the byte-sharded
    step does not. Since PR 51 a lane hands the step its rows as they
    were put, unstacked: the RS kernel's entry follows from what the
    step is handed, and no ``[B, k, n]`` array is made in front of it."""
    pipe = make_pipe()
    calls = []
    real = StoragePipeline.fused_step

    def counting(self, data, ids):
        calls.append([r.shape for r in data]
                     if isinstance(data, (tuple, list)) else data.shape)
        return real(self, data, ids)

    monkeypatch.setattr(StoragePipeline, "fused_step", counting)
    segs = rnd((BATCH, SEG), 8)
    ids = np.arange(BATCH * ROWS, dtype=np.int32).reshape(BATCH, ROWS)
    sharded_step(pipe, make_mesh(jax.devices()[:4], 4, 1), segs, ids)
    assert calls == [[(FRAG,)] * (BATCH // 4 * K)]    # per-device rows
    pipe.fused_program()(jnp.asarray(segs), jnp.asarray(ids))
    assert calls[1:] == [(BATCH, K, FRAG)]
    pipe.fused_program()(jax.device_put(linear_rows(segs, K)),
                         jnp.asarray(ids))
    assert calls[2:] == [[(FRAG,)] * (BATCH * K)]     # the rows, unstacked
    sharded_step(pipe, make_mesh(jax.devices()[:4], 2, 2), segs, ids)
    assert len(calls) == 3


GEOMETRIES = {"rs4p8": (4, 8), "rs2p1": (2, 1), "rs10p4": (10, 4)}


@pytest.mark.parametrize("id_kind", ["default", "pair"])
@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_pooled_lanes_hand_their_rows_to_the_rs_kernel_unstacked(
        geometry, id_kind):
    """The four-lane mesh under the chip's lowering (interpret mode), 8
    segments a lane as in ``stream-4p8.pool4``: every lane's program
    takes the rows entry (PR 51: ``direct_rows`` counts every batch),
    and fragments and tags equal the plain reference's and the one-chip
    array form's byte for byte, ragged tail included. Two segments a
    lane fall back to the stack by their shape, same bits."""
    k, m = GEOMETRIES[geometry]
    rows = k + m
    pipe = StoragePipeline(
        PipelineConfig(k=k, m=m, segment_size=k * 1024, strategy="pallas"),
        podr2_key=podr2.Podr2Key.generate(51))
    n_segments = 32 + 5
    segs = rnd((n_segments, k * 1024), 510)
    pair = id_kind == "pair"
    ids = rnd((n_segments, rows, 2), 511, np.uint32) if pair else \
        np.arange(n_segments * rows, dtype=np.int32).reshape(-1, rows)
    pool = DevicePool(n=LANES)
    ing = StreamingIngest(pipe, 32, **pool.stream_entry(pipe, 32,
                                                        pair_ids=pair))
    got = ing.ingest(segs, ids if pair else None)
    want_frags, want_tags = plain_reference(pipe, segs, ids)
    assert np.array_equal(np.asarray(got["fragments"]), want_frags)
    assert np.array_equal(np.asarray(got["tags"]), want_tags)
    array = pipe.fused_program()(jnp.asarray(segs[:8]),
                                 jnp.asarray(ids[:8]))
    for name in ("fragments", "tags"):
        assert np.array_equal(np.asarray(got[name][:8]),
                              np.asarray(array[name])), name
    st = ing.stats
    assert st.direct_rows == st.linear_puts == st.batches == 2
    assert st.lanes == LANES
    # two segments a lane: no multiple of 8, so the lanes stack
    small = StreamingIngest(pipe, BATCH, **pool.stream_entry(
        pipe, BATCH, pair_ids=pair))
    out = small.ingest(segs[:BATCH], ids[:BATCH] if pair else None)
    assert np.array_equal(np.asarray(out["fragments"]), want_frags[:BATCH])
    assert np.array_equal(np.asarray(out["tags"]), want_tags[:BATCH])
    assert small.stats.direct_rows == 0 and small.stats.batches == 1


def test_the_pooled_put_is_three_child_stages_a_batch():
    """The pooled put's three calls (the row views, the one
    ``device_put``, the global arrays) are three stages, children of the
    driver's ``stream.put`` under an armed tracer: three a batch, never
    one a row, each put's three in the call's order."""
    from cess_tpu import obs

    pipe = make_pipe()
    ing = StreamingIngest(pipe, BATCH, pool=DevicePool(n=LANES))
    tracer = obs.Tracer()
    with obs.armed(tracer):
        ing.ingest(rnd((3 * BATCH, SEG), 61))
    spans = tracer.finished()
    puts = [s for s in spans if s["name"] == "stream.put"]
    assert len(puts) == 3
    parts = ["stream.put.slice", "stream.put.place", "stream.put.assemble"]
    for put in puts:
        mine = sorted((s for s in spans
                       if s["parent_id"] == put["span_id"]),
                      key=lambda s: s["span_id"])
        assert [s["name"] for s in mine] == parts
        assert sum(s["dur_s"] for s in mine) <= put["dur_s"] + 1e-4
    assert sum(s["name"].startswith("stream.put.") for s in spans) == 9


def test_stream_stats_lanes_is_a_gauge():
    pipe = make_pipe()
    segs = rnd((BATCH, SEG), 9)
    one = StreamingIngest(pipe, BATCH)
    assert one.stats.lanes == 1 and one.stats.raw()["lanes"] == 1
    one.ingest(segs)
    assert one.stats.lanes == 1
    eng = make_engine(K, M, rs_backend="jax")
    try:
        pooled = StreamingIngest(pipe, BATCH, pool=DevicePool(n=LANES),
                                 engine=eng)
        pooled.ingest(segs)
        assert pooled.stats.lanes == LANES
        assert pooled.stats.snapshot()["lanes"] == LANES
        assert pooled.stats.metrics()["cess_engine_stream_lanes"] == LANES
        # two attached streams: counters add up, the gauge does not
        second = StreamingIngest(pipe, BATCH, pool=DevicePool(n=2),
                                 engine=eng)
        second.ingest(segs)
        merged = eng.stats_metrics()
        assert merged["cess_engine_stream_lanes"] == LANES
        assert merged["cess_engine_stream_batches"] == 2
    finally:
        eng.close()


def test_tag_kernel_types_its_output_under_checked_shard_map(monkeypatch):
    """``_tags_3d``'s out_shape carries the data operand's varying axes.
    Traced (not run) with the kernel lowered as for the TPU: the
    interpreter's own grid slicing is refused by the check, for a
    reason that has nothing to do with the kernel's typing."""
    monkeypatch.setattr(target, "interpret", lambda: False)
    jax.clear_caches()
    try:
        pipe = make_pipe()
        mesh = make_mesh(jax.devices()[:4], seg=4, byte=1)
        ids = jnp.arange(8, dtype=jnp.int32)
        frags = jnp.asarray(rnd((8, FRAG), 11))

        def tag(i, f):
            out = podr2.tag_fragments(pipe.podr2_key, i, f)
            assert jax.typeof(out).vma == frozenset({"seg"})
            return out

        mapped = jax.shard_map(tag, mesh=mesh, in_specs=(P("seg"), P("seg")),
                               out_specs=P("seg"))        # check_vma on
        text = str(jax.make_jaxpr(mapped)(ids, frags))
        assert podr2_pallas.KERNEL_NAME in text
    finally:
        jax.clear_caches()      # no TPU-lowered trace serves a later test
