"""Streamed ingest (serve/stream.py) + fused forward (models/pipeline):

- the fused encode+tag forward is bit-identical to the separate
  encode_step -> tag_step path;
- the double-buffered streaming driver is bit-identical to the direct
  path on BOTH MAC limb widths (Podr2Params limbs=2/3), including the
  ragged final batch and explicit hash-pair ids;
- the sharded mesh stream entry matches the single-device fused path
  (topology invariance extends to the streaming program);
- stream stage counters are exact and export through the engine's
  cess_engine_stream_* metrics surface;
- a staged batch crosses the link linear (PR 43): the put seam is handed
  1-D uint8 views of the staged chunk (no host copy), the fused program
  stacks them on the device, and the result equals ``forward()`` on
  ``[B, segment_size]`` byte for byte at RS(2,1) and RS(4,8);
- the fused program keeps the fragments' shape between its two kernels
  (PR 44: the RS kernel writes the codeword, the tag kernel takes the
  batch as it is) and is still bit-identical to ``encode_step`` ->
  ``tag_step`` and to the NumPy codec + the plain per-fragment MAC, at
  both geometries, batch 1, an odd batch, pair ids, both input forms,
  with the chip's ``pallas`` lowering (interpret mode) and the CPU's;
- the driver's order (PR 50): a batch's put is asked for before the
  wait for the oldest result, once the put two before it has arrived
  (``gate_s``, no part of ``stall_s``), its program directly behind its
  own put; ``depth`` results and ``depth + 1`` batches' rows at most; a
  fault at either seam, or a consumer that stops, leaves every span
  closed and the counters consistent;
- the rows entry (PR 51): under the chip's lowering a batch of 8 or 16
  goes to the RS kernel as the put's linear rows and comes back
  fragment-major, bit-identical to the array form and the reference at
  the three geometries; other batches and the CPU's lowering stack;
  ``StreamStats.direct_rows`` counts the batches that went unstacked;
- a stage keeps its distribution and its worst cases (PR 54): the five
  stages of a batch go through one sink into ``StreamStats.stages``, on
  the program's one ladder, under one ``seq``; a wait over
  ``LONG_WAIT_S`` lands in ``long_waits`` and the flight journal with
  what was in flight, a short one builds nothing; ``wall_s`` is
  ``stage_s + gate_s + h2d_s + stall_s + dispatch_s + consumer_s`` and
  a remainder;
- the repair warm path (rs.py warm_reconstruct / engine.warm_repair)
  returns byte-exact reconstructions through pre-compiled programs.
"""
import time
import weakref

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from cess_tpu import obs
from cess_tpu.models.pipeline import PipelineConfig, StoragePipeline, \
    linear_rows
from cess_tpu.obs import flight
from cess_tpu.obs.slo import SloBoard, SloTarget
from cess_tpu.ops import podr2, rs
from cess_tpu.resilience import FaultInjected, FaultPlan, FaultSpec, faults
from cess_tpu.serve import AdmissionPolicy, make_engine
from cess_tpu.serve import stream as stream_mod
from cess_tpu.serve.stats import StreamStats
from cess_tpu.serve.stream import StreamingIngest, _rebatch
from test_pool_stream import plain_reference

K, M = 2, 1
FRAG = 1024                 # 2 PoDR2 blocks per fragment
SEG = K * FRAG
ROWS = K + M


def rnd(shape, seed=0, dtype=np.uint8):
    rng = np.random.default_rng(seed)
    return rng.integers(0, np.iinfo(dtype).max, shape, dtype=dtype)


def make_pipe(limbs=2):
    params = podr2.Podr2Params(limbs=limbs)
    key = podr2.Podr2Key.generate(31, params)
    return StoragePipeline(PipelineConfig(k=K, m=M, segment_size=SEG),
                           podr2_key=key)


# -- fused forward ----------------------------------------------------------

def test_fused_forward_matches_per_step():
    pipe = make_pipe()
    segs = rnd((4, SEG), 1)
    out = pipe.forward(segs)
    shards = pipe.encode_step(segs)
    tags = pipe.tag_step(shards)
    assert np.array_equal(np.asarray(out["fragments"]),
                          np.asarray(shards))
    assert np.array_equal(np.asarray(out["tags"]), np.asarray(tags))


def test_fused_forward_explicit_pair_ids():
    pipe = make_pipe()
    segs = rnd((3, SEG), 2)
    ids = rnd((3, ROWS, 2), 3, dtype=np.uint32)
    out = pipe.forward(segs, fragment_ids=ids)
    shards = pipe.encode_step(segs)
    tags = pipe.tag_step(shards, ids)
    assert np.array_equal(np.asarray(out["tags"]), np.asarray(tags))


# -- streamed driver vs direct ---------------------------------------------

@pytest.mark.parametrize("limbs", [2, 3])
def test_stream_bit_identical_both_limb_widths(limbs):
    """7 segments through batches of 3: two full batches plus a ragged
    1-segment tail, default (global arange) ids — bit-identical to the
    direct per-step path over the whole array at once."""
    pipe = make_pipe(limbs)
    segs = rnd((7, SEG), 10 + limbs)
    shards = pipe.encode_step(segs)
    tags = pipe.tag_step(shards)            # arange over all 7*ROWS
    ing = StreamingIngest(pipe, 3)
    out = ing.ingest(segs)
    assert out["tags"].shape[-1] == limbs
    assert np.array_equal(np.asarray(out["fragments"]),
                          np.asarray(shards))
    assert np.array_equal(np.asarray(out["tags"]), np.asarray(tags))
    st = ing.stats
    assert st.batches == 3
    assert st.segments == 7
    assert st.padded_segments == 2          # tail padded 1 -> 3
    assert st.bytes_in == 7 * SEG
    # what came out, pad rows not counted: fragments and tags
    assert st.bytes_out == 7 * ROWS * (FRAG + FRAG // 512 * limbs * 4)


def test_stream_explicit_ids_and_device_results():
    pipe = make_pipe()
    segs = rnd((5, SEG), 20)
    ids = rnd((5, ROWS, 2), 21, dtype=np.uint32)
    outs = list(StreamingIngest(pipe, 2).run(segs, fragment_ids=ids))
    assert [o["rows"] for o in outs] == [2, 2, 1]   # ragged tail sliced
    for o in outs:
        assert isinstance(o["tags"], jax.Array)     # stays on device
    got = np.concatenate([np.asarray(o["tags"]) for o in outs])
    want = np.asarray(pipe.tag_step(pipe.encode_step(segs), ids))
    assert np.array_equal(got, want)


def test_stream_iterable_source_rebatches():
    """A chunked source (the network-receive shape) re-batches into
    the compiled batch size; results identical to the array source."""
    pipe = make_pipe()
    segs = rnd((6, SEG), 30)
    pieces = [segs[0:1], segs[1:4], segs[4:6]]      # ragged chunks
    got = StreamingIngest(pipe, 4).ingest(iter(pieces))
    want = StreamingIngest(pipe, 4).ingest(segs)
    assert np.array_equal(np.asarray(got["tags"]),
                          np.asarray(want["tags"]))
    # the rebatcher itself: 6 rows into 4+2
    sizes = [c.shape[0] for c in _rebatch(iter(pieces), 4)]
    assert sizes == [4, 2]


def test_stream_stats_export_through_engine_metrics():
    pipe = make_pipe()
    eng = make_engine(K, M, policy=AdmissionPolicy(max_delay=0.005))
    try:
        ing = StreamingIngest(pipe, 2, engine=eng)
        for _ in ing.run(rnd((4, SEG), 40)):
            pass
        m = eng.stats_metrics()
        assert m["cess_engine_stream_batches"] == 2
        assert m["cess_engine_stream_segments"] == 4
        assert m["cess_engine_stream_bytes_in"] == 4 * SEG
        assert m["cess_engine_stream_bytes_out"] \
            == 4 * ROWS * (FRAG + FRAG // 512 * 2 * 4)
        assert "cess_engine_stream_stall_frac" in m
        snap = eng.stats_snapshot()
        assert snap["streams"][0]["batches"] == 2
    finally:
        eng.close()


def test_stream_rejects_bad_shapes():
    pipe = make_pipe()
    with pytest.raises(ValueError, match="batch"):
        StreamingIngest(pipe, 0)
    ing = StreamingIngest(pipe, 2)
    with pytest.raises(ValueError, match="rows"):
        list(ing.run(rnd((3, SEG), 1), fragment_ids=rnd((2, ROWS, 2), 2,
                                                        np.uint32)))
    with pytest.raises(ValueError, match="empty"):
        ing.ingest(np.zeros((0, SEG), np.uint8))
    # explicit ids demand an array source — a chunked/iterator source
    # cannot line up with a pre-shaped id array (loud, not an opaque
    # numpy coercion error)
    segs = rnd((4, SEG), 3)
    with pytest.raises(ValueError, match="array segment source"):
        list(ing.run(iter([segs[:2], segs[2:]]),
                     fragment_ids=rnd((4, ROWS, 2), 4, np.uint32)))


def test_stream_detach_stops_metric_contribution():
    """detach() removes the driver's counters from the engine's merged
    gauges (idempotent); a second attached driver keeps reporting."""
    pipe = make_pipe()
    eng = make_engine(K, M, policy=AdmissionPolicy(max_delay=0.005))
    try:
        a = StreamingIngest(pipe, 2, engine=eng)
        for _ in a.run(rnd((2, SEG), 70)):
            pass
        b = StreamingIngest(pipe, 2, engine=eng)
        for _ in b.run(rnd((4, SEG), 71)):
            pass
        assert eng.stats_metrics()["cess_engine_stream_batches"] == 3
        a.detach()
        a.detach()                                  # idempotent
        assert eng.stats_metrics()["cess_engine_stream_batches"] == 2
        b.detach()
        assert "cess_engine_stream_batches" not in eng.stats_metrics()
    finally:
        eng.close()


# -- the linear way up (PR 43) ----------------------------------------------

GEOMETRIES = {"rs2p1": (2, 1), "rs4p8": (4, 8), "rs10p4": (10, 4)}
# the lowering of the RS apply: the backend's default (``gather`` on the
# CPU: parity + concatenate) and the chip's (``pallas``, here in
# interpret mode: the kernel writes the codeword, PR 44)
STRATEGIES = [pytest.param(None, id="default"),
              pytest.param("pallas", id="pallas")]


def geometry_pipe(name, frag=FRAG, strategy=None):
    k, m = GEOMETRIES[name]
    return StoragePipeline(PipelineConfig(k=k, m=m, segment_size=k * frag,
                                          strategy=strategy),
                           podr2_key=podr2.Podr2Key.generate(43))


@pytest.mark.parametrize("strategy", STRATEGIES)
@pytest.mark.parametrize("id_kind", ["scalars", "pairs"])
@pytest.mark.parametrize("batch", [1, 3, 4], ids=["b1", "b3-odd", "b4"])
@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_fused_program_equals_the_steps_and_the_reference(
        geometry, batch, id_kind, strategy):
    """One program, the fragments in one shape from the RS kernel to
    the tag kernel (PR 44), against the two steps it fuses and against
    the plain reference: every byte and every tag word, and the
    systematic rows are the user's bytes."""
    pipe = geometry_pipe(geometry, strategy=strategy)
    cfg = pipe.config
    rows = cfg.k + cfg.m
    segs = rnd((batch, cfg.segment_size), 440 + batch)
    ids = rnd((batch, rows, 2), 441, np.uint32) if id_kind == "pairs" \
        else rnd((batch, rows), 442, np.uint32).astype(np.int32)
    got = pipe.fused_program()(jax.device_put(linear_rows(segs, cfg.k)),
                               jnp.asarray(ids))
    assert got["fragments"].shape == (batch, rows, FRAG)
    assert got["tags"].shape == (batch, rows, FRAG // 512, 2)
    frags = np.asarray(got["fragments"])
    assert np.array_equal(frags[:, :cfg.k].reshape(batch, -1), segs)
    shards = pipe.encode_step(segs)
    assert np.array_equal(frags, np.asarray(shards))
    assert np.array_equal(np.asarray(got["tags"]),
                          np.asarray(pipe.tag_step(shards, ids)))
    want_frags, want_tags = plain_reference(pipe, segs, ids)
    assert np.array_equal(frags, want_frags)
    assert np.array_equal(np.asarray(got["tags"]), want_tags)


@pytest.mark.parametrize("strategy", STRATEGIES)
@pytest.mark.parametrize("id_kind", ["default", "pairs"])
@pytest.mark.parametrize("n_segments", [6, 7], ids=["even", "ragged"])
@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_linear_path_equals_forward(geometry, n_segments, id_kind,
                                    strategy):
    """The driver's way (linear row views up, stacked on the device)
    against ``forward()`` on the ``[N, segment_size]`` array: the same
    fragments and tags, byte for byte."""
    pipe = geometry_pipe(geometry, strategy=strategy)
    cfg = pipe.config
    rows = cfg.k + cfg.m
    segs = rnd((n_segments, cfg.segment_size), 430 + n_segments)
    ids = rnd((n_segments, rows, 2), 431, np.uint32) \
        if id_kind == "pairs" else None
    ing = StreamingIngest(pipe, 3)
    got = ing.ingest(segs, fragment_ids=ids)
    want = pipe.forward(segs, fragment_ids=ids)
    assert got["fragments"].shape == (n_segments, rows, FRAG)
    for name in ("fragments", "tags"):
        assert np.array_equal(np.asarray(got[name]),
                              np.asarray(want[name])), name
    st = ing.stats
    # the code's stored bytes per user byte as a count (PR 47): the
    # fragments' share of ``bytes_out`` over ``bytes_in`` is (k + m) / k
    tag_bytes = n_segments * rows * (FRAG // 512) * 2 * 4
    assert (st.bytes_out - tag_bytes) * cfg.k == st.bytes_in * rows
    assert st.linear_puts == st.batches == -(-n_segments // 3)
    assert st.put_arrays == st.batches * 3 * cfg.k
    assert st.raw()["linear_puts"] == st.batches
    assert st.metrics()["cess_engine_stream_put_arrays"] == st.put_arrays


@pytest.mark.parametrize("strategy", STRATEGIES)
@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_fused_program_takes_both_input_forms(geometry, strategy):
    """One body: the linear rows the driver puts and the ``[B, S]``
    array ``forward`` passes give the same bits."""
    pipe = geometry_pipe(geometry, strategy=strategy)
    cfg = pipe.config
    segs = rnd((4, cfg.segment_size), 432)
    ids = jnp.arange(4 * (cfg.k + cfg.m), dtype=jnp.int32)
    rows_up = linear_rows(segs, cfg.k)
    assert len(rows_up) == 4 * cfg.k
    linear = pipe.fused_program()(jax.device_put(rows_up), ids)
    array = pipe.fused_program()(jnp.asarray(segs), ids)
    assert np.array_equal(np.asarray(linear["fragments"][:, :cfg.k])
                          .reshape(4, -1), segs)       # systematic rows
    for name in ("fragments", "tags"):
        assert np.array_equal(np.asarray(linear[name]),
                              np.asarray(array[name])), name


@pytest.fixture(scope="module")
def bench_ref():
    """The benchmark's frozen plain references (benchmark/reference),
    as benchmark/run.py sees them: what decides a cell's ``correct``."""
    import importlib
    import os
    import sys

    root = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    sys.path.insert(0, root)
    try:
        return (importlib.import_module("reference.rs_ref"),
                importlib.import_module("reference.podr2_ref"))
    finally:
        sys.path.remove(root)


@pytest.mark.parametrize("form", ["linear", "array"])
@pytest.mark.parametrize("strategy", STRATEGIES)
@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_fused_program_against_the_benchmarks_reference(
        bench_ref, geometry, strategy, form):
    """The comparison that decides ``correct`` in the stream cells
    (benchmark/traffic/stream.py ``check``), at a small size: a batch
    of the fused program, from the driver's linear rows and from
    ``[B, segment_size]``, byte for byte against ``ReferenceCodec.encode``
    and word for word against ``podr2_ref.tag_fragment`` under the
    stream's default ids, the systematic rows the user's bytes."""
    rs_ref, podr2_ref = bench_ref
    pipe = geometry_pipe(geometry, strategy=strategy)
    cfg = pipe.config
    rows = cfg.k + cfg.m
    segs = rnd((2, cfg.segment_size), 470)
    ids = jnp.arange(2 * rows, dtype=jnp.int32)
    staged = jax.device_put(linear_rows(segs, cfg.k)) if form == "linear" \
        else jnp.asarray(segs)
    got = pipe.fused_program()(staged, ids)
    frags, tags = np.asarray(got["fragments"]), np.asarray(got["tags"])
    assert np.array_equal(frags[:, :cfg.k].reshape(2, -1), segs)
    codec = rs_ref.ReferenceCodec(cfg.k, cfg.m)
    key = podr2_ref.generate_key(43)
    for s in range(2):
        assert np.array_equal(frags[s],
                              codec.encode(segs[s].reshape(cfg.k, -1)))
        for row in (0, cfg.k - 1, cfg.k, rows - 1):
            want = podr2_ref.tag_fragment(key, np.int32(s * rows + row),
                                          frags[s, row])
            assert np.array_equal(tags[s, row], np.asarray(want)), (s, row)


@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_put_seam_receives_views_of_the_staged_chunk(geometry):
    """No host copy on the way up: what the put seam is handed are 1-D
    uint8 views into the caller's own array (a full batch of an array
    source is staged where it lies); the ragged tail's pad is the one
    copy, made before the views as before."""
    pipe = geometry_pipe(geometry)
    cfg = pipe.config
    segs = rnd((5, cfg.segment_size), 433)
    seen = []

    def put(x):
        seen.append(x)
        return jax.device_put(x)

    ing = StreamingIngest(pipe, 2, put=put)
    got = ing.ingest(segs)
    want = pipe.forward(segs)
    assert np.array_equal(np.asarray(got["tags"]), np.asarray(want["tags"]))
    batches = [x for x in seen if isinstance(x, tuple)]    # not the ids
    assert len(batches) == 3
    for i, rows_up in enumerate(batches):
        assert len(rows_up) == 2 * cfg.k
        for j, row in enumerate(rows_up):
            assert isinstance(row, np.ndarray) and row.ndim == 1
            assert row.dtype == np.uint8 and row.shape == (FRAG,)
            assert row.flags.c_contiguous
            assert np.shares_memory(row, segs) == (i < 2)   # tail: padded
        # the views tile the chunk in order: row j of segment i at i*k+j
        flat = np.concatenate(rows_up)
        real = segs[2 * i:2 * i + 2].reshape(-1)
        assert np.array_equal(flat[:real.size], real)
        assert not flat[real.size:].any()
    assert ing.stats.linear_puts == ing.stats.batches == 3


def _spanned(log, name, fn):
    """benchmark/spans.py ``Spans.wrap``'s shape: a plain closure."""
    def call(*args, **kw):
        log.append(name)
        return fn(*args, **kw)
    return call


def _flip_parity(program):
    """benchmark/traffic/stream.py's control: a program around the
    program, handed whatever the put returned."""
    def broken(dev, ids):
        out = dict(program(dev, ids))
        f = out["fragments"]
        out["fragments"] = f.at[:, -1, 0].set(f[:, -1, 0] ^ 1)
        return out
    return broken


@pytest.mark.parametrize("fault", [False, True], ids=["sound", "fault"])
@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_the_benchmarks_seams_drive_the_linear_path(geometry, fault):
    """A traced benchmark run hands the driver ``program=wrap(
    fused_program())`` and ``put=wrap(jax.device_put)``, its controls a
    program around ``program(dev, ids)``: the same code runs, linear."""
    pipe = geometry_pipe(geometry)
    segs = rnd((4, pipe.config.segment_size), 434)
    log = []
    program = pipe.fused_program()
    if fault:
        program = _flip_parity(program)
    ing = StreamingIngest(
        pipe, 2, program=_spanned(log, "stream.dispatch", program),
        put=_spanned(log, "stream.device_put", jax.device_put))
    got = ing.ingest(segs)
    want = pipe.forward(segs)
    assert log == ["stream.device_put", "stream.device_put",
                   "stream.dispatch"] * 2               # rows, ids, call
    assert np.array_equal(np.asarray(got["tags"]), np.asarray(want["tags"]))
    differ = np.asarray(got["fragments"]) != np.asarray(want["fragments"])
    assert differ.sum() == (4 if fault else 0)   # a parity byte a segment
    assert ing.stats.linear_puts == ing.stats.batches == 2


# -- the rows entry (PR 51) -------------------------------------------------

@pytest.mark.parametrize("id_kind", ["scalars", "pairs"])
@pytest.mark.parametrize("batch,direct", [(8, True), (16, True), (4, False)],
                         ids=["b8", "b16", "b4-stacks"])
@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_rows_entry_equals_the_array_form_and_the_reference(
        geometry, batch, direct, id_kind):
    """Under the chip's lowering (interpret mode) the fused program hands
    the driver's linear rows to the RS kernel as they lie and takes the
    codeword fragment-major (PR 51): no ``u8[B, k, n]`` array in the
    program, a ``u8[k + m, B, n]`` one instead, and ``"fragments"`` /
    ``"tags"`` equal to the array form's and to the plain reference's
    byte for byte. A batch that is no multiple of 8 stacks by its shape:
    the old program, the same bits."""
    pipe = geometry_pipe(geometry, strategy="pallas")
    cfg = pipe.config
    rows = cfg.k + cfg.m
    assert pipe.rows_direct(batch, FRAG) == direct
    segs = rnd((batch, cfg.segment_size), 510 + batch)
    ids = rnd((batch, rows, 2), 511, np.uint32) if id_kind == "pairs" \
        else rnd((batch, rows), 512, np.uint32).astype(np.int32)
    staged = jax.device_put(linear_rows(segs, cfg.k))
    got = pipe.fused_program()(staged, jnp.asarray(ids))
    array = pipe.fused_program()(jnp.asarray(segs), jnp.asarray(ids))
    want_frags, want_tags = plain_reference(pipe, segs, ids)
    assert got["fragments"].shape == (batch, rows, FRAG)
    assert np.array_equal(np.asarray(got["fragments"]), want_frags)
    assert np.array_equal(np.asarray(got["tags"]), want_tags)
    for name in ("fragments", "tags"):
        assert np.array_equal(np.asarray(got[name]),
                              np.asarray(array[name])), name
    text = str(jax.make_jaxpr(pipe.fused_program())(staged,
                                                    jnp.asarray(ids)))
    stacked = f"u8[{batch},{cfg.k},{FRAG}]"
    fragment_major = f"u8[{rows},{batch},{FRAG}]"
    assert (stacked in text) == (not direct)
    assert (fragment_major in text) == direct
    assert pipe.fused_program().direct_rows(staged) == direct
    assert not pipe.fused_program().direct_rows(jnp.asarray(segs))


@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_the_cpus_lowering_keeps_the_stack(geometry):
    """``gather`` (the CPU's) stacks the rows whatever the batch: the
    rows entry is the chip's kernel's."""
    pipe = geometry_pipe(geometry)
    cfg = pipe.config
    assert not pipe.rows_direct(8, FRAG)
    segs = rnd((8, cfg.segment_size), 513)
    ids = jnp.arange(8 * (cfg.k + cfg.m), dtype=jnp.int32)
    staged = jax.device_put(linear_rows(segs, cfg.k))
    got = pipe.fused_program()(staged, ids)
    want = geometry_pipe(geometry, strategy="pallas").fused_program()(
        staged, ids)
    for name in ("fragments", "tags"):
        assert np.array_equal(np.asarray(got[name]),
                              np.asarray(want[name])), name
    assert f"u8[8,{cfg.k},{FRAG}]" in str(
        jax.make_jaxpr(pipe.fused_program())(staged, ids))


@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_direct_rows_counts_the_batches_the_kernel_took_unstacked(geometry):
    """``StreamStats.direct_rows``: equal to ``batches`` on the rows
    path (the ragged tail is padded to the batch first), in ``raw()``,
    the snapshot, the export and the engine's merged sum; 0 for a program
    handed an array, for the CPU's lowering, for a batch that stacks by
    its shape, and for a program that does not say (the benchmark's span
    wrapper hides the attribute: its witness is the trace)."""
    pipe = geometry_pipe(geometry, strategy="pallas")
    cfg = pipe.config
    segs = rnd((19, cfg.segment_size), 514)
    want = pipe.forward(segs)
    eng = make_engine(cfg.k, cfg.m, rs_backend="jax")
    try:
        ing = StreamingIngest(pipe, 8, engine=eng)
        got = ing.ingest(segs)
        for name in ("fragments", "tags"):
            assert np.array_equal(np.asarray(got[name]),
                                  np.asarray(want[name])), name
        st = ing.stats
        assert st.direct_rows == st.linear_puts == st.batches == 3
        assert st.raw()["direct_rows"] == st.snapshot()["direct_rows"] == 3
        assert st.metrics()["cess_engine_stream_direct_rows"] == 3
        assert eng.stats_metrics()["cess_engine_stream_direct_rows"] == 3
    finally:
        eng.close()

    def packed(rows_up):        # an array program: [B, segment_size] up
        return jnp.asarray(np.concatenate(rows_up).reshape(8, -1)) \
            if isinstance(rows_up, tuple) else jax.device_put(rows_up)

    for name, kw in {
            "array": dict(pipe=pipe, batch=8, put=packed),
            "gather": dict(pipe=geometry_pipe(geometry), batch=8),
            "stacks": dict(pipe=pipe, batch=4),
            "wrapped": dict(pipe=pipe, batch=8, program=_spanned(
                [], "stream.dispatch", pipe.fused_program()))}.items():
        ing = StreamingIngest(kw.pop("pipe"), kw.pop("batch"), **kw)
        got = ing.ingest(segs)
        assert np.array_equal(np.asarray(got["tags"]),
                              np.asarray(want["tags"])), name
        assert ing.stats.direct_rows == 0 < ing.stats.batches, name


# -- the driver's order (PR 50) ---------------------------------------------

# five batches, a letter an event: g the gate (a put waits for the put
# two before it to have arrived), p a put, w the wait for the oldest
# result, P a program enqueued. At depth 2: put 0, program 0, put 1,
# program 1, put 2, THEN the first wait for a result
ORDERS = {1: "pPpwPpwPpwPpwPw",      # the put two before is out: no gate
          2: "pPpPgpwPgpwPgpwPww",
          3: "pPpPgpPgpwPgpwPwww"}


class _Recorded:
    """A recording ``put=`` / ``program=`` pair around the real ones,
    and ``jax.block_until_ready`` told apart by what is waited for: a
    put's rows (the gate) or a result (the stall)."""

    def __init__(self, pipe, monkeypatch, gate_sleep=0.0):
        self.events = []
        self.puts = []              # weak references to a put's first row
        # batches' rows on the device at most: those the driver holds,
        # and those of batches put whose result nobody has waited for
        self.rows_held = self.rows_owed = self.results = 0
        self.fused = pipe.fused_program()
        self.on_put = lambda: None
        block = jax.block_until_ready

        def waited(x):
            if isinstance(x, tuple) and any(x[0] is p() for p in self.puts):
                # all the rows of the put two before the next, one call
                assert x[0] is self.puts[-2]() and len(x) > 1
                self.events.append("g")
                time.sleep(gate_sleep)
            else:
                self.events.append("w")
            return block(x)

        monkeypatch.setattr(jax, "block_until_ready", waited)

    def put(self, rows):
        self.on_put()
        self.events.append("p")
        dev = jax.device_put(rows)
        self.puts.append(weakref.ref(dev[0]))
        self.rows_held = max(self.rows_held,
                             sum(p() is not None for p in self.puts))
        self.rows_owed = max(self.rows_owed, self.events.count("p")
                             - self.events.count("w"))
        return dev

    def program(self, dev, ids):
        self.events.append("P")
        self.results = max(self.results, self.events.count("P")
                           - self.events.count("w"))
        return self.fused(dev, ids)


@pytest.mark.parametrize("depth", sorted(ORDERS))
@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_the_put_is_asked_for_before_the_wait_for_the_oldest_result(
        geometry, depth, monkeypatch):
    """Put i + 1 is asked for before result i - 1 is waited for, once
    put i - 1 has arrived (two puts on the link at a time), and program
    i is enqueued behind its own put, before any later one. Never more
    than ``depth`` results in flight, nor more than ``depth + 1``
    batches' rows alive: a batch's rows go with its result, never while
    its program may be pending (so at depth 1 the put two before is
    out, and nothing gates). ``gate_s`` grows only once two puts are
    held and is no part of ``stall_s``; the results are ``forward``'s,
    ragged tail included."""
    pipe = geometry_pipe(geometry)
    cfg = pipe.config
    segs = rnd((9, cfg.segment_size), 500)          # 5 batches, 1 ragged
    rec = _Recorded(pipe, monkeypatch, gate_sleep=0.02)
    ing = StreamingIngest(pipe, 2, depth=depth, put=rec.put,
                          put_ids=jax.device_put, program=rec.program)
    gate_at_put = []
    rec.on_put = lambda: gate_at_put.append(ing.stats.gate_s)
    tracer = obs.Tracer()
    with obs.armed(tracer):
        outs = list(ing.run(segs))
    assert "".join(rec.events) == ORDERS[depth]
    assert rec.results == depth
    assert (rec.rows_held, rec.rows_owed) == (depth + 1, depth + 1)
    want = pipe.forward(segs)
    for name in ("fragments", "tags"):
        got = np.concatenate([np.asarray(o[name]) for o in outs])
        assert np.array_equal(got, np.asarray(want[name])), name
    st = ing.stats
    gates = ORDERS[depth].count("g")                # 3, or 0 at depth 1
    assert gate_at_put[:2] == [0.0, 0.0]
    assert (gate_at_put[2] >= 0.02) == (gates > 0)
    assert st.gate_s >= gates * 0.02 and st.raw()["gate_s"] == st.gate_s
    assert (st.gate_s > 0) == (gates > 0)
    assert "cess_engine_stream_gate_s" in st.metrics()
    # each clock runs inside its own stage's span, and no gate lies
    # inside a stall: the gates' sleeps are in no part of ``stall_s``
    spans = tracer.finished()
    by = lambda name: [s for s in spans if s["name"] == name]  # noqa: E731
    assert len(by("stream.gate")) == gates and len(by("stream.stall")) == 5
    for counter, stage in (("gate_s", "stream.gate"),
                           ("stall_s", "stream.stall")):
        assert getattr(st, counter) <= sum(s["dur_s"]
                                           for s in by(stage)) + 1e-4
    for g in by("stream.gate"):
        for w in by("stream.stall"):
            assert g["ts_s"] + g["dur_s"] <= w["ts_s"] + 1e-6 \
                or w["ts_s"] + w["dur_s"] <= g["ts_s"] + 1e-6


def test_a_stream_of_two_batches_never_gates(monkeypatch):
    pipe = geometry_pipe("rs2p1")
    rec = _Recorded(pipe, monkeypatch)
    ing = StreamingIngest(pipe, 2, put=rec.put, put_ids=jax.device_put,
                          program=rec.program)
    ing.ingest(rnd((4, pipe.config.segment_size), 501))
    assert "".join(rec.events) == "pPpPww"
    assert ing.stats.gate_s == 0.0 and ing.stats.stall_s > 0.0


@pytest.mark.parametrize("site", ["stream.h2d", "stream.dispatch"])
def test_a_fault_at_either_seam_lands_the_batch_and_burns_the_slo(site):
    """The third batch dies in its put, or in its program (behind the
    window now: the first result is already out): its span lands with
    the error, the stream SLO is burnt, the journal has the escape, and
    what was counted was counted with ``batches``."""
    pipe = geometry_pipe("rs2p1")
    cfg = pipe.config
    segs = rnd((10, cfg.segment_size), 502)
    tracer = obs.Tracer()
    board = SloBoard((SloTarget("stream", 1.0),))
    eng = make_engine(2, 1, policy=AdmissionPolicy(max_delay=0.005),
                      tracer=tracer, slo=board)
    recorder = flight.FlightRecorder(b"pr50")
    try:
        ing = StreamingIngest(pipe, 2, engine=eng, tenant="t")
        outs = []
        with faults.armed(FaultPlan({site: {2: FaultSpec("raise")}})), \
                flight.armed(recorder), pytest.raises(FaultInjected):
            for out in ing.run(segs):
                outs.append(out)
    finally:
        eng.close()
    # the window stands between a batch's put and its program
    assert len(outs) == (0 if site == "stream.h2d" else 1)
    spans = tracer.finished()
    assert tracer.started == len(spans)             # none left open
    batches = [s for s in spans if s["name"] == "stream.batch"]
    assert len(batches) == 3
    assert ["error" in s["attrs"] for s in batches] == [False, False, True]
    assert "FaultInjected" in batches[2]["attrs"]["error"]
    st = ing.stats
    assert st.linear_puts == st.batches == 2
    assert st.put_arrays == st.batches * 2 * cfg.k
    assert st.segments == 4 and st.bytes_in == 4 * cfg.segment_size
    mine = board.snapshot()["tenants"]["t"]["stream"]
    assert (mine["requests"], mine["failed"], mine["rows"]) == (3, 1, 4)
    assert [e["kind"] for e in recorder.journal_tail("stream")] == ["escape"]


def test_a_consumer_that_stops_after_one_result_closes_every_span():
    """The first result comes out between the third batch's put and its
    program: that batch's span still lands, and a put is counted only
    with its batch, so no snapshot sees one without the other."""
    pipe = geometry_pipe("rs2p1")
    cfg = pipe.config
    tracer = obs.Tracer()
    ing = StreamingIngest(pipe, 2)
    with obs.armed(tracer):
        run = ing.run(rnd((10, cfg.segment_size), 503))
        next(run)
        st = ing.stats
        assert st.linear_puts == st.batches == 2
        assert st.put_arrays == st.batches * 2 * cfg.k
        run.close()
    spans = tracer.finished()
    assert tracer.started == len(spans)
    names = [s["name"] for s in spans]
    assert names.count("stream.put") == names.count("stream.batch") == 3
    assert names.count("stream.dispatch") == 2
    assert names.count("stream.run") == 1
    assert st.wall_s > 0


# -- a stage's distribution and its worst cases (PR 54) ---------------------

PARTS = ("stage_s", "gate_s", "h2d_s", "stall_s", "dispatch_s",
         "consumer_s")


def test_a_streams_stages_keep_their_distribution_beside_their_sums():
    """Every key ``raw()`` had reads as before; the five stages' ladders
    hold what the float counters hold (the put's stage is ``h2d_s``), a
    stage's ``n`` / ``s`` are its buckets' sums, and the Prometheus
    family observes a batch's host cost once a batch."""
    pipe = geometry_pipe("rs2p1")
    ing = StreamingIngest(pipe, 2)
    had = {"batches", "segments", "padded_segments", "bytes_in",
           "bytes_out", "linear_puts", "put_arrays", "direct_rows",
           "h2d_s", "dispatch_s", "stall_s", "gate_s", "wall_s", "lanes"}
    assert had <= set(StreamStats().raw())
    ing.ingest(rnd((9, pipe.config.segment_size), 540))     # 5 batches
    st, raw = ing.stats, ing.stats.raw()
    assert set(raw["stages"]) == set(StreamStats.STAGES)
    for stage, counter in (("stream.stage", "stage_s"),
                           ("stream.gate", "gate_s"),
                           ("stream.put", "h2d_s"),
                           ("stream.dispatch", "dispatch_s"),
                           ("stream.stall", "stall_s")):
        acc = raw["stages"][stage]
        assert acc["n"] == sum(n for _, n, _ in acc["buckets"]), stage
        assert acc["s"] == pytest.approx(
            sum(s for _, _, s in acc["buckets"]), rel=1e-9), stage
        assert acc["s"] == pytest.approx(raw[counter], rel=1e-9), stage
    counts = {k: v["n"] for k, v in raw["stages"].items()}
    assert counts == {"stream.stage": 6, "stream.gate": 3,
                      "stream.put": 5, "stream.dispatch": 5,
                      "stream.stall": 5}
    assert raw["long_waits"] == []
    assert st.hist.count == 5
    # gate + put + the stall ahead of the program + dispatch, a batch:
    # all of them but the final drain's two stalls
    assert st.hist.sum <= st.gate_s + st.h2d_s + st.stall_s \
        + st.dispatch_s + 1e-9
    assert st.hist.sum >= st.h2d_s + st.dispatch_s
    # scalars only on the flat surface; the gauge that misled is gone
    metrics = st.metrics()
    assert "cess_engine_stream_stage_s" in metrics
    assert "cess_engine_stream_consumer_s" in metrics
    assert not [k for k in metrics if "stages" in k or "long_waits" in k
                or "h2d_frac" in k]


def test_wall_s_is_its_seven_addends():
    """``wall_s`` = the five stages' seconds + the time suspended at a
    yield + a remainder, the loop's own bookkeeping: never negative (the
    addends are disjoint pieces of the run) and, in the quietest of a
    few short runs, under a millisecond. The consumer's sleeps are
    ``consumer_s``, also the last one's, after which it never comes
    back."""
    pipe = geometry_pipe("rs2p1")
    ing = StreamingIngest(pipe, 2)
    segs = rnd((4, pipe.config.segment_size), 541)
    ing.ingest(segs)                                # compile outside
    remainders = []
    for _ in range(5):
        a = ing.stats.raw()
        for _out in ing.run(segs):
            time.sleep(0.01)
        b = ing.stats.raw()
        d = {k: b[k] - a[k] for k in PARTS + ("wall_s",)}
        assert d["consumer_s"] >= 2 * 0.01
        remainders.append(d["wall_s"] - sum(d[k] for k in PARTS))
    assert min(remainders) >= -1e-9
    assert min(remainders) < 1e-3
    # a consumer that stops at a yield: its time there is still its own
    a = ing.stats.raw()
    run = ing.run(segs)
    next(run)
    time.sleep(0.02)
    run.close()
    b = ing.stats.raw()
    assert b["consumer_s"] - a["consumer_s"] >= 0.02
    assert b["wall_s"] - a["wall_s"] >= sum(b[k] - a[k] for k in PARTS) \
        - 1e-9


class _SlowResult:
    """``jax.block_until_ready`` that sleeps before the n-th wait for a
    RESULT (a dict's tags: not a put's rows), and counts the readings of
    the host's counters."""

    def __init__(self, monkeypatch, nth, seconds, fresh=0.0):
        self.waits = self.readings = 0
        # how old a reading may be and still serve as a wait's "before":
        # at 0.0 every wait reads its own
        monkeypatch.setattr(stream_mod, "_COUNTERS_FRESH_S", fresh)
        block, read = jax.block_until_ready, stream_mod._host_counters

        def waited(x):
            if not isinstance(x, tuple):
                if self.waits == nth:
                    time.sleep(seconds)
                self.waits += 1
            return block(x)

        def counters():
            self.readings += 1
            return read()

        monkeypatch.setattr(jax, "block_until_ready", waited)
        monkeypatch.setattr(stream_mod, "_host_counters", counters)


@pytest.mark.parametrize("seconds,long", [(0.3, True), (0.001, False)],
                         ids=["0.3s", "1ms"])
def test_a_scripted_stall_lands_in_long_waits_with_what_was_in_flight(
        seconds, long, monkeypatch):
    """The wait for batch 1's result blocks: over ``LONG_WAIT_S`` it is
    kept with its ``seq``, the results and puts in flight and the host's
    counters over the wait, in ``raw()["long_waits"]`` and the flight
    journal, and its seconds stand in the ladder above the threshold;
    at 1 ms nothing is built (with a reading's freshness at 0, one
    reading before each wait, none after)."""
    pipe = geometry_pipe("rs2p1")
    slow = _SlowResult(monkeypatch, nth=1, seconds=seconds)
    ing = StreamingIngest(pipe, 2)
    recorder = flight.FlightRecorder(b"pr54")
    with flight.armed(recorder):
        ing.ingest(rnd((10, pipe.config.segment_size), 542))  # 5 batches
    raw = ing.stats.raw()
    waits = 5 + 3                                   # stalls + gates
    over = sum(s for le, _, s in raw["stages"]["stream.stall"]["buckets"]
               if le is None or le > obs.trace.LONG_WAIT_S)
    notes = recorder.journal_tail("stream")
    if not long:
        assert raw["long_waits"] == [] and notes == [] and over == 0.0
        assert slow.readings == waits
        return
    assert slow.readings == waits + 1
    (rec,) = raw["long_waits"]
    assert rec["stage"] == "stream.stall" and rec["seq"] == 1
    assert 0.3 <= rec["seconds"] == pytest.approx(over)
    # batch 3's put is out ahead of the window while batch 1's result is
    # waited for, batch 2's program behind it
    assert (rec["results_in_flight"], rec["puts_in_flight"]) == (2, 3)
    assert {"nvcsw", "nivcsw", "minflt", "stime_s"} <= set(rec)
    assert rec["nvcsw"] >= 1                        # it slept
    assert rec["start"] <= time.perf_counter() - rec["seconds"]
    assert [e["kind"] for e in notes] == ["long_wait"]


def test_a_reading_serves_the_waits_that_begin_within_its_freshness(
        monkeypatch):
    """The host's counters are not read before every wait: a reading
    serves as "before" until it is ``_COUNTERS_FRESH_S`` old (50 ms), so
    a run of short waits inside that reads them once; a long wait's own
    reading after it is the next waits' "before"."""
    assert stream_mod._COUNTERS_FRESH_S == obs.trace.LONG_WAIT_S / 5
    pipe = geometry_pipe("rs2p1")
    slow = _SlowResult(monkeypatch, nth=1, seconds=0.001, fresh=3600.0)
    ing = StreamingIngest(pipe, 2)
    ing.ingest(rnd((10, pipe.config.segment_size), 545))     # 8 waits
    assert slow.readings == 1
    ing.ingest(rnd((4, pipe.config.segment_size), 546))      # a new run
    assert slow.readings == 2
    slow = _SlowResult(monkeypatch, nth=1, seconds=0.3, fresh=3600.0)
    ing.ingest(rnd((10, pipe.config.segment_size), 547))
    assert slow.readings == 2                    # the first, the after
    (rec,) = ing.stats.raw()["long_waits"]
    assert rec["seq"] == 8 and rec["nvcsw"] >= 1


def test_a_short_wait_costs_one_comparison():
    from cess_tpu.serve.stats import LongWaits

    def never():
        raise AssertionError("a short wait built its context")

    waits = LongWaits("stream")
    waits.observe("stream.stall", 0.0, obs.trace.LONG_WAIT_S, never)
    assert waits.snapshot() == []
    for i in range(6):                              # the four longest
        waits.observe("stream.stall", float(i), 1.0 + i,
                      lambda seq: {"seq": seq}, i)
    assert [w["seq"] for w in waits.snapshot()] == [2, 3, 4, 5]


def test_the_five_stages_of_a_batch_carry_one_seq():
    """Under a tracer every stream stage span has the ``seq`` of its
    batch: staging, gate, put and dispatch of batch i in turn i, the
    stall that waits for its result later; a second run goes on
    counting."""
    pipe = geometry_pipe("rs2p1")
    ing = StreamingIngest(pipe, 2)
    tracer = obs.Tracer()
    with obs.armed(tracer):
        ing.ingest(rnd((8, pipe.config.segment_size), 543))   # seq 0..3
        ing.ingest(rnd((4, pipe.config.segment_size), 544))   # seq 4, 5
    by_seq: dict = {}
    for s in tracer.finished():
        if s["name"] in StreamStats.STAGES or s["name"] == "stream.batch":
            by_seq.setdefault(s["attrs"]["seq"], []).append(s["name"])
    for seq in range(6):
        names = sorted(by_seq[seq])
        want = ["stream.batch", "stream.dispatch", "stream.put",
                "stream.stage", "stream.stall"]
        if seq in (2, 3):           # two puts held: the gate engages
            want.append("stream.gate")
        if seq == 4:                # the staging that found run 1 dry
            want.append("stream.stage")
        assert names == sorted(want), seq
    assert sorted(by_seq) == [0, 1, 2, 3, 4, 5, 6]
    assert by_seq[6] == ["stream.stage"]            # and run 2


# -- sharded mesh stream entry ---------------------------------------------

def test_sharded_stream_entry_matches_single_device():
    from cess_tpu.parallel.mesh import make_mesh, stream_entry

    byte = 2
    frag = byte * 2 * 512                   # blocks % byte == 0
    cfg = PipelineConfig(k=K, m=M, segment_size=K * frag)
    pipe = StoragePipeline(cfg)
    mesh = make_mesh(jax.devices()[:4], seg=2, byte=byte)
    segs = rnd((6, K * frag), 50)
    ing = StreamingIngest(pipe, 2, **stream_entry(pipe, mesh, 2))
    out = ing.ingest(segs)
    ref = pipe.forward(segs)                # single-device fused
    assert np.array_equal(np.asarray(out["fragments"]),
                          np.asarray(ref["fragments"]))
    assert np.array_equal(np.asarray(out["tags"]),
                          np.asarray(ref["tags"]))


def test_sharded_stream_entry_pair_ids():
    """pair_ids=True: explicit hash-pair ids shard correctly and match
    the single-device fused path; the default arange ids are rejected
    LOUDLY (no pair-shaped default exists)."""
    from cess_tpu.parallel.mesh import make_mesh, stream_entry

    byte = 2
    frag = byte * 2 * 512
    cfg = PipelineConfig(k=K, m=M, segment_size=K * frag)
    pipe = StoragePipeline(cfg)
    mesh = make_mesh(jax.devices()[:4], seg=2, byte=byte)
    segs = rnd((4, K * frag), 51)
    ing = StreamingIngest(pipe, 2,
                          **stream_entry(pipe, mesh, 2, pair_ids=True))
    with pytest.raises(ValueError, match="pair_ids=True"):
        list(ing.run(segs))                 # default ids: no pair shape
    ids = rnd((4, ROWS, 2), 52, np.uint32)
    out = ing.ingest(segs, fragment_ids=ids)
    ref = pipe.forward(segs, fragment_ids=ids)
    assert np.array_equal(np.asarray(out["tags"]),
                          np.asarray(ref["tags"]))


def test_stream_device_array_source():
    """A device-resident (jax.Array) source is fetched ONCE and
    re-batched like a host array — never iterated row-by-row."""
    pipe = make_pipe()
    segs = rnd((5, SEG), 53)
    dev = StreamingIngest(pipe, 2).ingest(jnp.asarray(segs))
    host = StreamingIngest(pipe, 2).ingest(segs)
    assert np.array_equal(np.asarray(dev["tags"]),
                          np.asarray(host["tags"]))
    sizes = [c.shape[0] for c in _rebatch(jnp.asarray(segs), 2)]
    assert sizes == [2, 2, 1]


def test_stream_run_validates_eagerly():
    """run() raises at the CALL site, not at the consumer's first
    next() — it is a validating method over an inner generator."""
    pipe = make_pipe()
    segs = rnd((4, SEG), 54)
    with pytest.raises(ValueError, match="array segment source"):
        StreamingIngest(pipe, 2).run(
            iter([segs[:2], segs[2:]]),
            fragment_ids=rnd((4, ROWS, 2), 55, np.uint32))


# -- repair warm path -------------------------------------------------------

@pytest.mark.parametrize("strategy", ["gather", "pallas"])
def test_warm_reconstruct_bit_exact_and_cached(strategy, compiles):
    codec = rs.TPUCodec(K, M, strategy=strategy)
    data = rnd((K, 520), 60)          # a width no other test compiles
    coded = np.asarray(codec.encode(data))
    surv = coded[[1, 2]]
    codec.warm_reconstruct((1, 2), (0,), surv.shape)
    compiled = compiles()
    codec.warm_reconstruct((1, 2), (0,), surv.shape)      # once
    rec = np.asarray(codec.reconstruct(surv, (1, 2), (0,)))
    assert compiles() == compiled
    assert np.array_equal(rec[0], coded[0])
    # the program is jit's and the pattern its argument, so a pattern
    # of the same shape that was never warmed runs it too
    surv2 = coded[[0, 2]]
    rec2 = np.asarray(codec.reconstruct(surv2, (0, 2), (1,)))
    assert np.array_equal(rec2[0], coded[1])
    assert compiles() == compiled


def test_engine_warm_repair_prepopulates_programs():
    eng = make_engine(K, M, rs_backend="jax",
                      policy=AdmissionPolicy(max_delay=0.005))
    try:
        n = 256
        eng.warm_repair([((1, 2), (0,))], n)
        built = eng.stats_snapshot()["programs_built"]
        assert built >= 1
        data = rnd((1, K, n), 61)
        coded = np.asarray(eng.codec.encode(data))
        rec = eng.reconstruct(coded[:, [1, 2]], (1, 2), (0,))
        assert np.array_equal(np.asarray(rec)[:, 0], coded[:, 0])
        snap = eng.stats_snapshot()
        # the restoral request hit the warmed program, not a compile
        assert snap["programs_built"] == built
        assert snap["programs_reused"] >= 1
    finally:
        eng.close()


def test_miner_warm_restoral_smoke():
    """warm_restoral warms the restoral shape without error on both
    the engine and the direct-codec path (the NumPy reference codec is
    a documented no-op): one program for every lost row."""
    from cess_tpu.node.chain_spec import dev_spec
    from cess_tpu.node.network import Node
    from cess_tpu.node.offchain import MinerAgent

    node = Node(dev_spec(), "warm-node", {})
    pipe = make_pipe()
    MinerAgent(node, "m1", [], pipe).warm_restoral()
    eng = make_engine(K, M, rs_backend="jax",
                      policy=AdmissionPolicy(max_delay=0.005))
    try:
        MinerAgent(node, "m2", [], pipe, engine=eng).warm_restoral()
        built = eng.stats_snapshot()["programs_built"]
        assert built >= 1
        n = pipe.config.fragment_size
        coded = np.asarray(eng.codec.encode(rnd((1, K, n), 62)))
        for row in range(ROWS):
            present = tuple(j for j in range(ROWS) if j != row)[:K]
            rec = eng.reconstruct(coded[:, list(present)], present,
                                  (row,))
            assert np.array_equal(np.asarray(rec)[:, 0], coded[:, row])
        assert eng.stats_snapshot()["programs_built"] == built
    finally:
        eng.close()
