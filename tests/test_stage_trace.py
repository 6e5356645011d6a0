"""Stage spans (ISSUE 25): the engine, the gateway and the stream driver
time their stages through ONE hook, ``obs.trace.stage``, which counts them
(``ClassStats.stage_n/stage_s``), writes them into any live profiler trace
as ``cess:<name>`` events — no tracer, flag or argument needed — and makes
them spans of an armed tracer.

No timing thresholds here: counts, names, nesting and the accounting
identity (a batch's stages are pieces of its members' submit -> resolve
latency).
"""
import glob
import os

import numpy as np
import pytest

import jax

from cess_tpu import obs
from cess_tpu.models.pipeline import PipelineConfig, StoragePipeline
from cess_tpu.node.offchain import OssGateway
from cess_tpu.obs import trace
from cess_tpu.ops import podr2
from cess_tpu.serve import AdmissionPolicy, make_engine
from cess_tpu.serve.policy import CLASSES
from cess_tpu.serve.stats import STAGES
from cess_tpu.serve.stream import StreamingIngest

K, M = 2, 1
FRAG = 1024               # bytes per fragment -> 2 PoDR2 blocks
DOCUMENTED = ("queue", "assemble", "dispatch", "wait", "fetch", "resolve")


@pytest.fixture(scope="module")
def pkey():
    return podr2.Podr2Key.generate(25)


def rnd(shape, seed=0, dtype=np.uint8):
    rng = np.random.default_rng(seed)
    return rng.integers(0, np.iinfo(dtype).max, shape, dtype=dtype)


def _engine(pkey, **kw):
    return make_engine(K, M, rs_backend="jax", podr2_key=pkey,
                       policy=AdmissionPolicy(max_delay=0.002), **kw)


def _round(eng, pkey, seed=b"stage-round"):
    """One audit round's worth of submits; returns the classes it ran."""
    frags = rnd((3, FRAG), 4)
    ids = np.stack([podr2.fragment_id_from_hash(bytes([i]) * 32)
                    for i in range(3)])
    tags = np.asarray(eng.tag_fragments(ids, frags))
    blocks = tags.shape[1]
    idx, nu = (np.asarray(a) for a in podr2.gen_challenge(seed, blocks))
    r = np.asarray(podr2.aggregate_coeffs(seed, ids))
    mu, sigma = eng.prove_aggregate(frags, tags, idx, nu, r)
    assert eng.verify_aggregate(ids, blocks, idx, nu, r, mu, sigma)


# one workload per op class: what to submit so that the class runs
def _drive(eng, pkey, cls):
    if cls == "encode":
        for seed in range(3):
            eng.encode(rnd((2, K, FRAG), seed))
    elif cls == "repair":
        coded = np.asarray(eng.encode(rnd((2, K, FRAG), 7)))
        for _ in range(3):
            eng.reconstruct(coded[:, 1:], (1, 2), (0,))
        eng.decode_data(coded[:, 1:], (1, 2))
    else:                       # tag, prove and verify: an audit round
        _round(eng, pkey)
        _round(eng, pkey, b"stage-round-2")


def test_stage_names_are_the_documented_set():
    assert STAGES == DOCUMENTED
    assert set(CLASSES) == {"verify", "prove", "tag", "repair", "encode"}


@pytest.mark.parametrize("cls", sorted(CLASSES))
def test_every_batch_counts_each_stage_once(pkey, cls):
    eng = _engine(pkey)
    try:
        _drive(eng, pkey, cls)
        eng.flush()
        st = eng.stats.classes[cls]
        snap = eng.stats_snapshot()["classes"][cls]
        latency = sum(st.latencies)
    finally:
        eng.close()
    assert snap["batches"] >= 2
    assert set(snap["stages"]) == set(DOCUMENTED)
    for stage in DOCUMENTED:
        assert snap["stages"][stage]["n"] == snap["batches"], stage
        assert snap["stages"][stage]["s"] >= 0.0, stage
    # raw and unrounded: the snapshot is the counters themselves
    assert {s: a["s"] for s, a in snap["stages"].items()} == st.stage_s
    # the stages are pieces of submit -> resolve (resolve alone runs on
    # past the latency's clock stop, by the time of resolving futures)
    pieces = sum(st.stage_s[s] for s in DOCUMENTED if s != "resolve")
    assert 0.0 < pieces <= latency + 1e-3


@pytest.mark.parametrize("cls", sorted(CLASSES))
def test_stage_metrics_are_flat_gauges(pkey, cls):
    eng = _engine(pkey)
    try:
        _drive(eng, pkey, cls)
        eng.flush()
        metrics = eng.stats_metrics()
        batches = eng.stats_snapshot()["classes"][cls]["batches"]
    finally:
        eng.close()
    for stage in DOCUMENTED:
        assert metrics[f"cess_engine_{cls}_stage_{stage}_count"] == batches
        assert metrics[f"cess_engine_{cls}_stage_{stage}_seconds"] >= 0
    # the flattening loop was never handed the nested dict
    assert all(isinstance(v, (int, float)) for v in metrics.values())
    assert f"cess_engine_{cls}_stages" not in metrics


def test_coalesced_batch_counts_once_and_sums_its_members_queue(pkey):
    eng = make_engine(K, M, rs_backend="jax",
                      policy=AdmissionPolicy(max_delay=0.25))
    try:
        futs = [eng.submit_encode(rnd((1, K, FRAG), s)) for s in range(4)]
        for f in futs:
            f.result(30)
        eng.flush()
        st = eng.stats.classes["encode"]
        assert st.batches < st.completed == 4       # they coalesced
        assert all(n == st.batches for n in st.stage_n.values())
        # every member waited out the coalescing delay: the queue stage
        # is summed over members, so it alone can pass a batch's time
        assert sum(st.stage_s.values()) <= sum(st.latencies) + 1e-3
    finally:
        eng.close()


def test_pool_lanes_keep_their_own_stage_sinks():
    """Lane workers run batches of one class at the same time: each has
    its own sink (thread-local), merged under the engine lock, so no
    count is lost — more submitters than cores, a short switch interval."""
    import sys
    import threading

    eng = make_engine(K, M, rs_backend="jax", pool=2,
                      policy=AdmissionPolicy(max_delay=0.001))
    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        def client(seed):
            for i in range(6):
                eng.encode(rnd((1, K, FRAG), seed * 100 + i), timeout=60)
        threads = [threading.Thread(target=client, args=(s,))
                   for s in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        assert not any(t.is_alive() for t in threads)
        assert eng.flush(60)
        st = eng.stats.classes["encode"]
        assert st.completed == 24 and st.batches >= 1
        assert all(n == st.batches for n in st.stage_n.values()), \
            (st.stage_n, st.batches)
    finally:
        sys.setswitchinterval(was)
        eng.close()


def test_profile_feed_takes_the_stage_clock(pkey):
    from cess_tpu.obs import profile

    plane = profile.ProfilePlane()
    eng = _engine(pkey, profile=plane)
    try:
        _drive(eng, pkey, "encode")
        eng.flush()
        st = eng.stats.classes["encode"]
    finally:
        eng.close()
    acct = [a for a in plane.ops.snapshot()["accounts"]
            if a["cls"] == "encode"]
    assert sum(a["batches"] for a in acct) == st.batches
    for field, stage in (("queue_s", "queue"), ("dispatch_s", "dispatch"),
                         ("sync_s", "wait")):
        assert sum(a[field] for a in acct) \
            == pytest.approx(st.stage_s[stage], abs=1e-5)
        assert all(a["h2d_s"] == 0.0 for a in acct)


# -- tracer off / on ---------------------------------------------------------
def test_stage_without_a_tracer_starts_no_span():
    assert trace.armed_tracer() is None
    sink = {}
    with trace.stage("engine.encode.wait", sink) as st:
        assert st._span is obs.NOOP_SPAN
        assert obs.span("anything") is obs.NOOP_SPAN     # identity
        assert obs.current_span() is obs.NOOP_SPAN
    assert sink["engine.encode.wait"][0] == 1
    assert sink["engine.encode.wait"][1] == st.seconds >= 0.0
    with trace.stage("engine.encode.wait", sink):
        pass
    assert sink["engine.encode.wait"][0] == 2            # accumulates
    assert obs.span("still off") is obs.NOOP_SPAN


def test_stage_under_an_armed_tracer_is_a_child_span():
    tracer = obs.Tracer()
    with obs.armed(tracer):
        with obs.span("outer", sys="test") as outer:
            with trace.stage("gateway.hash", file="f") as st:
                assert obs.current_span() is st._span
            assert obs.current_span() is outer
        # an explicit NOOP parent means "no span", armed or not
        with trace.stage("engine.encode.batch", parent=obs.NOOP_SPAN) as b:
            assert b._span is obs.NOOP_SPAN
    spans = {s["name"]: s for s in tracer.finished()}
    assert set(spans) == {"outer", "gateway.hash"}
    assert spans["gateway.hash"]["parent_id"] == spans["outer"]["span_id"]
    assert spans["gateway.hash"]["sys"] == "gateway"
    assert spans["gateway.hash"]["attrs"] == {"file": "f"}


@pytest.mark.parametrize("cls", sorted(CLASSES))
def test_engine_stages_are_children_of_engine_batch(pkey, cls):
    tracer = obs.Tracer()
    eng = _engine(pkey, tracer=tracer)     # pinned, not armed
    try:
        _drive(eng, pkey, cls)
        eng.flush()
    finally:
        eng.close()
    spans = tracer.finished()
    batches = {s["span_id"] for s in spans
               if s["name"] == "engine.batch" and s["attrs"]["cls"] == cls}
    assert batches
    for stage in DOCUMENTED[1:]:            # queue is a counter, no span
        mine = [s for s in spans if s["name"] == f"engine.{cls}.{stage}"]
        assert len(mine) == len(batches), stage
        assert {s["parent_id"] for s in mine} == batches, stage
    assert not [s for s in spans if s["name"].endswith(".queue")]
    # the batch itself stays ONE span (engine.batch), not two
    assert not [s for s in spans if s["name"] == f"engine.{cls}.batch"]


def test_stream_stages_ride_the_batch_and_run_spans():
    seg = K * FRAG
    pipe = StoragePipeline(PipelineConfig(k=K, m=M, segment_size=seg))
    tracer = obs.Tracer()
    ingest = StreamingIngest(pipe, batch=2)
    with obs.armed(tracer):
        for _ in ingest.run(rnd((5, seg), 3)):
            pass
    spans = tracer.finished()
    (run,) = [s for s in spans if s["name"] == "stream.run"]
    batches = {s["span_id"] for s in spans if s["name"] == "stream.batch"}
    assert len(batches) == 3
    by = lambda name: [s for s in spans if s["name"] == name]  # noqa: E731
    assert {s["parent_id"] for s in by("stream.put")} == batches
    assert {s["parent_id"] for s in by("stream.dispatch")} == batches
    assert len(by("stream.stall")) == 3
    assert {s["parent_id"] for s in by("stream.stall")
            + by("stream.stage")} == {run["span_id"]}
    # the counters kept their names, and are what the stages timed
    raw = ingest.stats.raw()
    assert raw["batches"] == 3 and raw["stall_s"] > 0
    assert 0.0 < raw["h2d_s"] <= sum(s["dur_s"]
                                     for s in by("stream.put")) + 1e-4


# -- the profiler's trace ----------------------------------------------------
class _Node:
    def __init__(self):
        self.extrinsics = []

    def submit_extrinsic(self, *call):
        self.extrinsics.append(call)


@pytest.fixture(scope="module")
def profiled(pkey, tmp_path_factory):
    """One profiler session on the CPU backend, nothing armed: a small
    repair, an audit round, a gateway upload and a streamed run; returns
    the host events of the .xplane.pb as (line index, name, start, end)."""
    from jax.profiler import ProfileData

    assert trace.armed_tracer() is None
    where = str(tmp_path_factory.mktemp("xplane"))
    seg = K * FRAG
    cfg = PipelineConfig(k=K, m=M, segment_size=seg)
    eng = _engine(pkey)
    gateway = OssGateway(_Node(), "gw",
                         StoragePipeline(cfg, podr2_key=pkey, engine=eng))
    stream = StreamingIngest(StoragePipeline(cfg, podr2_key=pkey), batch=2)
    coded = np.asarray(eng.encode(rnd((1, K, FRAG), 1)))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(where, profiler_options=opts)
    try:
        eng.reconstruct(coded[:, 1:], (1, 2), (0,))
        _round(eng, pkey)
        gateway.upload("alice", "b", "f", rnd((2 * seg,), 2).tobytes())
        for _ in stream.run(rnd((3, seg), 3)):
            pass
        eng.flush()
    finally:
        jax.profiler.stop_trace()
        eng.close()
    (path,) = glob.glob(os.path.join(where, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    events = []
    lines = [ln for plane in ProfileData.from_file(path).planes
             for ln in plane.lines]
    for i, line in enumerate(lines):
        for ev in line.events:
            if ev.name.startswith(trace.STAGE_PREFIX):
                events.append((i, ev.name[len(trace.STAGE_PREFIX):],
                               ev.start_ns, ev.start_ns + ev.duration_ns))
    return events


def _inside(events, inner, outer, same_thread=True):
    """Every ``inner`` event lies inside an ``outer`` event."""
    outers = [e for e in events if e[1] == outer]
    inners = [e for e in events if e[1] == inner]
    assert inners and outers, (inner, outer)
    return all(any(o[2] <= e[2] and e[3] <= o[3]
                   and (o[0] == e[0] or not same_thread) for o in outers)
               for e in inners)


ENGINE_NESTING = [(f"engine.{cls}.{stage}", f"engine.{cls}.batch")
                  for cls in ("repair", "prove", "verify", "tag", "encode")
                  for stage in DOCUMENTED[1:]]
GATEWAY_NESTING = [(f"gateway.{stage}", "offchain.upload")
                   for stage in ("encode", "fetch", "hash", "tag", "store",
                                 "declare")]


@pytest.mark.parametrize("inner,outer", ENGINE_NESTING + GATEWAY_NESTING)
def test_profile_holds_each_stage_inside_its_unit(profiled, inner, outer):
    assert _inside(profiled, inner, outer)


@pytest.mark.parametrize("inner,outer", [
    ("engine.encode.batch", "gateway.encode"),
    ("engine.tag.batch", "gateway.tag")])
def test_profile_lays_engine_batches_inside_gateway_stages(profiled,
                                                          inner, outer):
    """Across threads, on one clock: the batcher's batch for an upload
    lies inside the gateway stage that waits for it."""
    upload = next(e for e in profiled if e[1] == "offchain.upload")
    mine = [e for e in profiled
            if upload[2] <= e[2] and e[3] <= upload[3]]
    assert _inside(mine, inner, outer, same_thread=False)
    assert {e[0] for e in mine if e[1] == inner} \
        != {e[0] for e in mine if e[1] == outer}       # two threads


@pytest.mark.parametrize("name", ["stream.stage", "stream.put",
                                  "stream.dispatch", "stream.stall"])
def test_profile_holds_the_stream_stages(profiled, name):
    mine = [e for e in profiled if e[1] == name]
    assert len(mine) >= 2          # 3 streamed rows, 2 a batch
    assert all(e[3] >= e[2] for e in mine)


def test_profile_has_one_event_per_stage_per_batch(profiled):
    names = [e[1] for e in profiled]
    assert names.count("engine.repair.batch") == 1
    for stage in DOCUMENTED[1:]:
        assert names.count(f"engine.repair.{stage}") == 1
    assert "engine.repair.queue" not in names      # a counter, no span
    assert names.count("offchain.upload") == 1
    assert names.count("gateway.fetch") == 2       # fragments, then tags
    assert names.count("gateway.hash") == 1        # one span, not per row
