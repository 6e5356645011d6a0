"""Stage spans (ISSUE 25): the engine, the gateway and the stream driver
time their stages through ONE hook, ``obs.trace.stage``, which counts them
(``ClassStats.stage_n/stage_s``), writes them into any live profiler trace
as ``cess:<name>`` events — no tracer, flag or argument needed — and makes
them spans of an armed tracer.

Since ISSUE 35 the same hook reaches the caller's side of a request
(``engine.<cls>.submit`` / ``.result`` on the caller's thread, counted per
request under ``caller``; the queue's seconds split under ``queue``), the
gateway's hash workers (``gateway.worker.copy`` / ``.hash``, a job each)
and the PoDR2 challenge (``podr2.challenge`` / ``podr2.coeffs``).

Since ISSUE 54 a stage keeps its distribution and its worst cases
beside its sum: every account of every class and the stream driver's five
stages are observed, where the sinks are merged, into one ladder of
buckets that keep ``[count, seconds]`` (``obs.trace.STAGE_LADDER_S``), so
two snapshots difference into a window's percentile; a batch's ``wait`` /
``fetch`` over ``LONG_WAIT_S`` is kept with the batch it was of; a
streamed batch's five stages carry its ``seq`` into the profiler trace.

No timing thresholds here: counts, names, nesting and the accounting
identities (a batch's stages are pieces of its members' submit -> resolve
latency; ``coalesce + wake == queue``; submit + stages + hand-back is the
blocking call).
"""
import glob
import importlib.util
import math
import os
import time

import numpy as np
import pytest

import jax

from cess_tpu import obs
from cess_tpu.models.pipeline import PipelineConfig, StoragePipeline
from cess_tpu.node.offchain import OssGateway
from cess_tpu.obs import trace
from cess_tpu.ops import podr2
from cess_tpu.serve import AdmissionPolicy, make_engine
from cess_tpu.serve.policy import CLASSES, EngineTimeout
from cess_tpu.obs import flight
from cess_tpu.serve.stats import (CALLER, LADDERS, LATE, QUEUE_PARTS, STAGES,
                                  ClassStats, EngineStats, StreamStats)
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


@pytest.mark.parametrize("cls", sorted(CLASSES))
def test_caller_and_queue_accounts_sit_beside_the_six_stages(pkey, cls):
    """The caller's side is counted per request and the queue's halves per
    batch, under keys of their own: ``stages`` keeps its six names (its
    readers sum whatever it holds)."""
    eng = _engine(pkey)
    try:
        _drive(eng, pkey, cls)
        eng.flush()
        snap = eng.stats_snapshot()["classes"][cls]
        metrics = eng.stats_metrics()
    finally:
        eng.close()
    assert tuple(snap["stages"]) == DOCUMENTED
    assert (CALLER, QUEUE_PARTS) == (("submit", "handoff"),
                                     ("coalesce", "wake"))
    assert tuple(snap["caller"]) == CALLER
    assert tuple(snap["queue"]) == QUEUE_PARTS
    # one submit and one hand-back a request, the halves once a batch
    assert snap["submitted"] == snap["completed"] >= 2
    for acct in CALLER:
        assert snap["caller"][acct]["n"] == snap["completed"], acct
        assert snap["caller"][acct]["s"] >= 0.0, acct
    for part in QUEUE_PARTS:
        assert snap["queue"][part]["n"] == snap["batches"], part
        assert snap["queue"][part]["s"] >= 0.0, part
    # exactly: the queue's total is kept as the sum of its halves
    assert snap["queue"]["coalesce"]["s"] + snap["queue"]["wake"]["s"] \
        == snap["stages"]["queue"]["s"]
    # a lone client's request waits out max_delay and no more on policy
    assert snap["queue"]["coalesce"]["s"] \
        == pytest.approx(0.002 * snap["batches"], rel=1e-6)
    # flat gauges beside the stages', and no nested dict among them
    for acct in CALLER:
        assert metrics[f"cess_engine_{cls}_caller_{acct}_count"] \
            == snap["caller"][acct]["n"]
        assert metrics[f"cess_engine_{cls}_caller_{acct}_seconds"] \
            == snap["caller"][acct]["s"]
    for part in QUEUE_PARTS:
        assert metrics[f"cess_engine_{cls}_queue_{part}_seconds"] \
            == snap["queue"][part]["s"]
    assert all(isinstance(v, (int, float)) for v in metrics.values())


def _lone(eng):
    eng.encode(rnd((1, K, FRAG), 1))
    return 1


def _coalesced(eng):
    futs = [eng.submit_encode(rnd((1, K, FRAG), s)) for s in range(4)]
    for f in futs:
        f.result(30)
    return 4


def _flushed(eng):
    futs = [eng.submit_encode(rnd((1, K, FRAG), s)) for s in range(2)]
    assert eng.flush(60)            # long before max_delay would trip
    for f in futs:
        f.result(30)
    return 2


@pytest.mark.parametrize("drive,max_delay", [
    (_lone, 0.002), (_coalesced, 0.25), (_flushed, 600.0)],
    ids=["lone", "coalesced", "flushed"])
def test_queue_is_its_two_halves(drive, max_delay):
    """``coalesce`` (enqueue -> the drain trigger trips) + ``wake`` (-> the
    batch starts) == ``queue``, exactly, whatever tripped the trigger: the
    oldest member's max_delay, or a flush."""
    eng = make_engine(K, M, rs_backend="jax",
                      policy=AdmissionPolicy(max_delay=max_delay))
    try:
        n = drive(eng)
        eng.flush()
        snap = eng.stats_snapshot()["classes"]["encode"]
    finally:
        eng.close()
    halves = snap["queue"]
    assert snap["completed"] == n
    assert halves["coalesce"]["s"] + halves["wake"]["s"] \
        == snap["stages"]["queue"]["s"] > 0.0
    assert halves["coalesce"]["s"] >= 0.0 and halves["wake"]["s"] >= 0.0
    # no member waits on policy past max_delay: a flush cuts it short
    assert 0.0 < halves["coalesce"]["s"] <= n * max_delay + 1e-9
    if drive is _lone:
        assert halves["coalesce"]["s"] == pytest.approx(max_delay, rel=1e-6)
    if drive is _coalesced:
        assert snap["batches"] < n


BLOCKING = {"encode": ("encode",),
            "repair": ("reconstruct", "decode_data"),
            "tag": ("tag_fragments",), "prove": ("prove_aggregate",),
            "verify": ("verify_aggregate",)}


def _blocking_accounts(pkey, cls) -> tuple[float, float, int]:
    """One drive of ``cls`` on an engine of its own, every request alone in
    its batch -> (the named seconds: ``caller.submit`` + the six stages +
    ``caller.handoff``; the blocking calls' own extent; requests)."""
    eng = _engine(pkey)
    extent = [0.0]

    def timed(call):
        def wrapper(*args, **kw):
            t0 = time.perf_counter()
            try:
                return call(*args, **kw)
            finally:
                extent[0] += time.perf_counter() - t0
        return wrapper

    for name in BLOCKING[cls]:
        setattr(eng, name, timed(getattr(eng, name)))
    try:
        _drive(eng, pkey, cls)
        eng.flush()
        snap = eng.stats_snapshot()["classes"][cls]
    finally:
        eng.close()
    assert snap["batches"] == snap["completed"] >= 2     # one a batch
    named = snap["caller"]["submit"]["s"] + snap["caller"]["handoff"]["s"] \
        + sum(acc["s"] for acc in snap["stages"].values())
    return named, extent[0], snap["completed"]


@pytest.mark.parametrize("cls", sorted(CLASSES))
def test_submit_stages_and_handback_are_the_blocking_call(pkey, cls):
    """The widened identity, for batches of one request: ``caller.submit``
    + the six stages + ``caller.handoff`` is the blocking call's own extent
    (entry of ``engine.reconstruct`` / ... -> its return), within the clock
    reads between them. A clock identity on a box that runs six test
    workers: the best of three drives is held to the tolerance, since a
    thread parked between two clock reads is the box's doing, and an
    account left out would show in every drive."""
    for _ in range(3):
        named, extent, completed = _blocking_accounts(pkey, cls)
        # what lies between the accounts is code between two clock
        # reads; a loaded box may park a thread there, so the room is
        # wide, and still far below any account left out (the queue
        # alone is 2 ms a request)
        close = pytest.approx(extent, rel=0.15, abs=1e-3 * completed)
        if named == close:
            break
    assert named == close


def _wait_done(fut, seconds=30.0):
    end = time.monotonic() + seconds
    while not fut.done():
        assert time.monotonic() < end
        time.sleep(0.002)


def test_a_late_caller_counts_a_handback_of_zero_once():
    eng = make_engine(K, M, rs_backend="jax",
                      policy=AdmissionPolicy(max_delay=0.002))
    try:
        fut = eng.submit_encode(rnd((1, K, FRAG), 5))
        _wait_done(fut)
        first = fut.result()
        assert np.array_equal(fut.result(), first)      # and again
        eng.flush()
        snap = eng.stats_snapshot()["classes"]["encode"]
    finally:
        eng.close()
    handoff = snap["caller"]["handoff"]
    assert (handoff["n"], handoff["s"]) == (1, 0.0)
    assert snap["caller"]["submit"]["n"] == 1


def test_a_rejected_future_counts_its_handback_once():
    """A request that times out in its queue: ``result()`` raises, counts
    the one hand-back the future has, and closes its stage (the caller's
    span is current again; every result span is finished)."""
    eng = make_engine(K, M, rs_backend="jax",
                      policy=AdmissionPolicy(max_delay=600.0))
    tracer = obs.Tracer()
    try:
        with obs.armed(tracer), obs.span("caller", sys="test") as outer:
            fut = eng.submit_encode(rnd((1, K, FRAG), 6), timeout=0.05)
            # the caller's own patience runs out first: no result yet,
            # so no hand-back to count
            with pytest.raises(EngineTimeout):
                fut.result(timeout=0.0)
            assert obs.current_span() is outer
            pending = eng.stats_snapshot()["classes"]["encode"]
            assert pending["caller"]["handoff"]["n"] == 0
            for _ in range(2):
                with pytest.raises(EngineTimeout):
                    fut.result(30)
                assert obs.current_span() is outer
        snap = eng.stats_snapshot()["classes"]["encode"]
    finally:
        eng.close()
    assert snap["timeouts"] == 1 and snap["completed"] == 0
    assert snap["caller"]["handoff"]["n"] == 1
    assert snap["caller"]["submit"]["n"] == 1
    spans = tracer.finished()
    results = [s for s in spans if s["name"] == "engine.encode.result"]
    (caller,) = [s for s in spans if s["name"] == "caller"]
    (submit,) = [s for s in spans if s["name"] == "engine.encode.submit"]
    (request,) = [s for s in spans if s["name"] == "engine.encode"]
    # the counted ones and the one that found nothing: all closed
    assert len(results) == 2
    assert {s["parent_id"] for s in results + [submit, request]} \
        == {caller["span_id"]}


def test_the_batcher_keeps_no_request_alive_past_its_batch():
    """The stamps of the queue's halves are written where the batch is
    drained: a name left bound to a request in the batcher's own frame
    would hold that request's payload until the next drain, and free it
    there, inside the next request's queue wait (found on the chip: 80 MiB
    unmapped under the GIL, 5.9 ms of ``wake``)."""
    import gc
    import weakref

    eng = make_engine(K, M, rs_backend="jax",
                      policy=AdmissionPolicy(max_delay=0.002))
    try:
        data = rnd((1, K, FRAG), 9)
        ref = weakref.ref(data)
        eng.encode(data)
        assert eng.flush(60)
        del data
        end = time.monotonic() + 10.0
        while ref() is not None and time.monotonic() < end:
            gc.collect()
            time.sleep(0.01)
        assert ref() is None
    finally:
        eng.close()


def test_caller_stages_make_no_root_span():
    """Without a span of the caller's there is nothing to be a child of:
    the stages are annotations and counters only, and the request's span
    stays the root it was."""
    tracer = obs.Tracer()
    eng = make_engine(K, M, rs_backend="jax",
                      policy=AdmissionPolicy(max_delay=0.002))
    try:
        with obs.armed(tracer):
            eng.encode(rnd((1, K, FRAG), 8))
            eng.flush()
    finally:
        eng.close()
    roots = [s["name"] for s in tracer.finished() if s["parent_id"] == 0]
    assert roots == ["engine.encode"]
    assert not [s for s in tracer.finished()
                if s["name"].endswith((".submit", ".result"))]


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
# -- a stage's distribution and its worst cases (ISSUE 54) -------------------

def _ladder_reader():
    """benchmark/stage_ladders.py, loaded from its file (benchmark/ is
    no package): the readers' own arithmetic."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "stage_ladders.py")
    spec = importlib.util.spec_from_file_location("stage_ladders", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_ladder_is_one_object_for_every_class_and_the_stream():
    ladder = trace.STAGE_LADDER_S
    assert ladder[0] == 50e-6 and ladder[-1] == 10.0
    assert trace.LONG_WAIT_S in ladder              # a bound, exactly
    assert all(b / a <= 1.1 for a, b in zip(ladder, ladder[1:]))
    stats = EngineStats()
    hists = [h for st in stats.classes.values()
             for h in st.ladders.values()]
    hists += list(StreamStats().ladders.values())
    assert len(hists) == len(CLASSES) * len(LADDERS) + 5
    assert all(h.bounds is ladder for h in hists)
    assert set(LADDERS) == set(STAGES) | set(CALLER) | set(LATE) \
        | {"queue." + part for part in QUEUE_PARTS}


@pytest.mark.parametrize("cls", sorted(CLASSES))
def test_an_accounts_n_and_s_are_its_buckets_sums(pkey, cls):
    """``n`` and ``s`` read as they did; the buckets beside them hold
    the same occurrences and the same seconds, for the six stages, the
    queue's halves and the caller's accounts; the flat gauges did not
    grow."""
    eng = _engine(pkey)
    try:
        _drive(eng, pkey, cls)
        eng.flush()
        st = eng.stats.classes[cls]
        snap = eng.stats_snapshot()["classes"][cls]
        metrics = eng.stats_metrics()
    finally:
        eng.close()
    accounts = [(f"stages.{k}", v) for k, v in snap["stages"].items()]
    accounts += [(f"caller.{k}", v) for k, v in snap["caller"].items()]
    accounts += [(f"queue.{k}", v) for k, v in snap["queue"].items()]
    # a late operand's wait is a verify round's (tests/test_verify_round.py
    # drives it): here the account is there and empty
    assert snap["late"] == {part: {"n": 0, "s": 0.0, "buckets": []}
                            for part in LATE}
    assert len(accounts) + len(LATE) == len(LADDERS)
    for name, acc in accounts:
        assert set(acc) == {"n", "s", "buckets"}, name
        assert acc["n"] == sum(n for _, n, _ in acc["buckets"]) > 0, name
        assert acc["s"] == pytest.approx(
            sum(s for _, _, s in acc["buckets"]), rel=1e-9, abs=1e-12), name
        les = [math.inf if le is None else le for le, _, _ in acc["buckets"]]
        assert les == sorted(les) and len(set(les)) == len(les), name
    assert snap["stages"]["wait"]["s"] == st.stage_s["wait"]
    assert not [k for k in metrics if "bucket" in k or "long_wait" in k]


def test_a_percentile_of_two_snapshots_is_within_a_bucket_of_the_exact():
    """A scripted sequence of stage times between two snapshots: the
    window's percentile, read from the difference of the buckets as the
    benchmark's readers read it, lies in the bucket that holds the exact
    one (so within the ladder's ratio of it), whatever stood in the
    ladder before; the seconds above the long-wait bound are exact."""
    reader = _ladder_reader()
    rng = np.random.default_rng(54)
    st = ClassStats()

    def batch(seconds):
        st.add_stages({"engine.repair.wait": [1, seconds],
                       "engine.repair.queue": [1, 0.0],
                       "engine.repair.queue.coalesce": [1, 0.0],
                       "engine.repair.queue.wake": [1, seconds / 7]})

    for seconds in rng.lognormal(math.log(0.05), 1.0, 300):   # the warm-up
        batch(float(seconds))
    before = st.ladders["wait"].buckets(), st.ladders["queue.wake"].buckets()
    script = [float(x) for x in rng.lognormal(math.log(4e-3), 0.6, 997)]
    script += [0.3, 1.7, 0.2500001]
    for seconds in script:
        batch(seconds)
    after = st.ladders["wait"].buckets(), st.ladders["queue.wake"].buckets()
    got = reader.window(before[0], after[0])
    assert reader.count(got) == len(script)
    exact = sorted(script)
    for q in (0.5, 0.95, 0.99, 1.0):
        want = exact[max(1, math.ceil(q * len(exact))) - 1]
        read = reader.percentile_s(got, q)
        assert want / 1.1 <= read <= want * 1.1, q
    assert reader.seconds_over(got, trace.LONG_WAIT_S) == pytest.approx(
        0.3 + 1.7 + 0.2500001)
    wake = reader.window(before[1], after[1])
    assert reader.percentile_s(wake, 0.95) == pytest.approx(
        exact[math.ceil(0.95 * len(exact)) - 1] / 7, rel=0.1)
    assert reader.window(after[0], after[0]) == []
    assert reader.percentile_s([], 0.95) is None


@pytest.mark.parametrize("seconds,long", [(0.3, True), (0.001, False)],
                         ids=["0.3s", "1ms"])
def test_a_batchs_long_wait_is_kept_with_the_batch_it_was_of(
        seconds, long, monkeypatch):
    """A repair whose result blocks for 0.3 s: the batch's ``wait`` is in
    ``stats_snapshot()["long_waits"]`` with class, bucket, rows and lane,
    and in the flight journal; at 1 ms nothing is kept."""
    eng = make_engine(K, M, rs_backend="jax")
    coded = np.asarray(eng.encode(rnd((1, K, FRAG), 9)))
    eng.reconstruct(coded[:, 1:], (1, 2), (0,))         # compiled
    block = jax.block_until_ready

    def slow(x):
        time.sleep(seconds)
        return block(x)

    recorder = flight.FlightRecorder(b"pr54")
    try:
        monkeypatch.setattr(jax, "block_until_ready", slow)
        t0 = time.perf_counter()
        with flight.armed(recorder):
            eng.reconstruct(coded[:, 1:], (1, 2), (0,))
            eng.flush()
        t1 = time.perf_counter()
        snap = eng.stats_snapshot()
    finally:
        monkeypatch.undo()
        eng.close()
    notes = [e for e in recorder.journal_tail("engine")
             if e["kind"] == "long_wait"]
    if not long:
        assert snap["long_waits"] == [] and notes == []
        return
    (rec,) = snap["long_waits"]
    assert rec["stage"] == "engine.repair.wait" and rec["seconds"] >= 0.3
    assert (rec["cls"], rec["bucket"], rec["rows"], rec["lane"]) \
        == ("repair", 1, 1, None)
    assert t0 <= rec["start"] and rec["start"] + rec["seconds"] <= t1
    assert len(notes) == 1
    over = sum(s for le, _, s in
               snap["classes"]["repair"]["stages"]["wait"]["buckets"]
               if le is None or le > trace.LONG_WAIT_S)
    assert over == pytest.approx(rec["seconds"])


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
    # a put waits for the put two before it to have arrived, under a
    # stage and a counter of its own: one wait for every batch past the
    # second (serve/stream.py _run, PR 50)
    assert len(by("stream.gate")) == 1
    assert {s["parent_id"] for s in by("stream.stall") + by("stream.gate")
            + by("stream.stage")} == {run["span_id"]}
    # the counters kept their names, and are what the stages timed
    raw = ingest.stats.raw()
    assert raw["batches"] == 3 and raw["stall_s"] > 0
    assert 0.0 < raw["h2d_s"] <= sum(s["dur_s"]
                                     for s in by("stream.put")) + 1e-4
    assert 0.0 < raw["gate_s"] <= sum(s["dur_s"]
                                      for s in by("stream.gate")) + 1e-4


# -- the PoDR2 challenge -----------------------------------------------------
def test_challenge_is_a_stage_of_its_caller_and_never_of_a_trace():
    ids = np.stack([podr2.fragment_id_from_hash(bytes([i]) * 32)
                    for i in range(3)])
    tracer = obs.Tracer()
    before = podr2.stage_counters()
    with obs.armed(tracer):
        with trace.stage("tee.round.challenge"):
            idx, nu = podr2.gen_challenge(b"stage-seed", 64)
            r = podr2.aggregate_coeffs(b"stage-seed", ids)
    spans = {s["name"]: s for s in tracer.finished()}
    outer = spans["tee.round.challenge"]
    assert spans["podr2.challenge"]["parent_id"] == outer["span_id"]
    assert spans["podr2.coeffs"]["parent_id"] == outer["span_id"]
    after = podr2.stage_counters()
    assert set(after) == {"podr2.challenge", "podr2.coeffs"}
    for name in after:
        assert set(after[name]) == {"n", "s", "programs"}, name
        assert after[name]["n"] == before[name]["n"] + 1, name
        assert after[name]["s"] >= before[name]["s"], name
        assert after[name]["programs"] >= 1, name
    assert podr2.stage_metrics()["cess_podr2_challenge_count"] \
        == after["podr2.challenge"]["n"]
    assert podr2.stage_metrics()["cess_podr2_coeffs_programs"] \
        == after["podr2.coeffs"]["programs"]

    # reached while JAX traces a caller, the call is a piece of that
    # program: no stage, no count, no program of its own, the same values
    @jax.jit
    def traced(fragment_ids):
        return (podr2.gen_challenge(b"stage-seed", 64),
                podr2.aggregate_coeffs(b"stage-seed", fragment_ids))

    with obs.armed(tracer):
        (jidx, jnu), jr = traced(ids)
    assert podr2.stage_counters() == after
    assert len(tracer.finished()) == 3
    assert np.array_equal(jidx, idx) and np.array_equal(jnu, nu)
    assert np.array_equal(jr, r)


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
        for _ in stream.run(rnd((7, seg), 3)):
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
                               ev.start_ns, ev.start_ns + ev.duration_ns,
                               dict(ev.stats)))
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


@pytest.mark.parametrize("name", ["stream.stage", "stream.gate",
                                  "stream.put", "stream.dispatch",
                                  "stream.stall"])
def test_profile_holds_the_stream_stages(profiled, name):
    mine = [e for e in profiled if e[1] == name]
    # 7 streamed rows, 2 a batch: four batches, the gate from the third
    assert len(mine) >= 2
    assert all(e[3] >= e[2] for e in mine)


@pytest.mark.parametrize("cls", ["repair", "prove", "verify", "tag",
                                 "encode"])
def test_profile_holds_the_callers_side_on_the_callers_thread(profiled,
                                                              cls):
    """One ``submit`` and one ``result`` a request, on another thread than
    the batch; the batcher starts to resolve while the caller is blocked
    in ``result`` (the overlap that shows the hand-back)."""
    batches = [e for e in profiled if e[1] == f"engine.{cls}.batch"]
    submits = [e for e in profiled if e[1] == f"engine.{cls}.submit"]
    results = [e for e in profiled if e[1] == f"engine.{cls}.result"]
    assert len(submits) == len(results) == len(batches) >= 1
    assert {e[0] for e in submits} == {e[0] for e in results}
    assert not {e[0] for e in submits} & {e[0] for e in batches}
    for resolve in (e for e in profiled
                    if e[1] == f"engine.{cls}.resolve"):
        assert any(r[2] <= resolve[2] <= r[3] for r in results)
    # a request is submitted before its batch runs
    assert min(e[3] for e in submits) <= min(e[2] for e in batches)
    assert f"engine.{cls}.queue.coalesce" not in {e[1] for e in profiled}


def test_profile_holds_the_workers_jobs_inside_the_upload(profiled):
    """2 segments x 3 fragments: a copy and a hash a fragment, a hash a
    segment, each on a worker's thread, inside the upload's extent."""
    (upload,) = [e for e in profiled if e[1] == "offchain.upload"]
    copies = [e for e in profiled if e[1] == "gateway.worker.copy"]
    hashes = [e for e in profiled if e[1] == "gateway.worker.hash"]
    assert (len(copies), len(hashes)) == (2 * (K + M), 2 * (K + M + 1))
    for e in copies + hashes:
        assert e[0] != upload[0]
        assert upload[2] <= e[2] and e[3] <= upload[3]
    for part in ("jobs", "put", "step"):
        assert _inside(profiled, f"gateway.encode.{part}", "gateway.encode")


def test_profile_holds_the_challenge(profiled):
    names = [e[1] for e in profiled]
    assert names.count("podr2.challenge") == 1      # one audit round
    assert names.count("podr2.coeffs") == 1


def test_profile_holds_a_batchs_seq_in_its_five_stream_stages(profiled):
    """The annotation's metadata: 7 streamed rows, 2 a batch, are four
    batches, each of whose stages carry its ``seq`` (the gate from the
    third on), and the staging that found the source dry the fifth's."""
    by_seq: dict = {}
    for e in profiled:
        if e[1].startswith("stream."):
            by_seq.setdefault(e[4]["seq"], []).append(e[1])
    assert sorted(by_seq) == [0, 1, 2, 3, 4]
    for seq in range(4):
        want = ["stream.dispatch", "stream.put", "stream.stage",
                "stream.stall"] + (["stream.gate"] if seq >= 2 else [])
        assert sorted(by_seq[seq]) == sorted(want), seq
    assert by_seq[4] == ["stream.stage"]


def test_profile_has_one_event_per_stage_per_batch(profiled):
    names = [e[1] for e in profiled]
    assert names.count("engine.repair.batch") == 1
    for stage in DOCUMENTED[1:]:
        assert names.count(f"engine.repair.{stage}") == 1
    assert "engine.repair.queue" not in names      # a counter, no span
    assert names.count("offchain.upload") == 1
    assert names.count("gateway.fetch") == 2       # fragments, then tags
    assert names.count("gateway.hash") == 1        # one span, not per row
