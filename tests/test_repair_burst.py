"""A deal's segments rebuilt in one burst (PR 52): RS(4,8) with four of a
segment's twelve rows lost — BASELINE's "4-erasure batched decode" as the
engine's repair class meets it. A deal's segments share their holders, so
a burst is eight per-segment requests of ONE loss pattern, each the list
of its four lowest survivors' rows as they lie, submitted in a row from
one thread (benchmark cell ``repair-4p8.erasure4``). Pinned here on the
CPU mesh at small n:

- a burst through a default-policy engine equals
  ``ReferenceCodec.reconstruct`` for ALL C(12,4) = 495 lost sets, data
  rows among the lost (the matrix a true inverse) or not;
- after ``warm_repair(..., buckets=(1, 2, 4, 8))`` a burst compiles
  nothing and builds no program, however many of its requests a batch
  holds (every count that pads to a bucket);
- the batcher coalesces: requests that gather behind a busy executor
  leave as one batch (``batched_requests`` > ``batches``);
- a one-segment request's many-row result is a VIEW of the one piece its
  batch row left the device as (PR 53): ``result_bytes`` counts it,
  ``regroup_s`` / ``regrouped_bytes`` stay 0 and no
  ``cess:engine.repair.fetch.regroup`` span is made, at every count of
  requests a batch can hold; a request of several segments still
  regroups, once a batch, inside ``fetch``.
"""
import glob
import itertools
import os

import numpy as np
import pytest

import jax

from cess_tpu import obs
from cess_tpu.obs import trace
from cess_tpu.ops.rs_ref import ReferenceCodec
from cess_tpu.serve import AdmissionPolicy, make_engine

K, M, LOST, BURST = 4, 8, 4, 8
N = 128
LOST_SETS = list(itertools.combinations(range(K + M), LOST))
SLICES = 15                   # 495 = 15 x 33
REF = ReferenceCodec(K, M)
CODED = REF.encode(np.random.default_rng(52).integers(
    0, 256, (BURST, K, N), dtype=np.uint8))
REGROUP = "engine.repair.fetch.regroup"


def _helpers(lost) -> tuple:
    """The four lowest surviving rows, the order MinerAgent.try_repair
    asks its peers in."""
    return tuple(j for j in range(K + M) if j not in lost)[:K]


def _burst(eng, lost, size=BURST) -> list:
    """``size`` per-segment requests of one pattern, submitted in a row;
    each a list of 1-D views of the pool."""
    helpers = _helpers(lost)
    return [eng.submit_reconstruct([CODED[s, j] for j in helpers],
                                   helpers, lost) for s in range(size)]


def _check(futs, lost) -> None:
    helpers = _helpers(lost)
    for s, fut in enumerate(futs):
        got = fut.result(60)
        assert isinstance(got, np.ndarray) and got.shape == (LOST, N)
        assert np.array_equal(got, REF.reconstruct(
            CODED[s, list(helpers)], helpers, lost)), (s, lost)
        assert np.array_equal(got, CODED[s, list(lost)]), (s, lost)


def _several(eng, lost, count=3):
    """One request of ``count`` segments: ``reconstruct`` of
    ``[count, k, n]``, the request that still regroups."""
    helpers = _helpers(lost)
    got = eng.reconstruct(CODED[:count][:, list(helpers)], helpers, lost)
    assert np.array_equal(got, CODED[:count][:, list(lost)])


def _root(a: np.ndarray) -> np.ndarray:
    """The array whose buffer ``a`` is a view of."""
    while isinstance(a.base, np.ndarray):
        a = a.base
    return a


def _repair(eng) -> dict:
    eng.flush()
    return eng.stats_snapshot()["classes"]["repair"]


def test_the_lost_sets_are_all_of_them():
    assert len(LOST_SETS) == 495 == SLICES * 33
    # 70 sets lose parity rows only; the other 425 invert data rows
    assert sum(all(j >= K for j in lost) for lost in LOST_SETS) == 70


@pytest.mark.parametrize("part", range(SLICES))
def test_a_burst_equals_the_reference_for_every_lost_set(part):
    eng = make_engine(K, M, rs_backend="jax")          # default policy
    try:
        assert eng.policy.max_delay is None
        for lost in LOST_SETS[part::SLICES]:
            _check(_burst(eng, lost), lost)
        st = _repair(eng)
    finally:
        eng.close()
    assert st["completed"] == st["batched_requests"] == 33 * BURST
    assert st["failed"] == 0 and st["linear_puts"] == st["batches"]
    assert st["drains"]["idle"] == st["batches"]
    assert st["queue"]["coalesce"]["s"] == 0.0


@pytest.mark.parametrize("size", range(1, BURST + 1))
def test_a_warm_burst_compiles_nothing(size, compiles):
    """Held by a window and forced out by the flush, ``size`` requests
    are one batch of exactly that many: every count that pads to a
    warmed bucket (3 in 4; 5, 6, 7 in 8) runs what the bucket loaded."""
    eng = make_engine(K, M, rs_backend="jax",
                      policy=AdmissionPolicy(max_delay=30.0))
    try:
        eng.warm_repair([((4, 5, 6, 7), (0, 1, 2, 3))], N,
                        buckets=(1, 2, 4, 8))
        warmed = eng.stats_snapshot()["programs_built"]
        # a repair program a bucket, a flatten a count of requests
        assert warmed == len(eng.programs) == 4 + 8
        compiled = compiles()
        lost = (1, 2, 6, 10)            # a pattern never named
        futs = _burst(eng, lost, size)
        eng.flush()
        _check(futs, lost)
        st = _repair(eng)
        assert st["batches"] == 1 and st["batched_requests"] == size
        assert st["rows"] == size
        assert st["rows"] + st["padded_rows"] in (1, 2, 4, 8)
        assert st["drains"]["forced"] == 1
        assert compiles() == compiled
        assert eng.stats_snapshot()["programs_built"] == warmed
    finally:
        eng.close()


def test_a_default_policy_burst_compiles_nothing_however_it_splits(
        compiles):
    eng = make_engine(K, M, rs_backend="jax")
    try:
        eng.warm_repair([((4, 5, 6, 7), (0, 1, 2, 3))], N,
                        buckets=(1, 2, 4, 8))
        warmed = eng.stats_snapshot()["programs_built"]
        compiled = compiles()
        for lost in LOST_SETS[7::45]:
            _check(_burst(eng, lost), lost)
        st = _repair(eng)
        assert compiles() == compiled
        assert eng.stats_snapshot()["programs_built"] == warmed
    finally:
        eng.close()
    # eleven bursts of eight: they did not all go request by request
    assert st["batched_requests"] == 11 * BURST > st["batches"]
    assert st["batch_occupancy"] > 1


def test_a_burst_behind_a_busy_batcher_is_one_plus_seven(gate):
    """The split the idle rule allows: the first request trips at its own
    enqueue and goes alone at bucket 1; the seven that gather while it
    runs leave together, padded to bucket 8."""
    eng = make_engine(K, M, rs_backend="jax")
    lost = (0, 3, 4, 11)
    try:
        held = gate(eng, "repair")
        helpers = _helpers(lost)
        first = eng.submit_reconstruct(
            [CODED[0, j] for j in helpers], helpers, lost)
        assert held.running()                  # the batcher took it alone
        rest = [eng.submit_reconstruct([CODED[s, j] for j in helpers],
                                       helpers, lost)
                for s in range(1, BURST)]
        held.open()
        _check([first, *rest], lost)
        st = _repair(eng)
    finally:
        eng.close()
    assert (st["batches"], st["batched_requests"]) == (2, BURST)
    assert (st["rows"], st["padded_rows"]) == (BURST, 1)
    assert st["drains"] == {"idle": 2, "window": 0, "size": 0, "forced": 0}
    assert st["queue"]["coalesce"]["s"] == 0.0 < st["queue"]["wake"]["s"]


# -- what a many-row result costs -------------------------------------------
def test_a_burst_of_many_row_results_is_handed_views():
    eng = make_engine(K, M, rs_backend="jax",
                      policy=AdmissionPolicy(max_delay=30.0))
    lost = (0, 2, 5, 9)
    try:
        futs = _burst(eng, lost)
        eng.flush()
        _check(futs, lost)
        st = _repair(eng)
        flat = eng.stats_metrics()
    finally:
        eng.close()
    assert st["batches"] == st["linear_fetches"] == 1
    # eight requests of four rebuilt rows each, none copied once more
    assert st["result_bytes"] == BURST * LOST * N
    assert st["regrouped_bytes"] == 0 and st["regroup_s"] == 0.0
    for name in ("result_bytes", "regroup_s", "regrouped_bytes",
                 "batched_requests"):
        assert flat[f"cess_engine_repair_{name}"] == st[name]
    for s, fut in enumerate(futs):
        got = fut.result(0)
        assert got.flags.c_contiguous
        # its four rows lie end to end in the one fetched piece, which
        # is its own and nobody else's
        piece = _root(got)
        assert piece is not got and piece.shape == (LOST * N,)
        assert all(_root(row) is piece for row in got)
        assert np.array_equal(got, CODED[s, list(lost)])


@pytest.mark.parametrize("size", range(1, BURST + 1))
def test_every_count_of_requests_a_batch_is_views_of_the_reference(size):
    """Every padded count of bucket 8: ``size`` requests, one batch, each
    result its own piece and byte-equal to the reference."""
    eng = make_engine(K, M, rs_backend="jax",
                      policy=AdmissionPolicy(max_delay=30.0))
    lost = (3, 4, 8, 11)
    try:
        futs = _burst(eng, lost, size)
        eng.flush()
        _check(futs, lost)
        st = _repair(eng)
    finally:
        eng.close()
    assert st["batches"] == st["linear_fetches"] == 1
    assert st["batched_requests"] == size
    assert st["result_bytes"] == size * LOST * N
    assert st["regrouped_bytes"] == 0 and st["regroup_s"] == 0.0
    got = [fut.result(0) for fut in futs]
    assert all(g.flags.c_contiguous for g in got)
    assert not any(np.shares_memory(a, b)
                   for a, b in itertools.combinations(got, 2))


def test_a_warmed_shape_builds_no_program_during_a_burst(compiles):
    """The flatten of ``r > 1`` is warmed with its shape like any other:
    bursts of every size after ``warm_repair`` leave ``programs_built``
    and the compile count flat."""
    eng = make_engine(K, M, rs_backend="jax")
    try:
        eng.warm_repair([((4, 5, 6, 7), (0, 1, 2, 3))], N,
                        buckets=(1, 2, 4, 8))
        warmed = eng.stats_snapshot()["programs_built"]
        compiled = compiles()
        for size, lost in zip(range(1, BURST + 1), LOST_SETS[3::61]):
            _check(_burst(eng, lost, size), lost)
        st = _repair(eng)
        assert eng.stats_snapshot()["programs_built"] == warmed
        assert compiles() == compiled
    finally:
        eng.close()
    assert st["completed"] == sum(range(1, BURST + 1))
    assert st["regrouped_bytes"] == 0 < st["result_bytes"]


def test_a_request_of_several_segments_is_regrouped():
    """``[B, k, n]`` in one request: its ``B`` pieces are its own, and
    are stacked once into its ``[B, r, n]``."""
    eng = make_engine(K, M, rs_backend="jax")
    lost = (1, 4, 6, 7)
    helpers = _helpers(lost)
    try:
        got = eng.reconstruct(CODED[:3][:, list(helpers)], helpers, lost)
        st = _repair(eng)
    finally:
        eng.close()
    assert np.array_equal(got, CODED[:3][:, list(lost)])
    assert st["result_bytes"] == st["regrouped_bytes"] == 3 * LOST * N
    assert 0.0 < st["regroup_s"] <= st["stages"]["fetch"]["s"]


def test_a_one_row_result_is_a_view_and_regroups_nothing():
    tracer = obs.Tracer()
    eng = make_engine(K, M, rs_backend="jax", tracer=tracer)
    try:
        for row in (0, 5, 11):
            helpers = _helpers((row,))
            got = eng.reconstruct([CODED[0, j] for j in helpers], helpers,
                                  (row,))
            assert np.array_equal(got, CODED[0, [row]])
        st = _repair(eng)
    finally:
        eng.close()
    assert st["batches"] == st["linear_fetches"] == 3
    assert st["result_bytes"] == 3 * N
    assert st["regrouped_bytes"] == 0 and st["regroup_s"] == 0.0
    names = [s["name"] for s in tracer.finished()]
    assert names.count("engine.repair.fetch") == 3
    assert REGROUP not in names


def test_a_device_submitter_is_handed_no_host_bytes():
    import jax.numpy as jnp

    eng = make_engine(K, M, rs_backend="jax")
    lost = (0, 1, 2, 3)
    helpers = _helpers(lost)
    try:
        got = eng.reconstruct(jnp.asarray(CODED[0, list(helpers)]),
                              helpers, lost)
        st = _repair(eng)
    finally:
        eng.close()
    assert isinstance(got, jax.Array)
    assert np.array_equal(np.asarray(got), CODED[0, list(lost)])
    assert st["result_bytes"] == st["regrouped_bytes"] == 0
    assert st["linear_fetches"] == 0


def test_the_regroup_is_a_child_span_of_the_fetch_stage():
    tracer = obs.Tracer()
    eng = make_engine(K, M, rs_backend="jax", tracer=tracer,
                      policy=AdmissionPolicy(max_delay=30.0))
    lost = (2, 3, 8, 10)
    try:
        for _ in range(2):
            _several(eng, lost)
        futs = _burst(eng, lost)          # a batch that regroups nothing
        eng.flush()
        _check(futs, lost)
        st = _repair(eng)
    finally:
        eng.close()
    spans = tracer.finished()
    fetches = {s["span_id"] for s in spans
               if s["name"] == "engine.repair.fetch"}
    regroups = [s for s in spans if s["name"] == REGROUP]
    # one a batch that holds a several-segment request, never one a
    # request or a piece, and none for the burst's batch
    assert len(fetches) == st["batches"] == 3
    assert len(regroups) == 2
    assert {s["parent_id"] for s in regroups} < fetches
    assert st["regrouped_bytes"] == 2 * 3 * LOST * N
    # the six stages keep their names and counts: the regroup is no stage
    # of the batch's own
    assert set(st["stages"]) == {"queue", "assemble", "dispatch", "wait",
                                 "fetch", "resolve"}
    assert all(acc["n"] == 3 for acc in st["stages"].values())


def test_a_profiler_trace_holds_the_regroup_inside_the_fetch(tmp_path):
    from jax.profiler import ProfileData

    assert trace.armed_tracer() is None
    eng = make_engine(K, M, rs_backend="jax")
    lost = (0, 6, 7, 9)
    try:
        _several(eng, lost)                         # loaded before the trace
        _check(_burst(eng, lost), lost)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            _several(eng, lost)
            _several(eng, lost, 2)
            _check(_burst(eng, lost), lost)
            eng.flush()
        finally:
            jax.profiler.stop_trace()
    finally:
        eng.close()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    events = [(i, ev.name[len(trace.STAGE_PREFIX):], ev.start_ns,
               ev.start_ns + ev.duration_ns)
              for i, line in enumerate(
                  ln for plane in ProfileData.from_file(path).planes
                  for ln in plane.lines)
              for ev in line.events
              if ev.name.startswith(trace.STAGE_PREFIX)]
    fetches = [e for e in events if e[1] == "engine.repair.fetch"]
    regroups = [e for e in events if e[1] == REGROUP]
    # the several-segment requests' batches regroup, the burst's do not
    assert len(regroups) == 2 < len(fetches)
    for e in regroups:
        assert any(f[0] == e[0] and f[2] <= e[2] and e[3] <= f[3]
                   for f in fetches)
