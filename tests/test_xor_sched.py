"""XOR-scheduled codec path (ops/xor_sched.py compiler +
ops/rs_xor.py executors + the strategy="xor"/"auto" wiring in
ops/rs.py, ISSUE 18).

The contracts pinned here:

- compilation is a pure function of the matrix bytes: same bitmatrix,
  byte-identical ``XorSchedule.witness()``, every time;
- the CSE'd schedule computes EXACTLY the dense GF matmul (property
  test over random GF matrices, both executors);
- strategy="xor" is bit-identical to the CPU ReferenceCodec on every
  geometry — encode, reconstruct (random and all-parity survivor
  sets), decode_data, and the regen symbol fold;
- strategy="auto" (the compile-time cost model) never changes
  results, only which program serves them — and the choice is pinned
  on both sides of the decision boundary;
- warm/AOT programs stay device-keyed under the new strategies
  (mirrors tests/test_pool.py's warm pins).
"""
import jax
import numpy as np
import pytest

from cess_tpu.ops import gf, rs, rs_xor, xor_sched
from cess_tpu.ops.regen import RegenCodec, fold_symbol_pairs
from cess_tpu.ops.rs_ref import ReferenceCodec

GEOMETRIES = [(2, 1), (2, 2), (3, 3), (4, 8), (10, 4)]


def rnd(shape, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 256, shape, dtype=np.uint8)


# -- the compiler -----------------------------------------------------------

def test_witness_is_byte_identical_across_compiles():
    bmat = gf.expand_bitmatrix(gf.cauchy_parity_matrix(4, 8))
    first = xor_sched.compile_schedule(bmat)
    w1 = first.witness()
    # clear the memo so the second compile actually recomputes
    xor_sched._compile_cached.cache_clear()
    second = xor_sched.compile_schedule(bmat)
    assert second.witness() == w1
    assert second == first
    # and the cached path returns the identical object
    assert xor_sched.compile_schedule(bmat) is second


def test_4p8_encode_matrix_meets_the_saving_bar():
    sched = xor_sched.compile_schedule(
        gf.expand_bitmatrix(gf.cauchy_parity_matrix(4, 8)))
    # acceptance: >= 25% XOR reduction vs the dense bitmatrix
    assert sched.saving_frac >= 0.25
    assert sched.n_xors < sched.dense_xors
    assert sched.saving_frac == pytest.approx(
        1.0 - sched.n_xors / sched.dense_xors)
    # scratch is liveness-bounded far below the intermediate count
    assert 1 <= sched.n_scratch < sched.n_xors
    d = sched.dump()
    assert d["kind"] == "xor_schedule"
    assert d["scratch_high_water"] == sched.n_scratch
    assert sum(d["op_counts"].values()) == d["total_ops"] == len(sched.ops)


def test_compile_rejects_non_bitmatrix_shapes():
    with pytest.raises(ValueError):
        xor_sched.compile_schedule(np.zeros((7, 16), np.uint8))
    with pytest.raises(ValueError):
        xor_sched.compile_schedule(np.zeros(16, np.uint8))


@pytest.mark.parametrize("seed", range(6))
def test_schedule_matches_dense_gf_matmul(seed):
    """Property test: over random GF matrices and data, the compiled
    schedule (both executors) equals the dense GF matmul oracle."""
    rng = np.random.default_rng(seed)
    r = int(rng.integers(1, 7))
    q = int(rng.integers(1, 7))
    mat = rng.integers(0, 256, (r, q), dtype=np.uint8)
    sched = xor_sched.compile_schedule(gf.expand_bitmatrix(mat))
    n = int(rng.integers(1, 200))
    data = rng.integers(0, 256, (2, q, n), dtype=np.uint8)
    want = np.stack([gf.gf_matmul(mat, data[i]) for i in range(2)])
    got = np.asarray(rs_xor.apply_schedule(sched, data, force="jnp"))
    assert np.array_equal(got, want)


def test_pallas_executor_matches_jnp_executor():
    # the kernel path, interpret-mode on the CPU mesh, small tile so
    # the grid actually iterates
    mat = gf.cauchy_parity_matrix(3, 3)
    sched = xor_sched.compile_schedule(gf.expand_bitmatrix(mat))
    data = rnd((2, 3, 100), seed=9)
    want = np.asarray(rs_xor.apply_schedule(sched, data, force="jnp"))
    got = np.asarray(rs_xor.apply_schedule(sched, data, tile_lanes=8,
                                           force="pallas"))
    assert np.array_equal(got, want)
    assert np.array_equal(want[0], gf.gf_matmul(mat, data[0]))


def test_executor_handles_leading_dims_and_row_mismatch():
    sched = xor_sched.compile_schedule(
        gf.expand_bitmatrix(gf.cauchy_parity_matrix(2, 1)))
    data = rnd((2, 3, 2, 33), seed=10)
    out = np.asarray(rs_xor.apply_schedule(sched, data, force="jnp"))
    assert out.shape == (2, 3, 1, 33)
    with pytest.raises(ValueError):
        rs_xor.apply_schedule(sched, rnd((3, 33), seed=1))


# -- strategy="xor" vs the reference codec ----------------------------------

@pytest.mark.parametrize("k,m", GEOMETRIES)
def test_xor_strategy_bit_identical_to_reference(k, m):
    ref = ReferenceCodec(k, m)
    codec = rs.TPUCodec(k, m, strategy="xor")
    rng = np.random.default_rng(k * 31 + m)
    data = rnd((3, k, 129), seed=k * 7 + m)
    coded_ref = np.asarray(ref.encode(data))
    assert np.array_equal(np.asarray(codec.encode(data)), coded_ref)
    # a random survivor set
    present = tuple(sorted(
        rng.choice(k + m, size=k, replace=False).tolist()))
    missing = tuple(i for i in range(k + m) if i not in present)
    surv = coded_ref[:, list(present)]
    assert np.array_equal(
        np.asarray(codec.reconstruct(surv, present, missing)),
        np.asarray(ref.reconstruct(surv, present, missing)))
    assert np.array_equal(
        np.asarray(codec.decode_data(surv, present)), data)
    # the all-parity survivor set (every data row lost), when it exists
    if m >= k:
        present = tuple(range(k, 2 * k))
        missing = tuple(range(k))
        surv = coded_ref[:, list(present)]
        assert np.array_equal(
            np.asarray(codec.reconstruct(surv, present, missing)),
            data)


def test_regen_fold_path_bit_identical_under_xor():
    codec = RegenCodec(4, 8, strategy="xor")
    pairs = rnd((3, 2, 65), seed=12)
    for coeff in (1, 7, 213):
        want = fold_symbol_pairs(pairs, coeff)
        got = np.asarray(codec.fold_symbol(pairs, coeff))
        assert np.array_equal(got, want)
    # and the regen closed-form reconstruct under the xor strategy
    ref = ReferenceCodec(4, 8)
    data = rnd((2, 4, 64), seed=13)
    coded = np.asarray(ref.encode(data))
    present, missing = (1, 3, 5, 9), (0,)
    assert np.array_equal(
        np.asarray(codec.reconstruct(coded[:, list(present)], present,
                                     missing)),
        coded[:, list(missing)])


# -- the compile-time cost model (strategy="auto") --------------------------

def test_cost_model_pins_both_sides_of_the_boundary():
    sched = xor_sched.compile_schedule(
        gf.expand_bitmatrix(gf.cauchy_parity_matrix(4, 8)))
    # tiny dispatch: per-instruction issue overhead dominates — dense
    small = xor_sched.estimate(sched.r8, sched.q8, sched.n_xors, 2)
    assert small["chosen"] == "dense"
    # wide dispatch: the issue cost amortizes and sparse work wins
    big = xor_sched.estimate(sched.r8, sched.q8, sched.n_xors, 64)
    assert big["chosen"] == "xor"
    for est in (small, big):
        assert est["n_xors"] == sched.n_xors
        assert isinstance(est["dense_cost"], int)
        assert isinstance(est["xor_cost"], int)


def test_auto_never_changes_results_only_programs():
    ref = ReferenceCodec(4, 8)
    codec = rs.TPUCodec(4, 8, strategy="auto")
    for batch in (1, 64):   # both sides of the decision boundary
        data = rnd((batch, 4, 64), seed=batch)
        assert np.array_equal(np.asarray(codec.encode(data)),
                              np.asarray(ref.encode(data)))
    meta_small = codec.program_meta("encode", shape=(1, 4, 64))
    meta_big = codec.program_meta("encode", shape=(64, 4, 64))
    assert dict(meta_small)["strategy"] == "auto:dense"
    assert dict(meta_big)["strategy"] == "auto:xor"


def test_explicit_strategy_always_forces():
    mat = gf.cauchy_parity_matrix(4, 8)
    forced = rs._MatrixApply(mat, "xor")
    # forced meta never says "auto:", whatever the shape
    assert dict(forced.cache_meta((1, 4, 64)))["strategy"] == "xor"
    assert dict(forced.cache_meta((64, 4, 64)))["strategy"] == "xor"
    # default strategies stay invisible in cache keys (zero-cost seam)
    assert rs._MatrixApply(mat, rs.default_strategy()).cache_meta(
        (64, 4, 64)) == ()
    # and a default-strategy codec reports no program meta at all
    assert rs.TPUCodec(4, 8).program_meta("encode",
                                          shape=(64, 4, 64)) == ()


# -- warm/AOT programs stay device-keyed (mirrors test_pool) ----------------

def test_warm_reconstruct_device_keys_under_xor_strategy():
    devs = jax.devices()
    assert len(devs) >= 2       # conftest: virtual CPU devices
    codec = rs.TPUCodec(2, 1, strategy="xor")
    data = rnd((2, 256), seed=21)
    coded = np.asarray(codec.encode(data))
    surv, present, missing = coded[[1, 2]], (1, 2), (0,)
    codec.warm_reconstruct(present, missing, surv.shape,
                           device=devs[0])
    # a dev-0 executable must not hit under dev-1's placement scope
    with jax.default_device(devs[1]):
        out = np.asarray(codec.reconstruct(surv, present, missing))
    assert codec.warm_hits == 0
    assert np.array_equal(out[0], data[0])
    codec.warm_reconstruct(present, missing, surv.shape,
                           device=devs[1])
    with jax.default_device(devs[1]):
        out2 = np.asarray(codec.reconstruct(surv, present, missing))
    assert codec.warm_hits == 1
    assert np.array_equal(out2, out)


def test_engine_warm_repair_keys_carry_cost_model_meta():
    from cess_tpu.serve import AdmissionPolicy, DevicePool, make_engine

    eng = make_engine(2, 1, rs_backend="jax", strategy="auto",
                      policy=AdmissionPolicy(max_delay=0.002),
                      pool=DevicePool(n=2))
    try:
        eng.warm_repair([((1, 2), (0,))], 256, buckets=(1,))
        meta = eng.codec.program_meta("repair", (1, 2), (0,),
                                      (1, 2, 256))
        assert dict(meta)["strategy"].startswith("auto:")
        # one device-free program + one per lane, all under the exact
        # meta-extended keys _op_repair looks up
        base = ("repair", 2, 1, 256, 1)
        keys = {base + meta,
                base + (("device", 0),) + meta,
                base + (("device", 1),) + meta}
        assert keys <= set(eng.programs._programs)
        warm_devices = {k[-1] for k in eng.codec._warm}
        assert {d for d in warm_devices if d is not None} \
            == {eng.pool.lanes[0].device, eng.pool.lanes[1].device}
        # the warmed program actually serves: a reconstruct through
        # the engine is bit-identical and hits the AOT path
        data = rnd((1, 2, 256), seed=22)
        coded = np.asarray(ReferenceCodec(2, 1).encode(data))
        out = eng.reconstruct(coded[:, [1, 2]], (1, 2), (0,),
                              timeout=60)
        assert np.array_equal(np.asarray(out), coded[:, [0]])
        assert eng.codec.warm_hits >= 1
    finally:
        eng.close()
