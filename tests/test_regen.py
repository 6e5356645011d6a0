"""Regenerating-code repair plane (cess_tpu/ops/regen.py, ISSUE 15).

The load-bearing contract everywhere: the FAST constructions are
BIT-IDENTICAL to the reference path — ``cauchy_inverse`` to
Gauss-Jordan ``gf.gf_mat_inv``, the Schur-complement ``decode_matrix``
to ``gf.decode_matrix``, the partial-sum symbol chain to a whole
``reconstruct``. "Faster" is never allowed to mean "different bytes".

conftest.py splits the CPU backend into 8 virtual devices, so the
device-keyed warm tests run in the tier-1 CPU gate.
"""
import itertools

import jax
import numpy as np
import pytest

from cess_tpu.ops import gf, regen, rs
from cess_tpu.ops.rs_ref import ReferenceCodec
from cess_tpu.serve import AdmissionPolicy, DevicePool, make_engine

GEOMETRIES = ((2, 1), (2, 2), (3, 3), (4, 8), (10, 4))


def rnd(shape, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 255, shape, dtype=np.uint8)


def _patterns(k, m, limit=40):
    """Deterministic sample of k-survivor patterns for RS(k, m):
    every pattern for small geometries, an evenly-strided subset for
    the big ones — always including the all-parity and the
    minimal-data extremes when they exist."""
    combos = list(itertools.combinations(range(k + m), k))
    if len(combos) <= limit:
        return combos
    step = len(combos) // limit
    picked = combos[::step][:limit]
    if m >= k:                      # all-parity survivor set exists
        all_parity = tuple(range(k, 2 * k))
        if all_parity not in picked:
            picked.append(all_parity)
    return picked


# -- the closed-form Cauchy inverse (arxiv 1611.09968) ----------------------

class TestCauchyInverse:
    def test_matches_gauss_jordan_for_every_size(self):
        # the subsystem nodes decode_matrix actually builds: x-nodes
        # are parity rows k+q, y-nodes are missing data columns
        for k, m in GEOMETRIES:
            for t in range(1, min(k, m) + 1):
                xs = [k + q for q in range(t)]
                ys = list(range(t))
                a = np.array([[gf.gf_inv(x ^ y) for y in ys]
                              for x in xs], dtype=np.uint8)
                fast = regen.cauchy_inverse(xs, ys)
                slow = gf.gf_mat_inv(a)
                assert np.array_equal(fast, slow), (k, m, t)

    def test_really_inverts(self):
        xs, ys = [4, 5, 7], [0, 1, 2]
        a = np.array([[gf.gf_inv(x ^ y) for y in ys] for x in xs],
                     dtype=np.uint8)
        prod = gf.gf_matmul(regen.cauchy_inverse(xs, ys), a)
        assert np.array_equal(prod, np.eye(3, dtype=np.uint8))

    def test_refuses_bad_node_sets(self):
        with pytest.raises(ValueError, match="square"):
            regen.cauchy_inverse([1, 2], [3])
        with pytest.raises(ValueError, match="distinct"):
            regen.cauchy_inverse([1, 1], [2, 3])
        with pytest.raises(ValueError, match="distinct"):
            regen.cauchy_inverse([1, 2], [2, 3])


# -- decode / repair matrices: byte-identical to the gf reference -----------

class TestDecodeMatrix:
    def test_bit_identical_to_reference_every_pattern(self):
        for k, m in GEOMETRIES:
            for present in _patterns(k, m):
                fast = regen.decode_matrix(k, m, present)
                slow = gf.decode_matrix(k, m, present)
                assert np.array_equal(fast, slow), (k, m, present)

    def test_all_parity_survivors(self):
        # the hardest pattern: zero data rows survive, the whole
        # decode is the Cauchy subsystem
        for k, m in ((2, 2), (3, 3), (4, 8)):
            present = tuple(range(k, 2 * k))
            fast = regen.decode_matrix(k, m, present)
            assert np.array_equal(fast, gf.decode_matrix(k, m, present))
            # and it really decodes: survivors = parity of known data
            data = rnd((k, 64), seed=k)
            coded = ReferenceCodec(k, m).encode(data)
            got = gf.gf_matmul(fast, coded[list(present)])
            assert np.array_equal(got, data)

    def test_permuted_present_order(self):
        # decode matrices are position-sensitive: survivor column p
        # corresponds to present[p], in the caller's order
        for present in ((3, 1), (1, 3), (2, 0), (0, 2)):
            fast = regen.decode_matrix(2, 2, present)
            assert np.array_equal(fast, gf.decode_matrix(2, 2, present))

    def test_no_missing_is_identity_permutation(self):
        mat = regen.decode_matrix(3, 3, (2, 0, 1))
        assert np.array_equal(mat, gf.decode_matrix(3, 3, (2, 0, 1)))
        data = rnd((3, 16), 3)
        assert np.array_equal(gf.gf_matmul(mat, data[[2, 0, 1]]), data)

    def test_refusals(self):
        with pytest.raises(ValueError, match="exactly k=2"):
            regen.decode_matrix(2, 2, (0, 1, 2))
        with pytest.raises(ValueError, match="duplicate"):
            regen.decode_matrix(2, 2, (1, 1))
        with pytest.raises(ValueError, match="out of range"):
            regen.decode_matrix(2, 2, (0, 4))

    def test_repair_matrix_matches_reference(self):
        for k, m in GEOMETRIES:
            for present in _patterns(k, m, limit=10):
                missing = tuple(r for r in range(k + m)
                                if r not in present)[:2]
                if not missing:
                    continue
                fast = regen.repair_matrix(k, m, present, missing)
                slow = gf.repair_matrix(k, m, present, missing)
                assert np.array_equal(fast, slow), (k, m, present)

    def test_repair_matrix_refuses_bad_missing(self):
        with pytest.raises(ValueError, match="duplicate missing"):
            regen.repair_matrix(2, 2, (0, 1), (2, 2))
        with pytest.raises(ValueError, match="out of range"):
            regen.repair_matrix(2, 2, (0, 1), (9,))


# -- the partial-sum symbol chain (arxiv 1412.3022) -------------------------

class TestSymbolChain:
    def test_coeffs_regenerate_one_row(self):
        with pytest.raises(ValueError, match="ONE row"):
            regen.repair_coeffs(2, 2, (0, 1), (2, 3))

    @pytest.mark.parametrize("k,m", ((2, 1), (2, 2), (4, 8), (10, 4)))
    def test_chain_equals_reference_reconstruct(self, k, m):
        data = rnd((k, 128), seed=k * 17 + m)
        coded = ReferenceCodec(k, m).encode(data)
        for present in _patterns(k, m, limit=6):
            for lost in [r for r in range(k + m) if r not in present][:2]:
                coeffs = regen.repair_coeffs(k, m, present, (lost,))
                # each helper folds coeff*fragment into the running
                # accumulator; the final aggregate IS the lost row
                acc = np.zeros(128, dtype=np.uint8)
                for p, row in enumerate(present):
                    acc = regen.fold_symbol_host(acc, coded[row],
                                                 coeffs[p])
                want = ReferenceCodec(k, m).reconstruct(
                    coded[list(present)], present, (lost,))[0]
                assert np.array_equal(acc, want), (present, lost)

    def test_pairs_twin_matches_host_fold(self):
        pairs = rnd((5, 2, 64), 9)
        for coeff in (0, 1, 2, 255):
            got = regen.fold_symbol_pairs(pairs, coeff)
            assert got.shape == (5, 1, 64)
            for b in range(5):
                want = regen.fold_symbol_host(pairs[b, 0], pairs[b, 1],
                                              coeff)
                assert np.array_equal(got[b, 0], want)

    def test_pairs_twin_refuses_non_pairs(self):
        with pytest.raises(ValueError, match="row pairs"):
            regen.fold_symbol_pairs(rnd((3, 64), 1), 7)


# -- RegenReference: the NumPy oracle -----------------------------------

class TestRegenReference:
    @pytest.mark.parametrize("k,m", ((2, 1), (2, 2), (4, 8)))
    def test_identical_to_reference_codec(self, k, m):
        ref, fast = ReferenceCodec(k, m), regen.RegenReference(k, m)
        data = rnd((2, k, 96), seed=k + m)
        coded = ref.encode(data)
        assert np.array_equal(fast.encode(data), coded)
        for present in _patterns(k, m, limit=5):
            surv = coded[:, list(present)]
            assert np.array_equal(fast.decode_data(surv, present),
                                  ref.decode_data(surv, present))
            missing = tuple(r for r in range(k + m)
                            if r not in present)
            if missing:
                assert np.array_equal(
                    fast.reconstruct(surv, present, missing),
                    ref.reconstruct(surv, present, missing))

    def test_fold_and_coeffs_surface(self):
        fast = regen.RegenReference(2, 2)
        pairs = rnd((2, 2, 32), 4)
        assert np.array_equal(fast.fold_symbol(pairs, 9),
                              regen.fold_symbol_pairs(pairs, 9))
        assert fast.repair_coeffs((1, 2), (0,)) == \
            regen.repair_coeffs(2, 2, (1, 2), (0,))


# -- RegenCodec: the device path behind the ErasureCodec gate ---------------

class TestRegenCodec:
    def test_make_codec_gate(self):
        codec = rs.make_codec(2, 2, backend="regen")
        assert isinstance(codec, regen.RegenCodec)
        with pytest.raises(ValueError):
            rs.make_codec(2, 2, backend="nope")

    def test_device_path_bit_identical(self):
        k, m = 2, 2
        codec = rs.make_codec(k, m, backend="regen")
        ref = regen.RegenReference(k, m)
        data = rnd((3, k, 256), 21)
        coded = np.asarray(codec.encode(data))
        assert np.array_equal(coded, ref.encode(data))
        for present in ((2, 3), (1, 2), (0, 3)):
            surv = coded[:, list(present)]
            missing = tuple(r for r in range(k + m)
                            if r not in present)
            assert np.array_equal(
                np.asarray(codec.reconstruct(surv, present, missing)),
                ref.reconstruct(surv, present, missing))
            assert np.array_equal(
                np.asarray(codec.decode_data(surv, present)),
                ref.decode_data(surv, present))

    def test_fold_symbol_matches_host_twin(self):
        # direct construction: make_codec is lru_cached, and these
        # tests assert per-instance warm/hit state
        codec = regen.RegenCodec(2, 1)
        pairs = rnd((4, 2, 128), 31)
        for coeff in (1, 3, 200):
            assert np.array_equal(
                np.asarray(codec.fold_symbol(pairs, coeff)),
                regen.fold_symbol_pairs(pairs, coeff))

    @pytest.mark.parametrize("strategy", ["gather", "pallas"])
    def test_warm_fold_hits(self, strategy, compiles):
        codec = regen.RegenCodec(2, 1, strategy=strategy)
        pairs = rnd((2, 2, 72), 5)  # a width no other test compiles
        codec.warm_fold(7, pairs.shape)
        compiled = compiles()
        out_warm = np.asarray(codec.fold_symbol(pairs, 7))
        assert compiles() == compiled
        assert np.array_equal(out_warm,
                              regen.fold_symbol_pairs(pairs, 7))
        # the coefficient is an operand of the warmed program, so
        # another one runs it too, bit-exact; another shape stays cold
        out_8 = np.asarray(codec.fold_symbol(pairs, 8))
        assert compiles() == compiled
        assert np.array_equal(out_8, regen.fold_symbol_pairs(pairs, 8))
        np.asarray(codec.fold_symbol(rnd((3, 2, 72), 6), 7))
        assert compiles() > compiled

    def test_fold_and_closed_forms_bit_identical_under_pallas(self):
        # the chip's lowering (interpret mode here) against the NumPy
        # twin: the fold for a few coefficients, and the closed-form
        # repair and decode matrices through the same kernel
        codec = regen.RegenCodec(4, 8, strategy="pallas")
        ref = regen.RegenReference(4, 8)
        pairs = rnd((3, 2, 65), 12)
        for coeff in (1, 7, 213):
            assert np.array_equal(
                np.asarray(codec.fold_symbol(pairs, coeff)),
                ref.fold_symbol(pairs, coeff))
        data = rnd((2, 4, 64), 13)
        coded = np.asarray(ref.encode(data))
        present, missing = (1, 3, 5, 9), (0,)
        surv = coded[:, list(present)]
        assert np.array_equal(
            np.asarray(codec.reconstruct(surv, present, missing)),
            ref.reconstruct(surv, present, missing))
        assert np.array_equal(
            np.asarray(codec.decode_data(surv, present)), data)

    @pytest.mark.parametrize("strategy", ["gather", "pallas"])
    def test_warm_fold_hits_only_its_own_device(self, strategy, compiles):
        # mirror of the reconstruct device-key pin (test_pool): a fold
        # warmed for dev-1 must not dispatch under dev-2's placement
        devs = jax.devices()
        assert len(devs) >= 3           # conftest: 8 virtual devices
        codec = regen.RegenCodec(2, 1, strategy=strategy)
        pairs = rnd((2, 2, 88), 8)      # a width no other test compiles
        codec.warm_fold(5, pairs.shape, device=devs[1])
        compiled = compiles()
        with jax.default_device(devs[2]):
            out = np.asarray(codec.fold_symbol(pairs, 5))
        assert compiles() > compiled
        assert np.array_equal(out, regen.fold_symbol_pairs(pairs, 5))
        # on its own device the warmed program folds any coefficient
        compiled = compiles()
        with jax.default_device(devs[1]):
            out2 = np.asarray(codec.fold_symbol(pairs, 5))
            out_9 = np.asarray(codec.fold_symbol(pairs, 9))
        assert compiles() == compiled
        assert np.array_equal(out2, out)
        assert np.array_equal(out_9, regen.fold_symbol_pairs(pairs, 9))


# -- the engine surface: submit class, warm keys, per-lane programs ---------

class TestEngineSymbols:
    def test_repair_symbol_round_trip(self):
        eng = make_engine(2, 1, rs_backend="regen",
                          policy=AdmissionPolicy(max_delay=0.002))
        try:
            pairs = rnd((3, 2, 256), 13)
            out = np.asarray(eng.repair_symbol(pairs, 9, timeout=60))
            assert np.array_equal(out,
                                  regen.fold_symbol_pairs(pairs, 9))
            # single-pair convenience: [2, n] in, [1, n] out
            one = np.asarray(eng.repair_symbol(pairs[0], 9, timeout=60))
            assert np.array_equal(one, out[0])
        finally:
            eng.close()

    def test_non_regen_engine_refuses_symbols(self):
        eng = make_engine(2, 1, rs_backend="jax",
                          policy=AdmissionPolicy(max_delay=0.002))
        try:
            with pytest.raises(ValueError, match="regenerating codec"):
                eng.repair_symbol(rnd((2, 256), 1), 9, timeout=60)
        finally:
            eng.close()

    def test_warm_repair_warms_fold_programs_per_lane(self, compiles):
        eng = make_engine(2, 1, rs_backend="regen",
                          policy=AdmissionPolicy(max_delay=0.002),
                          pool=DevicePool(n=2))
        try:
            eng.warm_repair([((1, 2), (0,))], 256, buckets=(1,))
            coeffs = set(regen.repair_coeffs(2, 1, (1, 2), (0,)))
            coeffs.discard(0)
            assert coeffs
            keys = set(eng.programs._programs)
            # base + one per lane, under the exact keys _op_repair
            # looks up — same discipline as the reconstructs; the
            # coefficient is the program's argument, not in its key
            assert ("symbol", 256, 1) in keys
            assert ("symbol", 256, 1, ("device", 0)) in keys
            assert ("symbol", 256, 1, ("device", 1)) in keys
            # the fold's program — the [1, 2] matrix's over two linear
            # rows of 256 (a host hop's way up since PR 32), at RS(2,1)
            # the one-row repair's too — is compiled for every lane's
            # device
            pairs = rnd((1, 2, 256), 2)
            compiled = compiles()
            for lane in eng.pool.lanes:
                with jax.default_device(lane.device):
                    rows = rs.LinearRows(
                        tuple(jax.device_put(list(pairs[0]))), 2)
                    jax.block_until_ready(
                        eng.codec.fold_symbol(rows, sorted(coeffs)[0]))
            assert compiles() == compiled
            # and the fold through the engine runs it
            out = np.asarray(eng.repair_symbol(
                pairs, sorted(coeffs)[0], timeout=60))
            assert np.array_equal(
                out, regen.fold_symbol_pairs(pairs, sorted(coeffs)[0]))
        finally:
            eng.close()
