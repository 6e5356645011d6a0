"""Golden tests: JAX codec strategies vs the NumPy oracle, byte-exact.

Byte-exact determinism between the CPU default path and the device path
is a protocol invariant — fragment hashes go on chain (SURVEY.md §7
hard part 4). Runs on the virtual CPU mesh; on the chip the same code
is held to the references by chip_smoke.py and by every cell of the
benchmark (benchmark/run.py decides ``correct`` against
benchmark/reference/).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cess_tpu.ops import gf, rs
from cess_tpu.ops.rs import LinearRows, TPUCodec, make_codec
from cess_tpu.ops.rs_ref import ReferenceCodec

GEOMETRIES = [(2, 1), (2, 2), (3, 3), (4, 8), (4, 2), (10, 4)]
# the two lowerings: the CPU's, and the chip's in interpret mode
STRATEGIES = ["gather", "pallas"]


def rand(shape, seed=0):
    return np.random.default_rng(seed).integers(0, 256, shape, dtype=np.uint8)


@pytest.mark.parametrize("k,m", GEOMETRIES)
@pytest.mark.parametrize("strategy", STRATEGIES)
def test_encode_matches_oracle(k, m, strategy):
    ref = ReferenceCodec(k, m)
    tpu = TPUCodec(k, m, strategy=strategy)
    data = rand((k, 512), seed=k * 31 + m)
    want = ref.encode(data)
    got = np.asarray(tpu.encode(data))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_encode_batched(strategy):
    k, m = 4, 8
    ref = ReferenceCodec(k, m)
    tpu = TPUCodec(k, m, strategy=strategy)
    data = rand((3, 5, k, 256), seed=7)
    np.testing.assert_array_equal(np.asarray(tpu.encode(data)), ref.encode(data))


@pytest.mark.parametrize("k,m", [(2, 1), (2, 2), (3, 3), (4, 8)])
@pytest.mark.parametrize("strategy", STRATEGIES)
def test_reconstruct_all_erasure_patterns(k, m, strategy):
    """Any k survivors recover every missing shard exactly."""
    import itertools

    ref = ReferenceCodec(k, m)
    tpu = TPUCodec(k, m, strategy=strategy)
    data = rand((2, k, 128), seed=99)
    shards = ref.encode(data)
    patterns = list(itertools.combinations(range(k + m), k))
    if len(patterns) > 12:  # keep runtime sane for (4,8): sample across the space
        rng = np.random.default_rng(k * 100 + m)
        patterns = [patterns[i] for i in rng.choice(len(patterns), 12, replace=False)]
    for present in patterns:
        missing = tuple(i for i in range(k + m) if i not in present)
        survivors = shards[:, list(present), :]
        got = np.asarray(tpu.reconstruct(survivors, present))
        np.testing.assert_array_equal(got, shards[:, list(missing), :])
        got_data = np.asarray(tpu.decode_data(survivors, present))
        np.testing.assert_array_equal(got_data, data)


HELPERS_10P4 = [
    ((0, 1, 2, 4, 5, 7, 8, 10, 12, 13), (3,)),
    ((1, 2, 3, 4, 5, 6, 8, 9, 11, 13), (0,)),
    ((0, 2, 3, 5, 6, 7, 9, 10, 11, 13), (1, 12)),
    ((0, 1, 3, 4, 6, 7, 9, 10, 11, 12), (2, 5, 13)),
    ((1, 2, 3, 5, 6, 8, 10, 11, 12, 13), (0, 4, 7, 9))]


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_reconstruct_10p4_from_helpers_that_are_not_the_lowest(strategy):
    """The archival tier's repair: ten helpers that are NOT the k lowest
    survivors (parity rows among them, data rows among the lost), one to
    four rows lost; every pattern after the first of a shape reuses its
    jitted program with another matrix."""
    k, m = 10, 4
    ref = ReferenceCodec(k, m)
    tpu = TPUCodec(k, m, strategy=strategy)
    shards = ref.encode(rand((2, k, 128), seed=104))
    for present, missing in HELPERS_10P4:
        survivors = shards[:, list(present), :]
        got = np.asarray(tpu.reconstruct(survivors, present, missing))
        np.testing.assert_array_equal(got, shards[:, list(missing), :])
        np.testing.assert_array_equal(
            got, ref.reconstruct(survivors, present, missing))
    assert len(tpu._cache) == 5


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_decode_data_10p4_from_helpers_that_are_not_the_lowest(
        strategy, compiles):
    """The same helpers asked for the DATA rows (a download that has to
    decode): every set of ten gives back the user's bytes, and since
    every decode matrix is 10 x 10 the first pattern's program serves
    the other four, its matrix an argument."""
    k, m = 10, 4
    ref = ReferenceCodec(k, m)
    tpu = TPUCodec(k, m, strategy=strategy)
    data = rand((2, k, 136), seed=105)    # a width no other test compiles
    shards = ref.encode(data)
    compiled = []
    for present, _ in HELPERS_10P4:
        survivors = shards[:, list(present), :]
        got = np.asarray(tpu.decode_data(survivors, present))
        np.testing.assert_array_equal(got, data)
        np.testing.assert_array_equal(
            got, ref.decode_data(survivors, present))
        compiled.append(compiles())
    assert len(set(compiled)) == 1 and len(tpu._cache) == 5


@pytest.mark.parametrize("kind", ["reconstruct", "decode_data"])
@pytest.mark.parametrize("k,m", GEOMETRIES)
@pytest.mark.parametrize("strategy", STRATEGIES)
def test_linear_rows_equal_the_stacked_array(k, m, strategy, kind):
    """The chip's repair input since PR 32: the survivors as ``B * k``
    linear ``u8[n]`` rows, stacked inside the program that applies the
    matrix (``_apply_rows``). Byte for byte the same call on the stacked
    array, and the oracle's answer."""
    ref = ReferenceCodec(k, m)
    tpu = TPUCodec(k, m, strategy=strategy)
    data = rand((2, k, 128), seed=k * 13 + m)
    shards = ref.encode(data)
    present = tuple(range(m, m + k)) if m < k else tuple(range(k, 2 * k))
    survivors = shards[:, list(present), :]
    rows = LinearRows(tuple(jnp.asarray(r) for seg in survivors
                            for r in seg), k)
    assert rows.shape == survivors.shape
    call = getattr(tpu, kind)
    got = np.asarray(call(rows, present))
    np.testing.assert_array_equal(got, np.asarray(call(survivors, present)))
    np.testing.assert_array_equal(
        got, getattr(ref, kind)(survivors, present))


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_segment_sized_shards(strategy):
    """One real-geometry shard column count (scaled-down fragment)."""
    k, m = 4, 8
    tpu = TPUCodec(k, m, strategy=strategy)
    ref = ReferenceCodec(k, m)
    data = rand((k, 64 * 1024), seed=3)
    np.testing.assert_array_equal(np.asarray(tpu.encode_parity(data)),
                                  ref.encode_parity(data))


def test_make_codec_backends():
    cpu = make_codec(2, 1, backend="cpu")
    dev = make_codec(2, 1, backend="jax")
    assert isinstance(cpu, ReferenceCodec) and isinstance(dev, TPUCodec)
    data = rand((2, 64), seed=1)
    np.testing.assert_array_equal(np.asarray(dev.encode(data)), cpu.encode(data))


@pytest.mark.parametrize("use_int8", [True, False])
def test_pallas_kernel_matches_oracle(use_int8):
    """Fused Pallas kernel (interpret mode on CPU) vs oracle, incl. padding."""
    from cess_tpu.ops.rs_pallas import apply_operand, group_for, operand_np

    k, m = 4, 8
    ref = ReferenceCodec(k, m)
    bmat = jnp.asarray(
        operand_np(gf.expand_bitmatrix(ref.parity), group_for(2), use_int8),
        dtype=jnp.int8 if use_int8 else jnp.bfloat16)
    for n in (512, 700):  # 700 exercises the pad-to-tile path
        data = rand((2, k, n), seed=n)
        got = np.asarray(apply_operand(bmat, data, tile_n=512, use_int8=use_int8))
        np.testing.assert_array_equal(got, ref.encode_parity(data))


@pytest.mark.parametrize("mxu_pack", [True, False], ids=["mxupack", "vpupack"])
@pytest.mark.parametrize("use_int8", [True, False], ids=["int8", "bf16"])
@pytest.mark.parametrize("batch", [1, 3, 4], ids=["b1", "b3-odd", "b4"])
@pytest.mark.parametrize("k,m", [(2, 1), (4, 8)], ids=["rs2p1", "rs4p8"])
def test_pallas_passthrough_writes_the_codeword(k, m, batch, use_int8,
                                                mxu_pack):
    """The kernel with its static pass-through (PR 44, interpret mode):
    the input rows come out first, as read, and the product's rows
    after them, in one array — the same bytes as parity + concatenate,
    whatever the group (an odd batch degrades it to 1), the pad to the
    tile and the lowering. Without it the kernel's output is what it
    was: the oracle's parity."""
    from cess_tpu.ops.rs_pallas import apply_operand, group_for, operand_np

    ref = ReferenceCodec(k, m)
    bmat = jnp.asarray(
        operand_np(gf.expand_bitmatrix(ref.parity), group_for(batch),
                   use_int8),
        dtype=jnp.int8 if use_int8 else jnp.bfloat16)
    for n in (512, 700):
        data = rand((batch, k, n), seed=n + batch)
        kw = dict(tile_n=512, use_int8=use_int8, mxu_pack=mxu_pack)
        parity = np.asarray(apply_operand(bmat, data, **kw))
        np.testing.assert_array_equal(parity, ref.encode_parity(data))
        got = np.asarray(apply_operand(bmat, data, passthrough=True, **kw))
        assert got.shape == (batch, k + m, n)
        np.testing.assert_array_equal(got[:, :k], data)    # the user's bytes
        np.testing.assert_array_equal(
            got, np.concatenate([data, parity], axis=-2))


@pytest.mark.parametrize("k,m", GEOMETRIES)
@pytest.mark.parametrize("strategy", STRATEGIES)
def test_matrix_apply_codeword_is_the_systematic_encode(k, m, strategy):
    """``_MatrixApply.codeword`` (what the fused ingest step calls): the
    data rows followed by the parity rows under both strategies, one
    kernel call under ``pallas``, bit-identical to ``TPUCodec.encode``
    and the oracle."""
    from cess_tpu.ops.rs import _MatrixApply

    data = rand((3, k, 640), seed=k * 7 + m)
    apply_ = _MatrixApply(gf.cauchy_parity_matrix(k, m), strategy)
    got = np.asarray(apply_.codeword(jnp.asarray(data)))
    np.testing.assert_array_equal(got, ReferenceCodec(k, m).encode(data))
    np.testing.assert_array_equal(
        got, np.asarray(TPUCodec(k, m, strategy=strategy).encode(data)))
    with pytest.raises(ValueError, match="shard rows"):
        apply_.codeword(jnp.asarray(data[:, :-1]))


@pytest.mark.parametrize("batch,n", [(8, 1024), (16, 512), (8, 16384)],
                         ids=["b8", "b16", "b8-two-tiles"])
@pytest.mark.parametrize("k,m", GEOMETRIES)
def test_pallas_rows_entry_writes_the_codeword_fragment_major(k, m, batch,
                                                              n):
    """The kernel's rows entry (PR 51, rs_pallas.apply_rows_operand): the
    batch's ``B * k`` linear rows in, each read as ``[n / 128, 128]``,
    the codeword ``u8[k + m, B, n]`` out, equal to the oracle's encode
    with the two leading dimensions swapped; the same matrix operand as
    the array entry (``operand_np``, ``group_for``)."""
    from cess_tpu.ops import rs_pallas

    data = rand((batch, k, n), seed=batch + k)
    operand = rs_pallas.operand_np(
        gf.expand_bitmatrix(gf.cauchy_parity_matrix(k, m)),
        rs_pallas.group_for(batch))
    rows = tuple(jnp.asarray(r) for r in data.reshape(batch * k, n))
    got = np.asarray(rs_pallas.apply_rows_operand(jnp.asarray(operand),
                                                  rows, k))
    assert got.shape == (k + m, batch, n)
    np.testing.assert_array_equal(got[:k].swapaxes(0, 1), data)
    np.testing.assert_array_equal(got.swapaxes(0, 1),
                                  ReferenceCodec(k, m).encode(data))


@pytest.mark.parametrize("batch,n,tile", [
    (8, 4 << 20, 8192), (8, 8 << 20, 8192), (16, 4 << 20, 4096),
    (8, 1024, 1024), (16, 512, 512),
    # what the entry leaves to the stack, by shape: a batch of other
    # than 8 or 16, rows of no whole column tile or no whole lane row
    (1, 4 << 20, 0), (4, 4 << 20, 0), (12, 1024, 0), (24, 1024, 0),
    (8, 8192 + 512, 0), (8, 576, 0)])
def test_rows_tile_decides_by_shape(batch, n, tile):
    from cess_tpu.ops import rs_pallas

    assert rs_pallas.rows_tile(batch, n) == tile


@pytest.mark.parametrize("batch,n", [(8, 1024), (4, 1024), (8, 576)],
                         ids=["direct", "odd-batch-stacks", "odd-row-stacks"])
@pytest.mark.parametrize("k,m", [(2, 1), (4, 8), (10, 4)])
@pytest.mark.parametrize("strategy", STRATEGIES)
def test_codeword_rows_is_the_systematic_encode(k, m, strategy, batch, n):
    """``rs.codeword_rows`` (what the fused ingest step calls on linear
    rows): ``u8[B, k + m, n]`` equal to ``codeword`` of the stacked array
    and to the oracle, whichever way the shape and the lowering send
    it; ``rows_direct`` says which."""
    from cess_tpu.ops.rs import _MatrixApply

    data = rand((batch, k, n), seed=3 * k + batch)
    apply_ = _MatrixApply(gf.cauchy_parity_matrix(k, m), strategy)
    assert rs.rows_direct(apply_, batch, n) == (
        strategy == "pallas" and (batch, n) == (8, 1024))
    rows = [jnp.asarray(r) for r in data.reshape(batch * k, n)]
    got = np.asarray(rs.codeword_rows(apply_, rows, k))
    np.testing.assert_array_equal(got, ReferenceCodec(k, m).encode(data))
    np.testing.assert_array_equal(
        got, np.asarray(apply_.codeword(jnp.asarray(data))))


@pytest.mark.parametrize("name", "xor auto bitmatrix".split())
def test_a_strategy_that_is_not_a_lowering_is_refused(name):
    """The codec has two lowerings; any other name is a caller's
    mistake, refused where the codec is built and with the two named."""
    with pytest.raises(ValueError, match="'gather' or 'pallas'"):
        TPUCodec(2, 1, strategy=name)


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_leading_dims_and_row_mismatch(strategy):
    """Any leading batch dimensions ride through an apply; a wrong row
    count is refused before anything is dispatched, for an array and
    for linear rows."""
    ref = ReferenceCodec(2, 1)
    tpu = TPUCodec(2, 1, strategy=strategy)
    data = rand((2, 3, 2, 36), seed=10)
    parity = np.asarray(tpu.encode_parity(data))
    assert parity.shape == (2, 3, 1, 36)
    np.testing.assert_array_equal(parity, ref.encode_parity(data))
    shards = ref.encode(data)
    got = np.asarray(tpu.reconstruct(shards[..., [1, 2], :], (1, 2)))
    np.testing.assert_array_equal(got, shards[..., [0], :])
    with pytest.raises(ValueError, match="shard rows"):
        tpu.encode_parity(rand((3, 36), seed=1))
    with pytest.raises(ValueError, match="shard rows"):
        tpu.reconstruct(LinearRows(
            tuple(jnp.asarray(r) for r in rand((3, 36), seed=2)), 3), (1, 2))


def test_default_strategy_follows_the_platform(monkeypatch):
    """Platform -> lowering, the one selector: ``gather`` on the CPU,
    the Pallas kernel on anything else; a codec built without a
    strategy takes it."""
    assert rs.default_strategy() == "gather"      # conftest: the CPU
    assert TPUCodec(2, 1).strategy == "gather"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert rs.default_strategy() == "pallas"
    assert TPUCodec(2, 1).strategy == "pallas"
    assert set(rs._DENSE) == {"gather", "pallas"}


def test_bitmatrix_expansion_roundtrip():
    """expand_bitmatrix really is the GF multiply, for all 256 constants."""
    xs = np.arange(256, dtype=np.uint8).reshape(1, 256)
    for c in [0, 1, 2, 3, 0x1D, 0x80, 0xFF]:
        bm = gf.expand_bitmatrix(np.array([[c]], dtype=np.uint8))
        bits = ((xs[:, None, :] >> np.arange(8)[None, :, None]) & 1).reshape(8, 256)
        obits = (bm.astype(np.int64) @ bits) & 1
        got = np.zeros(256, dtype=np.uint8)
        for a in range(8):
            got |= (obits[a] << a).astype(np.uint8)
        want = np.array([gf.gf_mul(c, int(x)) for x in range(256)], dtype=np.uint8)
        np.testing.assert_array_equal(got, want)


def test_property_encode_corrupt_repair_random_patterns():
    """SURVEY §4 implication: property tests for encode->corrupt->
    repair. Random geometries and random erasure sets across all
    three backends, byte-exact against the oracle."""
    import numpy as np

    from cess_tpu.ops import rs, rs_ref

    rng = np.random.default_rng(1234)
    for trial in range(12):
        k = int(rng.integers(1, 6))
        m = int(rng.integers(1, 6))
        n = int(rng.integers(1, 5)) * 64
        data = rng.integers(0, 256, (2, k, n), dtype=np.uint8)
        ref = rs_ref.ReferenceCodec(k, m)
        coded = ref.encode(data)
        # lose a random subset of up to m shards
        n_lose = int(rng.integers(1, m + 1))
        missing = tuple(sorted(rng.choice(k + m, size=n_lose,
                                          replace=False).tolist()))
        present = tuple(i for i in range(k + m) if i not in missing)[:k]
        surv = coded[:, list(present)]
        expect = coded[:, list(missing)]
        for backend in ("cpu", "native", "jax"):
            codec = rs.make_codec(k, m, backend=backend)
            got = np.asarray(codec.reconstruct(surv, present, missing))
            assert np.array_equal(got, expect), \
                (trial, backend, k, m, missing)
            got_data = np.asarray(codec.decode_data(surv, present))
            assert np.array_equal(got_data, data), (trial, backend)


def test_explicit_tpu_backend_refuses_without_an_accelerator():
    # "tpu" names the accelerator and must never become a TPUCodec on
    # the CPU backend in silence; "jax" is "wherever JAX runs"
    with pytest.raises(RuntimeError, match="no accelerator"):
        make_codec(2, 1, backend="tpu")
    assert make_codec(2, 1, backend="jax").k == 2
