"""The main path's Pallas kernels, the engine's two audit programs and
its tag program, its flatten of a byte result into linear rows, the
gateway's parity-rows program, the fused ingest program over the linear
rows the stream driver puts (no regrouping of the fragments between its
two kernels since PR 44) and the pooled stream step
over four chips, compiled at protocol widths for a
DESCRIBED TPU v5e (no chip attached): the installed TPU compiler
refuses here what it would refuse on the chip — a kernel Mosaic cannot
lower, a program that does not fit 16 GiB of HBM — at no chip time.

Nothing runs, so nothing here says anything about results or speed.
Interpret-mode tests (test_rs_tpu / test_podr2) pin
the results; chip_smoke.py is the run on the chip.

The topology is described inside a module-scoped fixture (never at
import: only one xdist worker may load the TPU library, and every
worker imports every test file), and all cases live in this one file
so one worker holds the library for all of them.
"""
import functools
import re
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

from cess_tpu import constants
from cess_tpu.models.pipeline import PipelineConfig, StoragePipeline
from cess_tpu.node import offchain
from cess_tpu.ops import gf, podr2, podr2_pallas, rs, rs_pallas, target
from cess_tpu.parallel import mesh as pmesh
from cess_tpu.serve import engine

MiB = 1 << 20
HBM_BYTES = 16 * 1024 ** 3      # one v5e chip
# A kernel compiles in a second or two and the fused program in three.
# The bound is for the relayouting uint8 reshape, whose compile time
# grows with the array (minutes at these widths; models/pipeline.py
# split_rows/merge_rows are written without it).
COMPILE_SECONDS = 60
# The fused ingest program's own bound (PR 44): 6 s at RS(4,8) and 2 s at
# RS(2,1) here, alone on the machine; its tag view written as ONE reshape
# of the batch, u8[8,12,4 MiB] -> u8[96,8192,512], took 156 s.
FUSED_COMPILE_SECONDS = 10
# The engine's flatten (PR 53): 2.6 s at u8[8, 4, 4 MiB] and under 0.1 s
# at r = 1 here, alone on the machine.
FLATTEN_COMPILE_SECONDS = 20


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler here / library held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def for_tpu(monkeypatch):
    """Steer the one interpret-mode decision (ops/target.py) to 'lower
    for the TPU' — the process still sees only the CPU backend — with
    the persistent compile cache off (a described-device executable
    cannot be read back without a chip), and drop every trace
    afterwards so a TPU-lowered kernel never serves a later CPU test."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(target, "interpret", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.clear_caches()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()
    jax.clear_caches()


def _rs_constant(mat, passthrough=False):
    # the matrix a constant of the program, as the fused ingest has it
    bmat = gf.expand_bitmatrix(mat)
    return lambda d: rs_pallas.apply_operand(
        jnp.asarray(rs_pallas.operand_np(
            bmat, rs_pallas.group_for(d.shape[0]))), d,
        passthrough=passthrough)


def _rs_encode(k, m, passthrough=False):
    return _rs_constant(gf.cauchy_parity_matrix(k, m), passthrough)


def _rs_repair_one_row():
    # RS(4,8), row 0 lost, rebuilt from the 4 lowest survivors
    return _rs_constant(gf.repair_matrix(4, 8, (1, 2, 3, 4), (0,)))


def _rs_operand():
    # the repair class's program since PR 31: the pattern's bit-matrix
    # is an ARGUMENT (int8 [8r, 8q] at batch 1), one program a shape
    return rs._DENSE["pallas"]


def _podr2_tags():
    key = podr2.Podr2Key.generate(0)
    w0, w1 = podr2_pallas.weight_limbs(key.alpha)
    lanes = 2 * key.alpha.shape[0]

    def run(prf, data):
        return podr2_pallas._tags_3d(
            jnp.asarray(w0), jnp.asarray(w1), prf, data, key.limbs,
            lanes, podr2_pallas.DEFAULT_BLOCK_TILE)
    return run


def _fused_forward(k, m, segment_size=constants.SEGMENT_SIZE):
    # strategy named: default_strategy() asks the (CPU) backend
    cfg = PipelineConfig(k=k, m=m, segment_size=segment_size,
                         strategy="pallas")
    return StoragePipeline(cfg).fused_program()


# the last element: the pinned kernel names (ops/*.KERNEL_NAME) whose
# custom calls the compiled program must hold under exactly that name —
# a device trace's events are these instructions, and the benchmark's
# roofline readers match "%_apply_3d" / "%_tags_3d"
RS, TAGS = rs_pallas.KERNEL_NAME, podr2_pallas.KERNEL_NAME
CASES = [
    ("rs_pallas-rs4p8-encode", lambda: _rs_encode(4, 8),
     [((8, 4, 4 * MiB), jnp.uint8)], (RS,)),
    ("rs_pallas-rs2p1-encode", lambda: _rs_encode(2, 1),
     [((8, 2, 8 * MiB), jnp.uint8)], (RS,)),
    # the archival tier's ingest (PR 47): the kernel as the fused step
    # calls it, the ten data rows passed through: [8, 10, 8 MiB] ->
    # [8, 14, 8 MiB], a grid step's bit-planes [2, 10, 8, 32768]
    ("rs_pallas-rs10p4-encode", lambda: _rs_encode(10, 4, passthrough=True),
     [((8, 10, 8 * MiB), jnp.uint8)], (RS,)),
    ("rs_pallas-repair-one-row", _rs_repair_one_row,
     [((1, 4, 8 * MiB), jnp.uint8)], (RS,)),
    # the archival tier (RS(10,4)): ten 8 MiB helpers -> r lost rows,
    # and the protocol's one-row repair through the same program
    *[(f"rs_pallas-repair-10p4-r{r}", _rs_operand,
       [((8 * r, 80), jnp.int8), ((1, 10, 8 * MiB), jnp.uint8)], (RS,))
      for r in (1, 2, 3, 4)],
    ("rs_pallas-repair-2p1-operand", _rs_operand,
     [((8, 16), jnp.int8), ((1, 2, 8 * MiB), jnp.uint8)], (RS,)),
    ("podr2_pallas-tags", _podr2_tags,
     [((8, 2, 16384), jnp.uint32), ((8, 16384, 512), jnp.uint8)],
     (TAGS,)),
    # the fused ingest program's array form (a batch as u8[B, 16 MiB])
    ("fused-forward-rs4p8", lambda: _fused_forward(4, 8),
     [((8, 16 * MiB), jnp.uint8), ((8 * 12,), jnp.int32)], (RS, TAGS)),
    ("fused-forward-rs2p1", lambda: _fused_forward(2, 1),
     [((8, 16 * MiB), jnp.uint8), ((8 * 3,), jnp.int32)], (RS, TAGS)),
    ("fused-forward-rs10p4", lambda: _fused_forward(10, 4, 80 * MiB),
     [((8, 80 * MiB), jnp.uint8), ((8 * 14,), jnp.int32)], (RS, TAGS)),
]


def _fits_hbm(compiled):
    """The program's bytes on one chip, against its HBM."""
    mem = compiled.memory_analysis()
    total = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
             + mem.output_size_in_bytes)
    print(f"temp {mem.temp_size_in_bytes / MiB:.0f} MiB, arguments "
          f"{mem.argument_size_in_bytes / MiB:.0f} MiB, outputs "
          f"{mem.output_size_in_bytes / MiB:.0f} MiB")     # pytest -s
    assert total < HBM_BYTES, mem


@pytest.mark.parametrize("build,shapes,kernels",
                         [pytest.param(b, s, k, id=i)
                          for i, b, s, k in CASES])
def test_kernel_compiles_for_v5e(one_chip, for_tpu, build, shapes, kernels):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    t0 = time.perf_counter()
    compiled = jax.jit(build()).lower(*args).compile()
    assert time.perf_counter() - t0 < COMPILE_SECONDS
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    for name in kernels:
        assert re.search(rf"%{name}\.\d+ = [^\n]* custom-call\(", text), name
    _fits_hbm(compiled)


# the submission engine's two audit programs (serve/engine.py) at the
# shapes of a protocol round: one miner (R = 1) of F fragments, c
# challenged blocks of 256 sectors, limbs = 2
def _audit_shapes(f, c):
    u16, u32 = jnp.uint16, jnp.uint32
    prove = [((1, f, c, 256), u16), ((1, f, c, 2), u32), ((1, f), u32),
             ((c,), u32)]
    verify = [((1, f, 2), u32), ((1, f), u32), ((1, 256), u32),
              ((1, 2), u32), ((c,), jnp.int32), ((c,), u32),
              ((256, 2), u32), ((2,), u32)]
    return prove, verify


@pytest.mark.parametrize("f,c,blocks", [
    pytest.param(32, 753, 16384, id="rs2p1-32x8MiB"),
    pytest.param(16, 376, 8192, id="rs4p8-16x4MiB"),
    pytest.param(64, 753, 16384, id="rs2p1-chunk-64x8MiB")])
def test_audit_programs_compile_for_v5e(one_chip, for_tpu, f, c, blocks):
    prove, verify = _audit_shapes(f, c)
    programs = (
        (engine._prove_missions, prove),
        (functools.partial(engine._verify_missions, num_blocks=blocks,
                           prf_impl="threefry2x32"), verify))
    for fn, shapes in programs:
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        t0 = time.perf_counter()
        compiled = jax.jit(fn).lower(*args).compile()
        assert time.perf_counter() - t0 < COMPILE_SECONDS
        mem = compiled.memory_analysis()
        print(f"temp {mem.temp_size_in_bytes / MiB:.1f} MiB, arguments "
              f"{mem.argument_size_in_bytes / MiB:.1f} MiB")  # pytest -s
        assert (mem.temp_size_in_bytes + mem.argument_size_in_bytes
                + mem.output_size_in_bytes) < HBM_BYTES, mem


def test_prove_step_compiles_for_v5e(one_chip, for_tpu):
    """A chunked prove's later steps (PR 38, serve/engine.py
    _prove_missions_step): the running (mu, sigma) plus the fold of one
    more chunk of ``podr2.PROVE_CHUNK`` fragments' challenged blocks.
    The chunk is the only shape past a chunk, so this and the first
    step's program (above, f = 64) are all a miner of any custody
    runs; the products [F, c, sectors] are fused away, not held."""
    f, c = podr2.PROVE_CHUNK, 753
    assert f == 64
    prove, _ = _audit_shapes(f, c)
    shapes = [((1, 256), jnp.uint32), ((1, 2), jnp.uint32)] + prove
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    t0 = time.perf_counter()
    compiled = jax.jit(engine._prove_missions_step).lower(*args).compile()
    assert time.perf_counter() - t0 < COMPILE_SECONDS
    mem = compiled.memory_analysis()
    print(f"temp {mem.temp_size_in_bytes / MiB:.1f} MiB, arguments "
          f"{mem.argument_size_in_bytes / MiB:.1f} MiB")      # pytest -s
    # the operands are a chunk's blocks as uint16 and its tags; the
    # uint32 products of the whole chunk would be 47 MiB more
    assert mem.temp_size_in_bytes < 40 * MiB, mem
    _fits_hbm(compiled)


@pytest.mark.parametrize("bucket,n", [
    pytest.param(16, 8 * MiB, id="upload-rs2p1-16x8MiB"),
    pytest.param(16, 4 * MiB, id="upload-rs4p8-16x4MiB")])
def test_tag_program_compiles_for_v5e(one_chip, for_tpu, bucket, n):
    """The engine's tag batch since PR 34 (ops/podr2.py TAG_PROGRAM):
    one program a batch shape, the key and the kernel's weights its
    operands, at the two shapes uploads send. The fragments are an
    ARGUMENT here (an intermediate in the fused ingest step), so the
    relayouting view u8[F, n] -> [F, blocks, 512] in front of the
    kernel must still compile in seconds (PERF.md, PR 22); the kernel
    keeps its pinned name and nothing calls back to the host."""
    u32, i32 = jnp.uint32, jnp.int32
    shapes = [((bucket, 2), u32), ((bucket, n), jnp.uint8),
              ((256, 2), u32), ((2,), u32)]
    ids, frags, alpha, key_data = (
        jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes)
    weights = tuple(jax.ShapeDtypeStruct((2, 512), i32, sharding=one_chip)
                    for _ in range(2))
    t0 = time.perf_counter()
    compiled = podr2.TAG_PROGRAM.lower(
        ids, frags, alpha, key_data, weights,
        prf_impl="threefry2x32").compile()
    took = time.perf_counter() - t0
    print(f"compiled in {took:.1f} s")                     # pytest -s
    assert took < COMPILE_SECONDS
    text = compiled.as_text()
    assert re.search(rf"%{TAGS}\.\d+ = [^\n]* custom-call\(", text)
    assert not re.search(r"callback|host_compute|outfeed|infeed", text)
    assert compiled.out_info.shape == (bucket, n // 512, 2)
    _fits_hbm(compiled)


@pytest.mark.parametrize("missions", [8, 512])
def test_round_fold_compiles_for_v5e(one_chip, for_tpu, missions):
    """The TEE's round programs (ops/podr2.py ROUND_FOLD / ROUND_CLOSE)
    at the protocol's widths: 16,384 flat rows a call, 753 challenged
    blocks, the smallest and the cap's mission bucket. The PRF
    intermediate is a loop step's, whatever the rows."""
    from cess_tpu.ops import podr2

    u32, i32 = jnp.uint32, jnp.int32
    fold = [((podr2.ROUND_ROWS, 2), u32), ((podr2.ROUND_ROWS,), i32),
            ((), i32), ((missions, 2), u32), ((753,), i32), ((753,), u32),
            ((2,), u32), ((256, 2), u32), ((2,), u32)]
    close = [((256, 2), u32), ((missions, 2), u32), ((missions, 256), u32),
             ((missions, 2), u32)]
    programs = (
        (functools.partial(podr2._round_fold_program,
                           prf_impl="threefry2x32"), fold),
        (podr2.round_close, close))
    for fn, shapes in programs:
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        t0 = time.perf_counter()
        compiled = jax.jit(fn).lower(*args).compile()
        assert time.perf_counter() - t0 < COMPILE_SECONDS
        mem = compiled.memory_analysis()
        print(f"temp {mem.temp_size_in_bytes / MiB:.1f} MiB, arguments "
              f"{mem.argument_size_in_bytes / MiB:.1f} MiB")  # pytest -s
        assert mem.temp_size_in_bytes < 256 * MiB, mem


@pytest.mark.parametrize("program,static,shapes,out", [
    pytest.param("CHALLENGE_PROGRAM", dict(num_blocks=16384, count=753),
                 [((2,), jnp.uint32)], [(753,), (753,)], id="challenge"),
    pytest.param("COEFFS_PROGRAM", {},
                 [((2,), jnp.uint32), ((32, 2), jnp.uint32)], [(32,)],
                 id="coeffs-32"),
    pytest.param("COEFFS_PROGRAM", {},
                 [((2,), jnp.uint32), ((16384, 2), jnp.uint32)], [(16384,)],
                 id="coeffs-16384")])
def test_round_derivations_compile_for_v5e(one_chip, for_tpu, program,
                                           static, shapes, out):
    """A round's challenge and its aggregation coefficients (ops/podr2.py
    CHALLENGE_PROGRAM / COEFFS_PROGRAM since PR 37) at the protocol's
    geometry, the audit cell's F and a miner's 16,384: the seed's words
    are the operand, nothing of the host is called back."""
    from cess_tpu.ops import podr2

    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    t0 = time.perf_counter()
    compiled = getattr(podr2, program).lower(*args, **static).compile()
    assert time.perf_counter() - t0 < COMPILE_SECONDS
    assert not re.search(r"callback|host_compute|outfeed|infeed",
                         compiled.as_text())
    assert [o.shape for o in jax.tree.leaves(compiled.out_info)] == out


@pytest.mark.parametrize("shape,held", [
    pytest.param((1, 1, 8 * MiB), 4, id="one-claim-rs2p1"),
    pytest.param((2, 1, 8 * MiB), 4, id="two-claims-rs2p1"),
    pytest.param((1, 3, 8 * MiB), 4, id="three-rows-lost-rs10p4"),
    *(pytest.param((t, 4, 4 * MiB), 4 * t, id=f"{t}-of-a-burst-rs4p8")
      for t in range(1, 9))])
def test_linear_rows_compile_for_v5e(one_chip, for_tpu, shape, held):
    """The engine's flatten (serve/engine.py _linear_rows) at the shapes
    of a repair's result, every count of a burst of eight among them: it
    compiles fast (index forms, and for ``r > 1`` a 1-D concatenate of
    what they give: no relayouting reshape), every batch row comes out
    ONE dense 1-D piece of its ``r * n`` logical bytes, and the argument
    holds ``held`` rows of ``n`` bytes on the device (four rows to a
    32-bit word: a lone row fills a quarter of each, three rows three
    quarters, four all of it) — the layout fact the linear fetch rests
    on (PERF.md, PR 28): a compiler that stops packing rows into words
    should be noticed. At ``r == 1`` the program is PR 28's, operation
    for operation: a slice and a squeeze a row."""
    rows, r, n = shape
    arg = jax.ShapeDtypeStruct(shape, jnp.uint8, sharding=one_chip)
    traced = engine._linear_rows.trace(arg)
    ops = [e.primitive.name for e in traced.jaxpr.eqns]
    assert ops.count("slice") == ops.count("squeeze") == rows * r
    assert ops.count("concatenate") == (rows if r > 1 else 0)
    assert len(ops) == 2 * rows * r + ops.count("concatenate")
    t0 = time.perf_counter()
    compiled = traced.lower().compile()
    assert time.perf_counter() - t0 < FLATTEN_COMPILE_SECONDS
    outs = jax.tree_util.tree_leaves(compiled.out_info)
    assert [o.shape for o in outs] == [(r * n,)] * rows
    mem = compiled.memory_analysis()
    # dense pieces: their logical bytes, plus the table of a tuple result
    assert 0 <= mem.output_size_in_bytes - rows * r * n < 4096, mem
    assert mem.argument_size_in_bytes == held * n, mem
    assert mem.temp_size_in_bytes == 0, mem


@pytest.mark.parametrize("k,m,r,bucket,n", [
    pytest.param(2, 1, 1, 1, 8 * MiB, id="one-claim-rs2p1"),
    pytest.param(2, 1, 1, 2, 8 * MiB, id="two-claims-rs2p1"),
    pytest.param(2, 1, 1, 8, 8 * MiB, id="a-storm-of-eight-rs2p1"),
    pytest.param(2, 1, 1, 16, 8 * MiB, id="a-storm-of-sixteen-rs2p1"),
    pytest.param(10, 4, 1, 1, 8 * MiB, id="one-claim-rs10p4"),
    pytest.param(10, 4, 3, 1, 8 * MiB, id="three-rows-lost-rs10p4"),
    pytest.param(4, 8, 4, 1, 4 * MiB, id="four-rows-lost-rs4p8"),
    pytest.param(4, 8, 4, 8, 4 * MiB, id="a-burst-of-eight-rs4p8")])
def test_rows_program_compiles_for_v5e(one_chip, for_tpu, k, m, r, bucket,
                                       n):
    """A host claim's way up since PR 32 (ops/rs.py _apply_rows): the
    survivors as ``bucket * k`` linear ``u8[n]`` rows, stacked and
    repaired by ONE program, the pattern's matrix its operand. It
    compiles in seconds (``stack`` forms only, no relayouting reshape
    of the whole), holds the Pallas kernel under its pinned name, and
    its arguments are the rows' logical bytes: dense 1-D rows, where
    the stacked ``u8[1, 2, n]`` operand was twice its bytes. The RS(4,8)
    cases (PR 52) are the decode shape of BASELINE's 4-erasure repair,
    ``[bucket, 4, 4 MiB] -> [bucket, 4, 4 MiB]`` with every data row
    lost (a true inverse), alone and as a deal's burst of eight."""
    bmat = rs_pallas.operand_np(
        gf.expand_bitmatrix(gf.repair_matrix(
            k, m, tuple(range(r, r + k)), tuple(range(r)))),
        rs_pallas.group_for(bucket))
    rows = tuple(jax.ShapeDtypeStruct((n,), jnp.uint8, sharding=one_chip)
                 for _ in range(bucket * k))
    t0 = time.perf_counter()
    compiled = rs._apply_rows.lower(
        (jax.ShapeDtypeStruct(bmat.shape, bmat.dtype, sharding=one_chip),),
        rows, strategy="pallas", q=k).compile()
    assert time.perf_counter() - t0 < COMPILE_SECONDS
    assert re.search(rf"%{RS}\.\d+ = [^\n]* custom-call\(",
                     compiled.as_text())
    assert compiled.out_info.shape == (bucket, r, n)
    mem = compiled.memory_analysis()
    assert 0 <= mem.argument_size_in_bytes - bucket * k * n < 65536, mem
    _fits_hbm(compiled)


@pytest.mark.parametrize("k,m", [
    pytest.param(10, 4, id="a-hop-on-the-archival-tier"),
    pytest.param(2, 1, id="a-hop-at-the-protocols-geometry")])
def test_symbol_fold_compiles_for_v5e(one_chip, for_tpu, k, m):
    """A helper's hop of a chained repair (PR 40): the regenerating
    codec's fold ``[1, 2, 8 MiB] -> [1, 1, 8 MiB]`` as the engine calls
    it, (accumulator, fragment) as two linear rows and the matrix
    ``[1, coeff]`` the program's operand. The codec builds the operand
    it would put; the program is the rows program at q = 2, r = 1
    whatever the geometry, holds the Pallas kernel under its pinned
    name (what ``rs_kernel_roofline.restore`` matches), and takes its
    rows dense; the only ``reshape`` in it is each row's own into
    ``u8[1, 1, n]`` (its relayout, a loop a row): none of the pair as a
    whole, whose compile time would grow with the array."""
    from cess_tpu.ops import regen

    n = 8 * MiB
    codec = regen.RegenCodec(k, m, strategy="pallas")
    coeff = regen.repair_coeffs(k, m, tuple(range(1, k + 1)), (0,))[0]
    apply_ = codec._matrix_for("symbol", (coeff,), ())
    assert apply_.mat.tolist() == [[1, coeff]]
    bmat = rs_pallas.operand_np(apply_._host[0], rs_pallas.group_for(1))
    rows = tuple(jax.ShapeDtypeStruct((n,), jnp.uint8, sharding=one_chip)
                 for _ in range(2))
    t0 = time.perf_counter()
    compiled = rs._apply_rows.lower(
        (jax.ShapeDtypeStruct(bmat.shape, bmat.dtype, sharding=one_chip),),
        rows, strategy=codec.strategy, q=2).compile()
    assert time.perf_counter() - t0 < COMPILE_SECONDS
    text = compiled.as_text()
    assert re.search(rf"%{RS}\.\d+ = [^\n]* custom-call\(", text)
    reshaped = re.findall(r"= (\S+?)\{\S* reshape\(", text)
    assert reshaped and set(reshaped) == {f"u8[1,1,{n}]"}
    assert compiled.out_info.shape == (1, 1, n)
    mem = compiled.memory_analysis()
    assert 0 <= mem.argument_size_in_bytes - 2 * n < 65536, mem
    _fits_hbm(compiled)


@pytest.mark.parametrize("shape,k", [
    pytest.param((4, 3, 8 * MiB), 2, id="upload-rs2p1-4-segments"),
    pytest.param((1, 12, 4 * MiB), 4, id="upload-rs4p8-1-segment")])
def test_parity_rows_compile_for_v5e(one_chip, for_tpu, shape, k):
    """The gateway's parity fetch (node/offchain.py _parity_rows) at an
    upload's encode results: only the ``m`` parity rows of each segment
    come out, each 1-D and dense, and the program compiles in seconds
    (index forms only, no relayouting reshape). The encode result stays
    whole on the device: the argument is not donated."""
    segs, rows, n = shape
    t0 = time.perf_counter()
    compiled = offchain._parity_rows.lower(
        jax.ShapeDtypeStruct(shape, jnp.uint8, sharding=one_chip),
        k=k).compile()
    assert time.perf_counter() - t0 < COMPILE_SECONDS
    outs = jax.tree_util.tree_leaves(compiled.out_info)
    assert [o.shape for o in outs] == [(n,)] * (segs * (rows - k))
    mem = compiled.memory_analysis()
    assert 0 <= mem.output_size_in_bytes - segs * (rows - k) * n < 4096, mem
    assert mem.alias_size_in_bytes == 0, mem
    assert "reshape" not in compiled.as_text()


def _u8_ops(text, at_least):
    """Opcode and shape of every instruction of a compiled program
    whose ``u8`` result holds ``at_least`` bytes or more (a ``while``
    or a tuple by the arrays it carries)."""
    found = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%[\w.\-]+ = (\(.*?\)|\S+) ([\w\-]+)\(",
                     line)
        if not m:
            continue
        for dims in re.findall(r"u8\[([\d,]+)\]", m.group(1)):
            if np.prod([int(d) for d in dims.split(",")]) >= at_least:
                found.append((m.group(2), f"u8[{dims}]"))
    return found


def _rows_reach_the_kernel_as_they_lie(text, b, k, rows, n):
    """The witness of PR 51 over PR 44's (models/pipeline.py fused_step
    over linear rows): the RS kernel takes the batch's rows as they were
    put, each a ``bitcast`` to ``u8[n / 128, 128]``, and writes the
    codeword fragment-major, ``u8[rows, B, n]``, which is the layout of
    the ``"fragments"`` result (a ``bitcast`` of the kernel's output).
    So: no stacked ``u8[B, k, n]`` array anywhere; in front of the kernel
    no ``concatenate``, ``pad``, ``fusion``, ``reshape`` or loop over a
    row or more (what XLA adds itself, its asynchronous prefetch of a few
    whole rows into its other memory space — ``slice-start`` /
    ``copy-start`` and a ``ConcatBitcast`` custom call — moves tiles,
    never bytes within a word); behind it AT MOST ONE copy-like
    operation of codeword size, the tag kernel's view (the one
    minor-dimension split), where PR 44's program had two. One call of
    each kernel under its pinned name. No ``merge_rows`` flat form, as
    since PR 44."""
    for name in (RS, TAGS):
        assert len(re.findall(rf"%{name}\.\d+ = [^\n]* custom-call\(",
                              text)) == 1, name
    assert f"u8[{b},{k},{n}]" not in text          # the stack's result
    assert f"u8[{b * rows},{n}]" not in text       # merge_rows' flat form
    row_or_more = _u8_ops(text, n)
    assert {op for op, _ in row_or_more} <= {
        "parameter", "bitcast", "tuple", "custom-call", "copy",
        "copy-start", "copy-done", "slice-start", "slice-done"}, \
        row_or_more
    # the custom calls that touch a row or more: the RS kernel, and
    # XLA's own joins of its prefetched slices
    for line in text.splitlines():
        if " custom-call(" in line and any(
                shape in line.split(" custom-call(")[0]
                for _, shape in row_or_more):
            assert line.lstrip().startswith(("%" + RS, "ROOT %" + RS)) \
                or 'custom_call_target="ConcatBitcast"' in line, line
    whole = _u8_ops(text, b * rows * n)
    assert f"u8[{rows},{b},{n}]" in {shape for _, shape in whole}
    assert sum(op == "custom-call" for op, _ in whole) == 1, whole
    assert sum(op == "copy" for op, _ in whole) <= 1, whole
    # a synchronous copy of a row or more is that one or none
    assert sum(op == "copy" for op, _ in row_or_more) <= 1, row_or_more


@pytest.mark.parametrize("k,m,segment_size", [
    pytest.param(4, 8, constants.SEGMENT_SIZE, id="rs4p8"),
    pytest.param(2, 1, constants.SEGMENT_SIZE, id="rs2p1"),
    pytest.param(10, 4, 80 * MiB, id="rs10p4")])
def test_linear_fused_program_compiles_for_v5e(one_chip, for_tpu, k, m,
                                               segment_size):
    """The one-chip stream cells' program as the driver calls it since
    PR 43 (models/pipeline.py fused_program over ``linear_rows``): a
    batch of 8 segments as its 8k linear ``u8[segment_size / k]`` rows.
    1-D dense arguments (their logical 128 MiB, where a
    ``u8[8, 2, 8 MiB]`` operand is twice that), both kernels under their
    pinned names, compiled in seconds; since PR 51 the rows go to the RS
    kernel unstacked and the codeword comes back fragment-major, so the
    program's only temporary is the tag kernel's view. ``rs10p4`` is the
    archival tier's cell (stream-10p4.corpus, PR 47): 80 rows of 8 MiB,
    640 MiB of arguments."""
    n = segment_size // k
    cfg = PipelineConfig(k=k, m=m, segment_size=segment_size,
                         strategy="pallas")
    rows = tuple(jax.ShapeDtypeStruct((n,), jnp.uint8, sharding=one_chip)
                 for _ in range(8 * k))
    ids = jax.ShapeDtypeStruct((8 * (k + m),), jnp.int32,
                               sharding=one_chip)
    t0 = time.perf_counter()
    compiled = StoragePipeline(cfg).fused_program().lower(
        rows, ids).compile()
    took = time.perf_counter() - t0
    print(f"compiled in {took:.1f} s")                     # pytest -s
    assert took < FUSED_COMPILE_SECONDS
    text = compiled.as_text()
    _rows_reach_the_kernel_as_they_lie(text, 8, k, k + m, n)
    out = compiled.out_info
    assert out["fragments"].shape == (8, k + m, n)
    assert out["tags"].shape == (8, k + m, n // 512, 2)
    mem = compiled.memory_analysis()
    assert 0 <= mem.argument_size_in_bytes - 8 * k * n < 65536, mem
    # the tag kernel's view of the codeword and nothing else of its
    # size: 385.5 MiB at RS(4,8), 192.3 at RS(2,1), 897.8 at RS(10,4),
    # where the stacked data and the codeword as the array entry writes
    # it (rows padded to the 8-row tile) made it 520, 773 and 2,809
    assert mem.temp_size_in_bytes < 1.2 * 8 * (k + m) * n, mem
    _fits_hbm(compiled)


def test_pooled_stream_step_compiles_for_v5e_2x2(topo, for_tpu):
    """The four-lane host's program (benchmark cell stream-4p8.pool4):
    sharded_stream_step on a (4, 1) mesh at RS(4,8), 32 segments a batch,
    handed over as stream_entry's ``put`` stages them since PR 43: 32
    row slots ``u8[4 x 4 MiB]``, a lane's linear row in each. Each chip
    must hold the one-chip step's two kernels under their pinned names,
    once each, hand its rows to the RS kernel unstacked and take the
    codeword fragment-major as the one-chip program does (one body,
    PR 51), and the step needs no collective."""
    cfg = PipelineConfig(k=4, m=8, segment_size=constants.SEGMENT_SIZE,
                         strategy="pallas")
    mesh = Mesh(np.array(topo.devices).reshape(4, 1), ("seg", "byte"))
    step = pmesh.sharded_stream_step(StoragePipeline(cfg), mesh)
    rows_sh = NamedSharding(mesh, P(("seg", "byte")))
    args = [
        tuple(jax.ShapeDtypeStruct((4 * 4 * MiB,), jnp.uint8,
                                   sharding=rows_sh) for _ in range(32)),
        jax.ShapeDtypeStruct((32, 12), jnp.int32,
                             sharding=NamedSharding(mesh, P("seg", None)))]
    t0 = time.perf_counter()
    compiled = jax.jit(step).lower(*args).compile()
    assert time.perf_counter() - t0 < FUSED_COMPILE_SECONDS
    text = compiled.as_text()
    assert not re.search(r"all-reduce|all-gather|all-to-all|"
                         r"collective-permute|reduce-scatter", text)
    _rows_reach_the_kernel_as_they_lie(text, 8, 4, 12, 4 * MiB)
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 1.2 * 8 * 12 * 4 * MiB
    _fits_hbm(compiled)
