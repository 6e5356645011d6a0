"""The device names of the two Pallas kernels are pinned (ISSUE 25).

A profiler trace shows each kernel as a custom call named after the
``pallas_call``'s ``name=``: ``%_apply_3d.<n> = ...`` and
``%_tags_3d.<n> = ...``. The benchmark's roofline readers
(benchmark/layer_metrics/*_kernel_roofline.*.py) match exactly those
prefixes, so renaming the jitted wrapper — which used to be where the
name came from — would silently empty three metrics. Here: the constants
are what the readers quote, every ``pallas_call`` the main path traces
carries them, and (tests/test_tpu_compile.py) the compiled TPU program's
custom calls are named so.
"""
import glob
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from cess_tpu.models import pipeline
from cess_tpu.ops import gf, podr2, podr2_pallas, rs_pallas

READERS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "layer_metrics")

PINNED = [(rs_pallas, "_apply_3d", "rs_kernel_roofline.ingest.py"),
          (rs_pallas, "_apply_3d", "rs_kernel_roofline.repair.py"),
          (podr2_pallas, "_tags_3d", "tag_kernel_roofline.ingest.py")]


@pytest.mark.parametrize("module,name,reader", PINNED,
                         ids=[r for _, _, r in PINNED])
def test_reader_matches_the_pinned_name(module, name, reader):
    assert module.KERNEL_NAME == name
    with open(os.path.join(READERS, reader)) as f:
        src = f.read()
    # the prefix the reader hands kernel_work.roofline_share, and the
    # one its docstring quotes
    assert f'"%{name}"' in src
    every = "".join(open(p).read() for p in
                    glob.glob(os.path.join(READERS, "*roofline*.py")))
    assert f"``%{name}``" in every


def _pallas_names(fn, *args) -> list[str]:
    """The ``name`` of every pallas_call in fn's jaxpr, nested ones too."""
    names = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                names.append(eqn.params["name"])
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        walk(inner)
    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return names


def test_rs_kernel_call_carries_the_name():
    bmat = gf.expand_bitmatrix(gf.cauchy_parity_matrix(2, 1))
    data = jnp.zeros((2, 2, rs_pallas.DEFAULT_TILE_N), jnp.uint8)
    operand = jnp.asarray(
        rs_pallas.operand_np(bmat, rs_pallas.group_for(data.shape[0])))
    assert _pallas_names(lambda d: rs_pallas.apply_operand(operand, d),
                         data) == [rs_pallas.KERNEL_NAME]


def test_fused_step_carries_both_names_and_its_scope():
    cfg = pipeline.PipelineConfig(k=2, m=1, segment_size=2 * 32768,
                                  strategy="pallas")
    pipe = pipeline.StoragePipeline(cfg,
                                    podr2_key=podr2.Podr2Key.generate(1))
    segs = jnp.zeros((2, cfg.segment_size), jnp.uint8)
    ids = jnp.arange(6, dtype=jnp.int32)
    names = _pallas_names(pipe.fused_program(), segs, ids)
    assert sorted(names) == sorted([rs_pallas.KERNEL_NAME,
                                    podr2_pallas.KERNEL_NAME])
    # the step's operations carry its named scope in their op_name
    text = pipe.fused_program().lower(segs, ids).as_text(debug_info=True)
    assert pipeline.FUSED_SCOPE == "cess_fused_step"
    assert pipeline.FUSED_SCOPE in text


@pytest.mark.parametrize("batch,direct", [(8, True), (16, True), (4, False)],
                         ids=["b8", "b16", "b4-stacks"])
def test_rows_form_program_carries_both_names_once_each(batch, direct):
    """The fused program over the driver's linear rows (PR 51): the RS
    kernel's rows entry is a second ``pallas_call`` under the SAME pinned
    name, exactly one a batch beside the tag kernel's one, under the
    step's scope — whether the batch goes to the kernel unstacked or,
    no multiple of 8, stacks by its shape."""
    cfg = pipeline.PipelineConfig(k=2, m=1, segment_size=2 * 8192,
                                  strategy="pallas")
    pipe = pipeline.StoragePipeline(cfg,
                                    podr2_key=podr2.Podr2Key.generate(1))
    assert pipe.rows_direct(batch, cfg.fragment_size) == direct
    rows = tuple(jnp.zeros((cfg.fragment_size,), jnp.uint8)
                 for _ in range(batch * cfg.k))
    ids = jnp.arange(batch * 3, dtype=jnp.int32)
    names = _pallas_names(pipe.fused_program(), rows, ids)
    assert sorted(names) == sorted([rs_pallas.KERNEL_NAME,
                                    podr2_pallas.KERNEL_NAME])
    text = pipe.fused_program().lower(rows, ids).as_text(debug_info=True)
    assert pipeline.FUSED_SCOPE in text


def test_rs_rows_entry_call_carries_the_name():
    bmat = gf.expand_bitmatrix(gf.cauchy_parity_matrix(2, 1))
    rows = tuple(jnp.zeros((rs_pallas.ROWS_TILE_N,), jnp.uint8)
                 for _ in range(16))
    operand = jnp.asarray(rs_pallas.operand_np(bmat, rs_pallas.group_for(8)))
    assert _pallas_names(
        lambda *r: rs_pallas.apply_rows_operand(operand, r, 2),
        *rows) == [rs_pallas.KERNEL_NAME]
