"""Bytes and operations from shapes, against numbers worked out by hand."""
import pytest

import kernel_work

MIB = 2 ** 20


def test_rs_encode_4p8_batch_of_8():
    # 8 segments of 16 MiB: 128 MiB of data rows in, 8 parity rows of
    # 4 MiB per segment out = 256 MiB
    w = kernel_work.rs_apply(q=4, r=8, n=4 * MIB, batch=8)
    assert w["bytes"] == 128 * MIB + 256 * MIB
    # (8*8) x (8*4) bit-matrix per byte column, 2 operations a MAC
    assert w["ops"] == 2 * 64 * 32 * 4 * MIB * 8
    least, bound = kernel_work.least_seconds(w, "TPU v5 lite")
    assert bound == "hbm"
    assert least == pytest.approx(384 * MIB / 819e9)


def test_rs_repair_2p1_one_fragment():
    # two 8 MiB survivors in, one 8 MiB fragment out
    w = kernel_work.rs_apply(q=2, r=1, n=8 * MIB, batch=1)
    assert w["bytes"] == 16 * MIB + 8 * MIB
    assert w["ops"] == 2 * 8 * 16 * 8 * MIB


def test_tag_is_one_pass_over_the_bytes():
    w = kernel_work.tag(fragments=96, nbytes=4 * MIB, block_bytes=512,
                        limbs=2)
    words = 96 * 8192 * 2
    assert w["bytes"] == 96 * 4 * MIB + 2 * 4 * words
    assert kernel_work.least_seconds(w, "TPU v5 lite")[1] == "hbm"


def test_ops_bound_is_named():
    w = {"bytes": 1, "ops": 10 ** 15, "ops_peak": "int8_ops_per_s"}
    assert kernel_work.least_seconds(w, "TPU v5 lite") == (
        pytest.approx(10 ** 15 / 393e12), "int8_ops_per_s")


@pytest.mark.parametrize("kind", ["TPU v4", "cpu", "source"])
def test_unknown_device_kind_is_an_error(kind):
    with pytest.raises(KeyError):
        kernel_work.peaks(kind)
