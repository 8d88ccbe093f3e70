import pytest

import roofline


def test_peaks_lookup():
    p = roofline.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("TPU v99")


def test_kernel_work_from_shapes():
    assert roofline.cache_probe_work(lanes=4096, ways=4)["bytes"] == \
        4096 * (4 + 32 + 8)
    assert roofline.gather_blocks_work(lanes=65536, itemsize=4)["bytes"] == \
        2 * 65536 * 4


def test_roofline_share():
    peak = roofline.peaks("TPU v5 lite")
    shape = dict(lanes=4096, itemsize=4)
    least = 2 * 4096 * 4 / 819e9
    assert roofline.roofline_share("gather_blocks", shape, 10, 10 * least,
                                   peak) == pytest.approx(100.0)
    assert roofline.roofline_share("gather_blocks", shape, 0, 1.0,
                                   peak) is None
