"""Exact counts of the filter's work and least time on a TPU v5e."""
import pytest

from bench import roofline

PX = 1920 * 1080


def test_u8_1080p_counts_and_least_time():
    w = roofline.filter_work(1080, 1920, 7, "uint8", "uint8")
    assert w.ops == 2 * 49 * PX == 203_212_800
    assert w.bytes == 2 * PX == 4_147_200
    assert w.integer
    lt = roofline.least_time(w, "TPU v5 lite")
    assert lt["compute_s"] == pytest.approx(203_212_800 / 393e12, rel=1e-12)
    assert lt["memory_s"] == pytest.approx(4_147_200 / 819e9, rel=1e-12)
    assert lt["bound"] == "memory"
    assert lt["seconds"] == pytest.approx(5.0637362637e-6, rel=1e-9)


def test_f32_1080p_counts_and_least_time():
    w = roofline.filter_work(1080, 1920, 7, "float32", "float32")
    assert w.ops == 203_212_800
    assert w.bytes == 8 * PX == 16_588_800
    assert not w.integer
    lt = roofline.least_time(w, "TPU v5 lite")
    assert lt["compute_s"] == pytest.approx(203_212_800 / 197e12, rel=1e-12)
    assert lt["seconds"] == pytest.approx(16_588_800 / 819e9, rel=1e-12)
    assert lt["bound"] == "memory"


def test_batch_and_shares_scale_the_work():
    one = roofline.filter_work(2160, 3840, 7, "uint8", "uint8")
    four = roofline.filter_work(1080, 1920, 7, "uint8", "uint8", planes=4)
    assert four == one
    assert (one / 4).bytes == one.bytes / 4


def test_unknown_device_is_an_error():
    w = roofline.filter_work(8, 8, 3, "uint8", "uint8")
    with pytest.raises(KeyError, match="no published peaks"):
        roofline.least_time(w, "TPU v9 imaginary")
