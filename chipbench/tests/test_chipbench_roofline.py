"""The roofline arithmetic: least time, its bound, the share."""
import pytest

from chipbench import roofline


def test_scan_least_time_at_10m_is_the_memory_bound():
    flops, nbytes = roofline.scan_cost(64, 10_000_000, 96, 10)
    assert flops == 2 * 64 * 10_000_000 * 96
    assert nbytes == 10_000_000 * 96 * 4 + 64 * 96 * 4 + 64 * 10 * 8
    t, bound = roofline.least_time(flops, nbytes, "TPU v5 lite")
    assert bound == "memory"
    assert t == pytest.approx(nbytes / 819e9)
    assert 4.6e-3 < t < 4.8e-3


def test_a_large_batch_is_compute_bound():
    flops, nbytes = roofline.scan_cost(8192, 1_000_000, 96, 10)
    t, bound = roofline.least_time(flops, nbytes, "TPU v5 lite")
    assert bound == "compute"
    assert t == pytest.approx(flops / 197e12)


def test_a_kind_without_published_peaks_is_an_error():
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")
