"""Roofline peaks come from a table keyed by device kind, with no default."""
import pytest

from repro.launch import roofline


def test_v5e_peaks_are_the_published_ones():
    peaks = roofline.chip_peaks("TPU v5 lite")
    assert peaks.flops == 197e12
    assert peaks.hbm_bw == 819e9


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        roofline.chip_peaks("cpu")
