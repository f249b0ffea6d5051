import pytest

from bench.lib.peaks import peaks_for


def test_v5e_peaks():
    p = peaks_for("TPU v5 lite")
    assert (p["bf16_flops"], p["hbm_bytes_per_s"]) == (197e12, 819e9)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks_for("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks_for("cpu")
