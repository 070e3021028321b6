import pytest

import peaks


def test_v5e_peaks_are_the_published_ones():
    p = peaks.peak("TPU v5 lite")
    assert p["bf16_flops"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9


def test_an_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peak("TPU v4")
    with pytest.raises(KeyError):
        peaks.peak("cpu")
