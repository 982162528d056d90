import pytest

from bench import peaks


def test_v5e_peaks_are_the_published_ones():
    p = peaks.peak("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5", ""])
def test_unknown_device_kind_is_an_error(kind):
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peak(kind)
