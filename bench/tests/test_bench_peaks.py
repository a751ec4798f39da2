import pytest

from bench.peaks import peak_for


def test_v5e_peaks():
    p = peak_for("TPU v5 lite")
    assert p.flops == 197e12 and p.hbm_bytes_s == 819e9
    assert "v5e" in p.source


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "", "TPU v5"])
def test_unknown_device_kind_is_refused(kind):
    with pytest.raises(KeyError):
        peak_for(kind)
