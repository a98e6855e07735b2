"""The table of peaks is keyed by device kind and refuses others."""

import pytest

import peaks


def test_v5e_peaks():
    p = peaks.lookup("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5", ""])
def test_unknown_kind_is_an_error(kind):
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.lookup(kind)
