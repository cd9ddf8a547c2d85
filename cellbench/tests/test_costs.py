import pytest

from cellbench import costs


def test_peaks_are_keyed_by_device_kind_and_unknown_is_an_error():
    v5e = costs.device_peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        costs.device_peaks("cpu")


def test_fm_adam_step_min_bytes_at_kdd12():
    got = costs.fm_adam_step_min_bytes(54_686_452, 8, 65_536, 16)
    tables = 6 * 4 * (54_686_453 * 9 + 1)
    assert got == tables + 65_536 * 16 * 8 + 65_536 * 8 + 2 * 65_536 * 16 * 9 * 4
    # the least time at 819 GB/s: about 14.5 ms
    assert 14.0e-3 < got / 819e9 < 15.0e-3
