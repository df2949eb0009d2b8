import pytest

from benchmarks import devices


def test_the_v5e_is_in_the_table():
    peaks = devices.peaks_for("TPU v5 lite")
    assert peaks["bf16_flops_per_s"] == 197e12
    assert peaks["hbm_bytes_per_s"] == 819e9


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "", "_source"])
def test_an_unknown_device_kind_is_an_error(kind):
    with pytest.raises(KeyError):
        devices.peaks_for(kind)


def test_a_run_without_a_chip_is_refused():
    # the tests run on the CPU, which is exactly the case to refuse
    with pytest.raises(SystemExit):
        devices.require_chips(1)
