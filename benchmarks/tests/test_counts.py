"""counts.py against bytes and FLOPs worked by hand for each configuration."""

import pytest

from benchmarks.harness import counts, device, spec


def _count(cell_name):
    cell = spec.load_cell(cell_name)
    return counts.per_fit(cell.config["counts"], cell.stage_params(),
                          cell.config["inputData"]["paramMap"])


@pytest.mark.parametrize("cell", ["lr_fit_ref20", "lr_fit_ref20_dp4"])
def test_lr_20_rounds_is_the_same_work_on_one_chip_and_on_four(cell):
    c = _count(cell)
    # 20 rounds x 100000 rows; a row is 100 features + label + weight, f32
    assert c["rows"] == 2_000_000
    assert c["bytes"] == 2_000_000 * 102 * 4 == 816_000_000
    assert c["flops"] == 2_000_000 * 400 == 800_000_000


def test_least_seconds_says_which_roof():
    peaks = device.peaks_for("TPU v5 lite")
    least = counts.least_seconds(_count("lr_fit_ref20"), peaks, 1)
    assert least["bound"] == "bytes"
    assert least["seconds"] == pytest.approx(0.816e9 / 819e9)
    four = counts.least_seconds(_count("lr_fit_ref20_dp4"), peaks, 4)
    assert four["seconds"] == pytest.approx(least["seconds"] / 4)
    heavy = counts.least_seconds({"flops": 1e15, "bytes": 1e6}, peaks, 1)
    assert heavy["bound"] == "flops"
    assert heavy["seconds"] == pytest.approx(1e15 / 197e12)


def test_unknown_device_is_an_error():
    with pytest.raises(device.DeviceError):
        device.peaks_for("TPU v9 imaginary")
    with pytest.raises(KeyError):
        counts.per_fit("no_such_count", {}, {})
