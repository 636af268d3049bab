"""The benchmark measures only on a TPU whose peaks it knows."""

import json
from types import SimpleNamespace

import pytest

from bench import device, run


def fake(platform, kind, n):
    return [SimpleNamespace(platform=platform, device_kind=kind)] * n


def test_peaks_name_their_source_and_the_v5e():
    table = json.loads(device.PEAKS.read_text())
    assert "TPU v5e" in table["source"]
    v5e = device.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9


@pytest.mark.parametrize("precision,passes", [("default", 1), ("high", 3),
                                              ("highest", 6)])
def test_the_matmul_peak_divides_the_bf16_peak_by_its_passes(precision,
                                                              passes):
    v5e = device.peaks_for("TPU v5 lite")
    assert device.matmul_peak(v5e, precision) == 197e12 / passes


def test_an_unknown_matmul_precision_is_an_error():
    with pytest.raises(device.DeviceError, match="no pass count"):
        device.matmul_peak(device.peaks_for("TPU v5 lite"), "float64")


@pytest.mark.parametrize("kind", ["TPU v4", "TPU v6 lite", "cpu", ""])
def test_an_unknown_device_kind_is_an_error(kind):
    with pytest.raises(device.DeviceError, match="no published peaks"):
        device.peaks_for(kind)


@pytest.mark.parametrize("devices,match", [
    (fake("cpu", "cpu", 8), "no TPU"),
    (fake("gpu", "NVIDIA H100", 4), "no TPU"),
    ([], "no TPU"),
    (fake("tpu", "TPU v5 lite", 1), "needs 4 chips"),
    (fake("tpu", "TPU v9", 4), "no published peaks"),
])
def test_guard_refuses_what_it_cannot_measure(devices, match):
    with pytest.raises(device.DeviceError, match=match):
        device.guard(devices, 4)


def test_guard_returns_the_chips_peaks():
    assert device.guard(fake("tpu", "TPU v5 lite", 4), 4) == \
        device.peaks_for("TPU v5 lite")


def test_without_a_tpu_the_run_exits_nonzero_and_prints_no_result(capsys):
    """The tests run on the CPU: the entry point finds no TPU."""
    assert run.main(["--workload", "chol_n28k", "--seed", "1",
                     "--seconds", "1"]) == 1
    out, err = capsys.readouterr()
    assert out == ""
    assert "no TPU" in err and "[cpu cpu x" in err
