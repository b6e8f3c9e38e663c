"""Work counts, the peaks table and the roofline share, by hand."""

import json
import math

import pytest

from bench.work import UnknownDeviceKind, peaks_for, roofline_s, rowfft_work


@pytest.mark.parametrize("n,devices", [(8192, 1), (32768, 4), (1024, 2)])
def test_rowfft_work_by_hand(n, devices):
    w = rowfft_work(n, devices=devices)
    log2n = int(math.log2(n))
    assert w["flops"] == 2 * n * 5 * n * log2n
    assert w["bytes"] == 32 * n * n
    assert w["device_flops"] == w["flops"] / devices
    assert w["device_bytes"] == w["bytes"] / devices


def test_rowfft_work_8192_numbers():
    w = rowfft_work(8192)
    assert w["flops"] == 8_724_152_320
    assert w["bytes"] == 2_147_483_648


def test_bytes_bound_every_cell_size():
    peaks = peaks_for("TPU v5 lite")
    for n, devices in ((8192, 1), (32768, 4)):
        w = rowfft_work(n, devices=devices)
        t, bound = roofline_s(w["device_flops"], w["device_bytes"], peaks)
        assert bound == "bytes"
        assert t == pytest.approx(w["device_bytes"] / 819e9)


def test_flops_bound_when_flops_dominate():
    t, bound = roofline_s(197e12, 1.0, peaks_for("TPU v5 lite"))
    assert (t, bound) == (pytest.approx(1.0), "flops")


def test_peaks_for_v5e_has_its_source():
    p = peaks_for("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in p["source"]


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5e", ""])
def test_unknown_device_kind_raises(kind):
    with pytest.raises(UnknownDeviceKind, match="no peaks"):
        peaks_for(kind)


def test_peaks_from_another_table(tmp_path):
    table = tmp_path / "peaks.json"
    table.write_text(json.dumps({"X": {"flops_per_s": 1.0,
                                       "hbm_bytes_per_s": 2.0}}))
    assert peaks_for("X", table)["hbm_bytes_per_s"] == 2.0
    with pytest.raises(UnknownDeviceKind):
        peaks_for("TPU v5 lite", table)
