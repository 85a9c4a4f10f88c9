import pytest

from benchlib import cost
from benchlib.peaks import PEAKS, peaks_for


def test_histogram_reads_every_id_once():
    c = cost.histogram_cost(1 << 20, 33)
    assert c["bytes"] == 4 * ((1 << 20) + 33)
    assert c["ops"] == 1 << 20


def test_destinations_read_and_write_every_id():
    c = cost.destinations_cost(1000, 9)
    assert c["bytes"] == 4 * (2000 + 10)


def test_roofline_is_memory_bound_on_v5e():
    p = peaks_for("TPU v5 lite")
    c = cost.histogram_cost(1 << 23, 33)
    t = cost.roofline_seconds(c, p)
    assert t == pytest.approx(c["bytes"] / 819e9)
    assert c["ops"] / p["int8_ops"] < t


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        peaks_for("cpu")
    assert all("source" in v for v in PEAKS.values())
