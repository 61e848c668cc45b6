"""Device memory as the result line reports it, and two readers' arithmetic."""

import jax
import pytest

from benchmarks.harness import device
from benchmarks.layer_metrics import serve_good_rows_median_1s, serve_resident_gib


class _Device:
    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        return self._stats


def test_memory_peak_is_a_maximum_never_a_sum(monkeypatch):
    """The numbers of ``lr_tb.train_packed`` on the chip (PR 22), in GB."""
    chip = _Device({
        "bytes_in_use": 3.27, "bytes_reserved": 1.08,
        "peak_bytes_in_use": 3.55, "peak_bytes_reserved": 1.08,
    })
    monkeypatch.setattr(jax, "local_devices", lambda: [chip, _Device(None)])
    assert device.held_bytes() == 4  # one reading, in use + reserved, as int
    monkeypatch.setattr(jax, "local_devices", lambda: [
        _Device({k: int(v * 1e9) for k, v in chip.memory_stats().items()})
    ])
    held = device.held_bytes()
    assert held == 4_350_000_000
    assert device.memory_peak_bytes(held) == held  # not 3.55 + 1.08
    # a serve cell: the tier holds little, the peak is set-up's
    assert device.memory_peak_bytes(1_075_000_000) == 3_550_000_000


def test_an_unknown_device_kind_is_an_error():
    assert device.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="add it with its source"):
        device.peaks("TPU v9")


def test_serve_readers():
    run = {"window": {"good_per_second": [8600, 8700, 2000, 8650]}, "held_bytes": 1 << 30}
    assert serve_good_rows_median_1s.read(run) == 8625.0  # the stall's second does not move it
    assert serve_resident_gib.read(run) == 1.0
