"""The chip peaks table: keyed by the exact device_kind, unknown = error."""

import pytest

from cosmos_curate_tpu.models import flops


class _Device:
    def __init__(self, kind):
        self.device_kind = kind


def test_known_kind_returns_its_published_peak(monkeypatch):
    import jax

    monkeypatch.setattr(jax, "devices", lambda: [_Device("TPU v5 lite")])
    assert flops.chip_peak_flops() == 197e12
    assert flops.mfu(197e12, 2.0) == pytest.approx(0.5)


def test_unknown_kind_raises_instead_of_assuming_a_peak():
    """The CPU this suite runs on is not a chip with a published peak."""
    with pytest.raises(ValueError, match="no published peak for device kind"):
        flops.chip_peak_flops()
