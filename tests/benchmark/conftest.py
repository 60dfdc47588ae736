"""Shared by the benchmark's tests.  The chip requirement is stubbed HERE,
never in the benchmark: ``run.py`` itself has no CPU path."""

import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))


@pytest.fixture
def no_chip_needed(monkeypatch):
    """Stand in for the chip: the CPU device's record and the v5e's peaks."""
    from benchmark import generator, run
    from ggrs_tpu.utils.device import device_record

    monkeypatch.setattr(run, "require_chip", lambda chips=1: device_record())
    monkeypatch.setattr(run, "peaks_for", lambda kind: {"hbm_gbs": 819.0})
    # four matches on this CPU tick faster than any mix reckons the chip can
    load = generator.load_traffic
    monkeypatch.setattr(generator, "load_traffic",
                        lambda path: dict(load(path), max_ticks_per_s=8000))
