"""Fixtures shared by the harness's tests."""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from yardstick import peaks, spec  # noqa: E402


@pytest.fixture
def reader_contexts(monkeypatch) -> list:
    """Every context a run hands its per-layer readers, in order."""
    seen, orig = [], spec.metric_reader

    def metric_reader(name, bench=spec.BENCH):
        read = orig(name, bench)

        def recorded(ctx):
            seen.append(ctx)
            return read(ctx)
        return recorded
    monkeypatch.setattr(spec, "metric_reader", metric_reader)
    return seen


@pytest.fixture
def v5e_peaks(monkeypatch):
    """The TPU v5e's peaks for any device, so that a traced run on the
    CPU finds a row for its readers."""
    v5e = peaks.peaks("TPU v5 lite")
    monkeypatch.setattr(peaks, "peaks", lambda kind: v5e)
