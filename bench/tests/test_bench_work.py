"""Work counts, chip peaks and the trace reduction's interval logic."""
from __future__ import annotations

import pytest

from tiny import ROOT  # noqa: F401  (sets paths)


def test_ingest_bytes_for_known_shapes():
    from bench.work import ingest_bytes
    # 8 cameras x 8 frames of 1280x720, 2 colours of 64 bins
    n = 1280 * 720
    want = (8 * 8 * n * 3 + 8 * n * 8 + 8 * 8 * 4 * (2 * 65 + 2) + 8 * 8)
    assert ingest_bytes(8, 8, n, 2, 64) == want
    assert ingest_bytes(1, 1, 100, 1, 64) == 300 + 800 + 4 * 67 + 8


def test_least_time_is_bytes_at_the_hbm_peak():
    from bench.run import load_peaks
    from bench.work import least_seconds
    peaks = load_peaks("TPU v5 lite")
    assert least_seconds(819e9, peaks) == pytest.approx(1.0)


def test_unknown_device_kind_raises():
    from bench.run import load_peaks
    with pytest.raises(KeyError):
        load_peaks("cpu")


def test_interval_union_gaps_and_attribution():
    from bench import trace as tr
    busy = tr._union([(0.0, 1.0), (0.5, 2.0), (3.0, 4.0)])
    assert busy == [(0.0, 2.0), (3.0, 4.0)]
    gaps = tr._gaps(tr._clip(busy, 0.0, 5.0), 0.0, 5.0)
    assert gaps == [(2.0, 3.0), (4.0, 5.0)]
    spans = [(1.5, 4.5, "bench.step"), (2.5, 2.75, "bench.next_frames")]
    got = tr._attribute(gaps, spans)
    assert got["bench.step"] == pytest.approx(0.75 + 0.5)
    assert got["bench.next_frames"] == pytest.approx(0.25)
    assert got[tr.UNCOVERED] == pytest.approx(0.5)
