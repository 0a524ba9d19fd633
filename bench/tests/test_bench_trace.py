"""The trace reduction on one closed-loop serve step of 8 cameras x 8
frames at 1280x720, recorded on a TPU v5e (window, benchmark spans and
device operations on one timeline, as ``bench.trace.align`` gives
them), and the clock alignment on made-up marker runs."""
from __future__ import annotations

import gzip
import json

import pytest

from tiny import ROOT

TRACE = ROOT / "bench" / "tests" / "data" / "saturate_step_trace.json.gz"


@pytest.fixture(scope="module")
def reduced():
    from bench import trace as tr
    with gzip.open(TRACE, "rt") as f:
        return tr.summarize(json.load(f), chips=1, kernel="ingest")


def test_busy_and_window(reduced):
    assert reduced["window_s"] == pytest.approx(1.525982376)
    assert 0 < reduced["busy_s"] < reduced["window_s"]
    assert reduced["busy_s"] == pytest.approx(0.011438903, rel=1e-6)


def test_kernel_found_by_name(reduced):
    assert reduced["kernel_calls"] == 1
    assert reduced["kernel_s"] == pytest.approx(0.006920982, rel=1e-6)
    name, seconds = reduced["device_ops"][0]
    assert name.startswith("%ingest_batch") and seconds == reduced["kernel_s"]
    assert len(reduced["device_ops"]) <= 10


def test_idle_gaps_attributed_to_host_spans(reduced):
    gaps = dict(reduced["idle_gaps"])
    idle = reduced["window_s"] - reduced["busy_s"]
    assert sum(gaps.values()) == pytest.approx(idle, rel=1e-9)
    assert max(gaps, key=gaps.get) == "bench.step"
    assert {"bench.next_frames", "bench.backend"} <= set(gaps)


def test_layer_metrics_read_the_trace(reduced):
    from bench import run as R
    config = json.loads((ROOT / "bench/configs/detrac24_540p.json").read_text())
    record = {"steps": 1, "config": config,
              "peaks": R.load_peaks("TPU v5 lite"),
              "shape": {"cameras": 8, "frames": 8, "height": 720,
                        "width": 1280}}
    roof = R.load_module(ROOT / "bench/layer_metrics/"
                         "ingest_roofline.saturate.py").read(record, reduced)
    assert 4.0 < roof < 4.3          # 0.288 ms of bytes over 6.92 ms
    idle = R.load_module(ROOT / "bench/layer_metrics/"
                         "device_idle_share.saturate.py").read(record, reduced)
    assert idle == pytest.approx(100 * (1 - 0.011438903 / 1.525982376))


def test_align_moves_host_stamps_onto_the_device_clock():
    """Device clock = host clock + 100 s. Each marker ran 10 us into a
    host interval of 50 us and took 20 us, so both ends give the offset
    to within the interval's slack."""
    from bench import trace as tr
    off = 100.0
    host = {"window": (1.0, 3.0),
            "marks": [(0.99990, 0.99995), (3.00001, 3.00006)],
            "spans": [(1.1, 1.9, "bench.step"), (2.0, 2.9, "bench.step")]}
    dev = {"devices": {0: [(off + 1.5, off + 1.6, "%fusion"),
                           (off + 2.5, off + 2.55, "%ingest_batch.1")]},
           "marks": {0: [(off + 0.99991, off + 0.99993),
                         (off + 3.00002, off + 3.00004)]}}
    a = tr.align(dev, host)
    assert a["window"] == pytest.approx((off + 1.0, off + 3.0), abs=2e-5)
    assert abs(a["clock_drift_s"]) < 1e-6
    red = tr.summarize(a, chips=1, kernel="ingest")
    assert red["busy_s"] == pytest.approx(0.15, abs=1e-9)
    gaps = dict(red["idle_gaps"])
    assert gaps["bench.step"] == pytest.approx(0.8 + 0.9 - 0.15, abs=1e-4)
    assert gaps[tr.UNCOVERED] == pytest.approx(0.3, abs=1e-4)
