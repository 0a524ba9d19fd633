"""The ``cityflow40_1080p`` configuration: the file carries every key
the harness reads, in the kinds the 540p file gives them, its cameras
split evenly over its chips, and the saturate loop runs sound on it at
48x80 over a camera mesh of four virtual devices, as four chips would
run it."""
from __future__ import annotations

import json
import os
import subprocess
import sys

from tiny import ROOT

# every key of a configuration that bench/ reads
HARNESS_KEYS = (
    "cameras", "height", "width", "camera_fps", "rendered_streams", "chips",
    "query", "hue_ranges", "bins", "latency_bound_s", "background_alpha",
    "fg_threshold", "gain_range", "queue_size", "queue_capacity",
    "cdf_window", "quantile_bins", "quantile_range")
# what the deployment shares with detrac24_540p: the query and every
# setting of the shedder
SAME_AS_540P = (
    "query", "hue_ranges", "bins", "latency_bound_s", "background_alpha",
    "fg_threshold", "gain_range", "queue_size", "queue_capacity",
    "cdf_window", "quantile_bins", "quantile_range", "guarantees",
    "reduced")


def _load(name: str) -> dict:
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())


def test_config_has_what_the_harness_reads():
    cfg, base = _load("cityflow40_1080p"), _load("detrac24_540p")
    assert set(cfg) == set(base)
    for key in HARNESS_KEYS:
        assert type(cfg[key]) is type(base[key]), key
    for key in SAME_AS_540P:
        assert cfg[key] == base[key], key
    assert (cfg["cameras"], cfg["height"], cfg["width"],
            cfg["camera_fps"], cfg["chips"]) == (40, 1080, 1920, 10.0, 4)
    assert cfg["cameras"] % cfg["chips"] == 0
    assert cfg["cameras"] % cfg["rendered_streams"] == 0


FLEET = r'''
import sys
sys.path.insert(0, sys.argv[1])
from tiny import run_tiny
res = run_tiny("cityflow40_1080p.saturate", cameras=8, chips=4)
assert res["compiles_in_window"] == 0, res["compiles_in_window"]
assert res["attempted"] > 0 and res["failed"] == 0
print("CORRECT", res["correct"], res["device"]["count"])
'''


def test_saturate_loop_runs_sound_on_four_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=f"{ROOT}:{ROOT / 'src'}")
    p = subprocess.run([sys.executable, "-c", FLEET,
                        str(ROOT / "bench" / "tests")], env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    assert "CORRECT True 4" in p.stdout, p.stdout[-2000:]
