"""Cells cut to a size the CPU test run can hold (48x80 frames, a few
training frames), for the harness tests. Sizes only: the code path is
the cell's own."""
from __future__ import annotations

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def tiny_cell(workload: str, cameras: int = 8, chips: int = 0):
    """The cell ``<config>.<traffic>`` from its files, whether or not
    ``BENCHMARK.json`` lists it. ``chips`` above 1 spreads the cameras
    over a mesh of that many devices, as a four-chip configuration
    does."""
    import json
    name, mix = workload.split(".")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    config = json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                        .read_text())
    traffic = json.loads((ROOT / "bench" / "traffic" / f"{mix}.json")
                         .read_text())
    cell = {"name": workload, "config": name, "traffic": mix}
    config = dict(config, height=48, width=80, cameras=cameras,
                  rendered_streams=min(cameras, config["rendered_streams"]),
                  chips=chips or config["chips"])
    traffic = dict(traffic, train=dict(traffic["train"], frames=40),
                   calib=dict(traffic["calib"], frames=40))
    if traffic["driver"] == "serve":
        traffic["fps"] = 4.0
    return spec, dict(cell, chips=config["chips"]), config, traffic


def run_tiny(workload: str, seconds: float = 1.0, seed: int = 2 ** 31 + 3,
             cameras: int = 8, chips: int = 0):
    import jax
    from bench import run as R
    spec, cell, config, traffic = tiny_cell(workload, cameras, chips)
    return R.run_cell(spec, cell, config, traffic, seed, seconds, False,
                      lambda chips: jax.devices())
