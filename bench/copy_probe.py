#!/usr/bin/env python3
"""Time the host-to-device copy of one saturate window, on the chip.

    python3 bench/copy_probe.py [--workload <cell>] [--repeats 4]
        [--out <json>]

Makes one random uint8 window of the cell's shape, (cameras,
frames_per_step, height, width, 3), and hands it to the device with
``jnp.asarray`` until ``block_until_ready`` returns, ``--repeats``
times in each layout: as the session hands it over (``u8_5d``), with
each frame's pixels flattened to one axis (``u8_flat_pixels``, the
same bytes as (C, T, H*W*3)), and converted to float32 on the host
first (``f32_5d_convert_included``, the conversion's time included).
No session, kernel or program runs: this is the copy alone, which the
step's ``session.readback`` waits for along with its device work.

The last line of standard output is one JSON object: ``device``,
``shape`` and, for each layout, ``<layout>_ms`` (every repeat's time)
and ``<layout>_bytes`` (the bytes that crossed).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

LAYOUTS = {
    "u8_5d": lambda w: w,
    "u8_flat_pixels": lambda w: w.reshape(*w.shape[:2], -1),
    "f32_5d_convert_included": lambda w: np.asarray(w, np.float32),
}


def measure(shape, repeats: int, seed: int = 0) -> dict:
    """Each layout's copy times (ms) for one random uint8 window."""
    import jax.numpy as jnp
    win = np.random.default_rng(seed).integers(0, 256, shape,
                                               dtype=np.uint8)
    out = {"shape": list(shape)}
    for name, layout in LAYOUTS.items():
        times, nbytes = [], 0
        for _ in range(repeats):
            t0 = time.perf_counter()
            x = jnp.asarray(layout(win))
            x.block_until_ready()
            times.append((time.perf_counter() - t0) * 1e3)
            nbytes = x.nbytes
            del x
        out[name + "_ms"] = times
        out[name + "_bytes"] = nbytes
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="detrac24_540p.saturate")
    ap.add_argument("--repeats", type=int, default=4)
    ap.add_argument("--out", default=None,
                    help="also write the result to this JSON file")
    args = ap.parse_args(argv)
    from bench import harness
    from bench.run import load_cell
    _, cell, cfg, traffic = load_cell(args.workload)
    try:
        devices = harness.require_devices(cell["chips"])
    except harness.NoChip as e:
        print(e, file=sys.stderr)
        return 3
    shape = (cfg["cameras"], traffic["frames_per_step"], cfg["height"],
             cfg["width"], 3)
    res = measure(shape, args.repeats)
    res.update(workload=args.workload, device=devices[0].device_kind)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(res, indent=1))
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
