#!/usr/bin/env python3
"""Readings the limits of ``correct`` are set from (see PERF.md).

    python3 bench/limits.py --workload <cell> --seeds 1,2,3 --seconds 3 \
        [--control-seeds 3]

Runs the cell once per seed in one process, at the cell's own sizes and
load, with a short window, and prints per seed one JSON line with every
compared number. For the first ``--control-seeds`` seeds it also reads
the control: the plain reference computed in bfloat16 (the precision
below the configuration's float32) put in the program's place. The last
line gives, per number, the largest sound reading (the lower reading)
and the smallest control reading (the upper reading). The benchmark's
own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None, device_check=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    from bench import run as R
    import jax.numpy as jnp
    from bench import harness
    if device_check is None:
        devices = harness.require_devices(R.load_cell(args.workload)[1]["chips"])
        R.use_cache()
        device_check = lambda chips: devices  # noqa: E731
    spec, cell, config, traffic = R.load_cell(args.workload)
    lower, upper = {}, {}
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        res = R.run_cell(spec, cell, config, traffic, seed, args.seconds,
                         False, device_check,
                         control=jnp.bfloat16 if i < args.control_seeds
                         else None)
        sound = res["readings"]
        for k, v in sound.items():
            lower[k] = max(lower.get(k, v), v)
        for k, v in res.get("control", {}).items():
            upper[k] = min(upper.get(k, v), v)
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "sound": sound, "control": res.get("control")}),
              flush=True)
    print(json.dumps({"lower": lower, "upper": upper}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
