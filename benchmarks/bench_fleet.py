"""Fleet-scale sharded serving: ``ShedSession.step()`` with the camera
axis laid over a device mesh (``repro.core.fleet``) vs the single-device
device-serve path, at C >= 1024 cameras.

Three measurements on the same seeded trace:

  * ``single_device_ms`` — the unsharded device step at C cameras
    (the pre-fleet baseline);
  * ``fleet_wall_ms``   — the sharded step over all local devices;
  * ``shard_program_ms`` — the unsharded step at C/ndev cameras: the
    *exact* program each mesh device runs concurrently (the serve plane
    is row-local with zero cross-device collectives), i.e. the fleet
    step's critical path on hardware with one real core per device.

On a real multi-core/multi-chip host ``fleet_wall_ms`` tracks
``shard_program_ms``; on CI's simulated devices (8 XLA host devices
time-slicing ``host_cpus`` cores) the wall clock cannot beat the
baseline, so the scaling claim is asserted on ``speedup_bound =
single_device_ms / shard_program_ms`` — valid because every per-camera
op (admission compare, CDF ring push, (C,K) lane select, Eq. 17-20
tick; the (C,W) threshold sort dominates) is linear in the camera rows.
Bit parity of the sharded vs unsharded decisions is asserted
unconditionally.

It measures the devices of its own process and starts no other: one
device gives a one-shard mesh. The CPU rehearsal gets its simulated
devices from the command line:

  XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
      python -m benchmarks.run --quick --only fleet
"""
from __future__ import annotations

import json
import os
import sys

import numpy as np

from benchmarks.common import FPS, Timer, best_ms

BENCH_SEED = 0
PARITY_STEPS = 4


def _sessions(C, W, ndev, rng):
    from repro.core import Query, open_session
    hist = rng.uniform(0, 1, 2000).astype(np.float32)
    kw = dict(num_cameras=C, train_utilities=hist, queue_size=4,
              queue_capacity=16, cdf_window=W)
    q = Query.single("red", latency_bound=1.0, fps=FPS)
    single = open_session(q, **kw)
    fleet = open_session(q, shard_cameras=True, **kw)
    kw["num_cameras"] = C // ndev
    shard = open_session(q, **kw)
    return single, fleet, shard


def _measure(quick: bool) -> dict:
    import jax
    ndev = len(jax.devices())
    C = 1024 if quick else 2048
    W = 512 if quick else 2048
    T = 8
    rng = np.random.default_rng(BENCH_SEED)
    single, fleet, shard = _sessions(C, W, ndev, rng)
    for s in (single, fleet, shard):
        s.report_backend_latency(1.0 / (C * FPS))

    # bit parity on a seeded trace before any timing
    parity_ok = True
    for _ in range(PARITY_STEPS):
        u = rng.uniform(0, 1, (C, T)).astype(np.float32)
        r1 = single.step(utilities=u, tick=True)
        r2 = fleet.step(utilities=u, tick=True)
        if not (np.array_equal(r1.decisions, r2.decisions) and
                np.array_equal(np.asarray(single.state.threshold),
                               np.asarray(fleet.state.threshold))):
            parity_ok = False
    assert parity_ok, "sharded decisions diverged from single-device path"

    u = rng.uniform(0, 1, (C, T)).astype(np.float32)
    u_shard = u[: C // ndev]
    t_single = best_ms(lambda: single.step(utilities=u, tick=True),
                       n=3, repeats=3)
    t_fleet = best_ms(lambda: fleet.step(utilities=u, tick=True),
                      n=3, repeats=3)
    t_shard = best_ms(lambda: shard.step(utilities=u_shard, tick=True),
                      n=3, repeats=3)

    speedup_bound = t_single / t_shard
    if ndev >= 8:
        assert speedup_bound >= 4.0, (
            f"per-shard program at C/{ndev} only {speedup_bound:.2f}x "
            f"faster than the C-camera single-device step")
    return {
        "cameras": C,
        "devices": ndev,
        "host_cpus": os.cpu_count(),
        "parity_ok": parity_ok,
        "single_device_ms": t_single,
        "fleet_wall_ms": t_fleet,
        "shard_program_ms": t_shard,
        "per_camera_us_single": t_single / C * 1e3,
        "per_camera_us_fleet_bound": t_shard / C * 1e3,
        "speedup_bound": speedup_bound,
        "fleet_wall_speedup": t_single / t_fleet,
    }


def run(quick=True):
    with Timer() as t:
        derived = _measure(quick)
    return {"us_per_call": t.us, "derived": derived}


if __name__ == "__main__":
    print(json.dumps(run("--full" not in sys.argv), indent=2))
