#!/usr/bin/env python3
"""Smoke test of the shedder's fused serve path on a TPU chip.

One chip (no arguments), 8 cameras at 1280x720, frames rendered from
seeds by ``repro.data.synthetic``:

  1. parity: the compiled Pallas ingest kernel (``ingest_batch``, both
     the plain build the fused serve step runs and the build with the
     foreground-bbox rider) against the jnp oracle ``ingest_batch_ref``
     on the same frames, two chained 8-frame batches with carried
     ``(bg, gain)`` state, at the oracle tests' tolerances;
  2. serve: ``repro.launch.serve.main`` once on the virtual clock, which
     compiles the serve programs, then with ``--wall-clock``, once with
     the mock backend and once with ``--real-backend``, each camera at
     ``SERVE_FPS``.

``--fleet`` (four chips) runs only the camera-sharded session: 32
cameras at 1280x720, 8 per chip, its fused steps and ``next_frames``
pops checked bit-identical against the unsharded session on one
chip, and every state leaf spread over 4 devices.

Earlier output lines are smoke facts (sizes, first-call seconds,
frames served, parity); the last line is the JSON result. The script
exits non-zero, printing no result, when JAX finds no TPU or any phase
fails.

  python3 chip_smoke.py
  python3 chip_smoke.py --fleet
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

SEED = 0
CAMS, T, H, W = 8, 8, 720, 1280
SERVE_FRAMES = 4 * T                  # frames per camera in a timed pass
WARMUP_FRAMES = T                     # frames per camera in the warm-up
# per-camera frame rate of the launcher passes: one (8, 1, 720, 1280, 3)
# float32 window (88 MB) arrives every 0.25 s, which the host stacks and
# copies to the chip within that period; at 30 fps the windows would
# outgrow the host and expire in the queue (PERF.md, section 7)
SERVE_FPS = 4.0
FLEET_CHIPS, FLEET_CAMS, FLEET_STEPS = 4, 32, 3
TOL = dict(atol=1e-4, rtol=1e-5)      # tests/test_ingest_fused.py


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def fact(name: str, **kv) -> None:
    print(json.dumps({"phase": name, **kv}), flush=True)


def render(cams: int, frames: int, height: int, width: int) -> np.ndarray:
    """(cams, frames, height*width, 3) float32 RGB from seeded scenes."""
    from repro.data.synthetic import generate_dataset
    scs = generate_dataset(range(SEED, SEED + cams), num_frames=frames,
                           height=height, width=width)
    rgb = np.stack([s.frames_rgb() for s in scs]).astype(np.float32)
    return rgb.reshape(cams, frames, height * width, 3)


def seeded_model(colors):
    """Random utility matrices in [0, 1] with unit normalizers, so
    utilities (PF rows sum to 1) stay in the CDF's [0, 1] range."""
    from repro.core.utility import B_S, B_V, UtilityModel
    rng = np.random.default_rng(SEED)
    nc = len(colors)
    m = rng.uniform(0, 1, (nc, B_S, B_V)).astype(np.float32)
    return UtilityModel(tuple(colors), m, np.zeros_like(m),
                        np.ones(nc, np.float32), "or")


def parity(cams: int = CAMS, frames: int = T, height: int = H,
           width: int = W) -> None:
    """Pallas ingest kernel vs jnp oracle, two chained batches, for the
    plain build (``width=0``) and the bbox build."""
    import jax
    import jax.numpy as jnp
    from repro.core import Query
    from repro.kernels.hsv_features.kernel import ingest_batch
    from repro.kernels.hsv_features.ref import ingest_batch_ref
    from repro.kernels.hsv_features.ops import query_constants

    q = Query.any_of("red", "yellow")
    model = seeded_model(q.colors)
    m_pos, norm, op = query_constants(model, q.num_colors, q.bs, q.bv, q.op)
    rgb = render(cams, 2 * frames, height, width)
    ref = jax.jit(ingest_batch_ref, static_argnames=(
        "hue_ranges", "op", "width", "bg_valid"))
    n = height * width
    names = ("counts", "totals", "fg_total", "utility", "bg", "gain",
             "bbox")
    bad_total = 0
    for bbox_width in (0, width):
        kw = dict(hue_ranges=q.hue_ranges, op=op, width=bbox_width)
        state_k = state_r = (jnp.zeros((cams, n)), jnp.ones((cams,)))
        for b in range(2):
            x = jnp.asarray(rgb[:, b * frames:(b + 1) * frames])
            t0 = time.perf_counter()
            out_k = jax.block_until_ready(
                ingest_batch(x, *state_k, m_pos, norm, bg_valid=b > 0, **kw))
            t_k = time.perf_counter() - t0
            t0 = time.perf_counter()
            out_r = jax.block_until_ready(
                ref(x, *state_r, m_pos, norm, bg_valid=b > 0, **kw))
            t_r = time.perf_counter() - t0
            check(len(out_k) == len(out_r) == 6 + bool(bbox_width),
                  f"width={bbox_width}: {len(out_k)} kernel outputs, "
                  f"{len(out_r)} oracle outputs")
            state_k, state_r = out_k[4:6], out_r[4:6]
            diffs = {}
            for name, a, r in zip(names, out_k, out_r):
                a, r = np.asarray(a), np.asarray(r)
                check(a.shape == r.shape,
                      f"{name} shape {a.shape} != {r.shape}")
                check(bool(np.isfinite(a).all()), f"{name} not finite")
                bad = ~np.isclose(a, r, **TOL)
                bad_total += int(bad.sum())
                diffs[name] = {"max_abs_diff": float(np.abs(a - r).max()),
                               "n_outside_tol": int(bad.sum())}
            fact("parity", bbox=bool(bbox_width), batch=b, cameras=cams,
                 frames=frames, height=height, width=width,
                 kernel_first_call_s=t_k, oracle_first_call_s=t_r,
                 fg_pixels=float(np.asarray(out_k[2]).sum()), diffs=diffs)
    check(bad_total == 0, f"{bad_total} kernel outputs outside {TOL}")


def serve(cams: int = CAMS, frames: int = SERVE_FRAMES, height: int = H,
          width: int = W, out_dir: Path = ROOT / "results" / "smoke") -> None:
    """The serving launcher: a short virtual-clock pass, which compiles
    the window programs before any clock starts, then the wall clock
    with the mock and the real backend.

    Every arrival instant holds one frame per camera and the coalescer
    flushes at its 50 ms deadline, so each window is ``(cams, 1)``
    frames and goes through the fused step; the warm-up has to run
    every window program (first and carried background) the timed
    passes run."""
    from repro.launch import serve as launcher
    passes = (("warmup", "mock", WARMUP_FRAMES, []),
              ("wall", "mock", frames, ["--wall-clock"]),
              ("wall", "real", frames, ["--wall-clock", "--real-backend"]))
    for clock, backend, n, extra in passes:
        argv = ["--cams", str(cams), "--frames", str(n),
                "--height", str(height), "--width", str(width),
                "--fps", str(SERVE_FPS), "--seed", str(SEED),
                "--metrics-out", str(out_dir / f"serve_{clock}_{backend}.json"),
                *extra]
        t0 = time.perf_counter()
        res = launcher.main(argv)
        counters = res.metrics["counters"]
        lat = res.e2e_latencies()
        fused = int(counters.get("dispatch.fused", 0))
        batched = int(counters.get("dispatch.batched", 0))
        fact("serve", clock=clock, backend=backend, cameras=cams, frames=n,
             height=height, width=width, fps=SERVE_FPS,
             seconds=time.perf_counter() - t0, offered=len(res.offered),
             processed=len(res.processed),
             expired=int(counters.get("sender.expired", 0)),
             violations=res.violations, fused_steps=fused,
             batched_offers=batched,
             e2e_p50_s=float(np.percentile(lat, 50)) if lat.size else None,
             e2e_max_s=float(lat.max()) if lat.size else None)
        what = f"{clock}/{backend}"
        check(len(res.offered) == cams * n,
              f"{what}: offered {len(res.offered)} != {cams * n}")
        check(len(res.processed) > 0, f"{what}: no frame served")
        check(fused > 0, f"{what}: the fused serve step never ran")
        if clock == "warmup":
            check(batched == 0, f"warm-up: {batched} windows missed the "
                  "fused step, so their programs stay uncompiled")


def fleet(chips: int = FLEET_CHIPS, cams: int = FLEET_CAMS,
          frames: int = T, height: int = H, width: int = W) -> None:
    """Camera-sharded session over ``chips`` devices vs the unsharded
    device session on one, bit for bit."""
    import jax
    from repro.core import Query, fleet as fleet_lib, open_session
    from repro.core.session import ADMIT

    devices = jax.devices()
    check(len(devices) >= chips, f"{len(devices)} devices < {chips}")
    q = Query.any_of("red", "yellow", latency_bound=0.5, fps=30.0)
    model = seeded_model(q.colors)
    # 8 rendered streams reused across the camera lanes
    steps = FLEET_STEPS
    streams = render(min(cams, CAMS), (steps + 1) * frames, height, width)
    lanes = np.arange(cams) % streams.shape[0]

    def window(i):
        return streams[lanes, i * frames:(i + 1) * frames].reshape(
            cams, frames, height, width, 3)

    # seed the admission CDFs with the streams' own utilities (window 0)
    # and report a backend latency at which Eq. 19 drops about half the
    # frames, so the compared decisions include sheds
    kw = dict(num_cameras=cams, frame_shape=(height, width), model=model)
    hist = open_session(q, **kw).ingest(window(0)).utility
    kw["train_utilities"] = hist.reshape(-1)
    latency = 2.0 / (cams * q.fps)
    ref = open_session(q, **kw)
    fl = open_session(q, mesh=fleet_lib.fleet_mesh(chips), **kw)
    shed = 0
    for s in range(steps):
        x = window(s + 1)
        for sess in (ref, fl):
            sess.report_backend_latency(latency)
        t0 = time.perf_counter()
        r1 = ref.step(frames=x, tick=True)
        t_ref = time.perf_counter() - t0
        t0 = time.perf_counter()
        r2 = fl.step(frames=x, tick=True)
        t_fl = time.perf_counter() - t0
        check(np.array_equal(r1.decisions, r2.decisions),
              f"step {s}: decisions differ")
        for f in dataclasses.fields(ref.state):
            a = np.asarray(getattr(ref.state, f.name))
            b = np.asarray(getattr(fl.state, f.name))
            check(np.array_equal(a, b), f"step {s}: state.{f.name} differs")
        admitted = int((r1.decisions == ADMIT).sum())
        shed += r1.decisions.size - admitted
        fact("fleet_step", step=s, cameras=cams, frames=frames,
             height=height, width=width, chips=chips,
             single_chip_s=t_ref, fleet_s=t_fl, admitted=admitted,
             shed=r1.decisions.size - admitted, decisions_equal=True,
             state_equal=True)
    check(shed > 0, "no frame was shed, so no shed decision was compared")
    k = cams * 2
    p1, p2 = ref.next_frames(k), fl.next_frames(k)
    check(p1 == p2, "next_frames pops differ")
    check(len(p1) > 0, "nothing was queued to pop")
    spread = {}
    for f in dataclasses.fields(fl.state):
        sh = getattr(fl.state, f.name).sharding
        spread[f.name] = len(sh.device_set)
        check(spread[f.name] == chips,
              f"state.{f.name} spans {spread[f.name]} devices")
        if f.name != "bg_valid":
            check(not sh.is_fully_replicated,
                  f"state.{f.name} is replicated, not sharded")
    fact("fleet_pop", popped=len(p1), pops_equal=True,
         devices_per_state_leaf=sorted(set(spread.values())))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--fleet", action="store_true",
                    help="run only the four-chip camera-sharded phase")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {platform!r})",
              file=sys.stderr)
        return 2
    from repro.launch.jax_cache import enable_compile_cache
    fact("setup", platform=platform, kind=devices[0].device_kind,
         devices=len(devices), compile_cache=enable_compile_cache())
    try:
        if args.fleet:
            fleet()
        else:
            parity()
            serve()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
