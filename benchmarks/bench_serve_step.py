"""Serve-step benchmark (device-resident control plane): the fused
``ShedSession.step()`` — CDF ring push + vectorized admission + top-cap
queue selection + ONE batched (C, W) quantile — against the seed-style
host loop (Python heapq pushes per admitted frame, per-camera
``np.sort`` at every tick).

Three contenders on identical seeded utility traces:

  * ``host_loop``   — :class:`HostLoopShedder`, the pre-fusion serve
    loop kept as baseline AND as the bit-exactness reference;
  * ``fused`` — ``session.step()`` with ``serve="host"`` (the
    vectorized-NumPy twin, the compiled-CPU serving default);
  * ``fused_device`` — ``session.step()`` with ``serve="device"`` (the
    jitted donated-buffer XLA program; ON CPU this pays XLA's slow sort
    lowering — it is the TPU path, reported for transparency).

Decisions and thresholds must match bit-exactly (float32) across all
three — the benchmark verifies this and reports ``parity`` in derived.
Also reports control-tick cost vs ``cdf_window``.
"""
from __future__ import annotations

from typing import List

import numpy as np

from repro.core import Query, open_session
from repro.core.session import ADMIT, SHED_ADMISSION, SHED_QUEUE
from repro.core.shed_queue import UtilityQueue
from repro.core.threshold import next_above
from benchmarks.common import Timer, median_ms

BENCH_SEED = 0


class HostLoopShedder:
    """The seed-style serve control plane: one Python ``UtilityQueue``
    per camera, scalar heapq pushes in ``admit``, and a per-camera
    ``np.sort`` + quantile loop in ``tick``.

    Float32 end-to-end (matching the session's lane semantics, incl.
    the float32 quantile-index arithmetic of Eq. 17), so the fused
    ``step()`` must reproduce its decisions and thresholds bit-exactly.
    """

    def __init__(self, num_cameras: int, *, cdf_window: int = 4096,
                 queue_size: int = 8, queue_capacity: int = 64,
                 fps: float = 10.0, latency_bound: float = 1.0,
                 min_proc: float = 1e-6, ewma_alpha: float = 0.2,
                 ewma_alpha_up: float = 0.6):
        C = self.num_cameras = int(num_cameras)
        self.cdf_buf = np.zeros((C, cdf_window), np.float32)
        self.cdf_len = np.zeros((C,), np.int32)
        self.cdf_pos = np.zeros((C,), np.int32)
        self.threshold = np.full((C,), -np.inf, np.float32)
        self.proc_q = np.zeros((C,), np.float32)
        self.proc_seen = np.zeros((C,), bool)
        self.fps_obs = np.full((C,), float(fps), np.float32)
        self.queues: List[UtilityQueue] = [UtilityQueue(queue_size)
                                           for _ in range(C)]
        self.queue_capacity = int(queue_capacity)
        self.queue_cap = np.full((C,), int(queue_size), np.int32)
        self.budget = float(latency_bound)
        self.min_proc = float(min_proc)
        self.ewma_alpha = float(ewma_alpha)
        self.ewma_alpha_up = float(ewma_alpha_up)

    # -- metric feeds (identical EWMA math to ShedSession) -------------------

    def report_backend_latency(self, lat: float) -> None:
        x = np.float32(max(float(lat), self.min_proc))
        a = np.where(x > self.proc_q, np.float32(self.ewma_alpha_up),
                     np.float32(self.ewma_alpha))
        self.proc_q = np.where(self.proc_seen,
                               self.proc_q + a * (x - self.proc_q), x)
        self.proc_seen = np.ones_like(self.proc_seen)

    def seed_cdf(self, us: np.ndarray) -> None:
        self._cdf_push(np.broadcast_to(
            np.asarray(us, np.float32).reshape(-1),
            (self.num_cameras, np.asarray(us).size)))

    def _cdf_push(self, us: np.ndarray) -> None:
        C, W = self.cdf_buf.shape
        us = np.asarray(us, np.float32)
        if us.shape[1] >= W:
            us = us[:, -W:]
        k = us.shape[1]
        if k == 0:
            return
        idx = (self.cdf_pos[:, None] + np.arange(k)[None]) % W
        self.cdf_buf[np.arange(C)[:, None], idx] = us
        self.cdf_pos = ((self.cdf_pos + k) % W).astype(np.int32)
        self.cdf_len = np.minimum(self.cdf_len + k, W).astype(np.int32)

    # -- the seed-style admit + tick loop ------------------------------------

    def admit(self, utilities: np.ndarray) -> np.ndarray:
        u = np.asarray(utilities, np.float32)
        C, T = u.shape
        self._cdf_push(u)
        decisions = np.where(u < self.threshold[:, None],
                             SHED_ADMISSION, ADMIT).astype(np.int8)
        for c in range(C):
            pushed = {}
            for i in np.flatnonzero(decisions[c] == ADMIT):
                item = (c, int(i))
                evicted = self.queues[c].push(item, float(u[c, i]))
                pushed[id(item)] = int(i)
                if evicted is not None and id(evicted) in pushed:
                    decisions[c, pushed[id(evicted)]] = SHED_QUEUE
        return decisions

    def tick(self) -> None:
        C = self.num_cameras
        p = np.maximum(self.proc_q, self.min_proc)
        rates = np.clip(
            1.0 - np.float32(1.0) / (p * C * np.maximum(self.fps_obs, 1e-9)),
            0.0, 1.0).astype(np.float32)
        for c in range(C):
            n = int(self.cdf_len[c])
            r = np.float32(rates[c])
            if n == 0 or r <= 0.0:
                self.threshold[c] = -np.inf
                continue
            v = np.sort(self.cdf_buf[c, :n])
            # float32 quantile-index arithmetic — the lane semantics
            idx = int(np.ceil(np.minimum(r, np.float32(1.0))
                              * np.float32(n))) - 1
            idx = max(0, min(idx, n - 1))
            self.threshold[c] = next_above(v[idx])
        cap = np.maximum((self.budget / p + 1e-9).astype(np.int32) - 1, 1)
        self.queue_cap = cap.astype(np.int32)
        for c, q in enumerate(self.queues):
            q.resize(min(int(cap[c]), self.queue_capacity))

    def step(self, utilities: np.ndarray) -> np.ndarray:
        d = self.admit(utilities)
        self.tick()
        return d


def _trace(C: int, T: int, steps: int, rng):
    """A seeded utility trace + backend-latency feed. Latencies scale
    with the camera count so the shared backend's target drop rate
    (Eq. 19: r = 1 - 1/(p*C*fps)) sweeps the paper's operating regime
    (~0-50%) at every C, rather than the degenerate shed-everything
    corner."""
    us = rng.uniform(0, 1, (steps, C, T)).astype(np.float32)
    lats = rng.uniform(0.7, 2.0, steps) / (C * 10.0)
    return us, lats


def _mk_session(C: int, serve: str, hist, *, cdf_window=4096):
    # exact_tick: this bench's contract is bit-parity with the
    # seed-style host loop's exact sort quantile
    return open_session(
        Query.single("red", latency_bound=1.0, fps=10.0), num_cameras=C,
        train_utilities=hist, queue_size=8, queue_capacity=64,
        cdf_window=cdf_window, serve=serve, exact_tick=True)


def _parity_and_time(C: int, T: int, steps: int, reps: int, rng):
    # enough history to fill the 4096-entry CDF windows: the steady
    # serving state, where every tick pays the full quantile
    hist = rng.uniform(0, 1, 4096 + 512).astype(np.float32)
    us, lats = _trace(C, T, steps, rng)

    ref = HostLoopShedder(C)
    ref.seed_cdf(hist)
    sh = _mk_session(C, "host", hist)
    sd = _mk_session(C, "device", hist)

    parity = True
    for s in range(steps):
        for obj in (ref, sh, sd):
            obj.report_backend_latency(float(lats[s]))
        d_ref = ref.step(us[s])
        r_h = sh.step(utilities=us[s], tick=True)
        r_d = sd.step(utilities=us[s], tick=True)
        parity &= bool(np.array_equal(d_ref, r_h.decisions))
        parity &= bool(np.array_equal(d_ref, r_d.decisions))
        parity &= bool(np.array_equal(ref.threshold,
                                      np.asarray(sh.state.threshold)))
        parity &= bool(np.array_equal(ref.threshold,
                                      np.asarray(sd.state.threshold)))

    # timing: steady-state repetition of one admit+tick step
    u0 = us[0]
    t_ref = median_ms(lambda: ref.step(u0), n=reps)
    t_host = median_ms(lambda: sh.step(utilities=u0, tick=True), n=reps)
    sd.step(utilities=u0, tick=True)      # warm the jit
    t_dev = median_ms(lambda: sd.step(utilities=u0, tick=True), n=reps)
    return {
        "cameras": C,
        "batch_frames": T,
        "host_loop_ms": t_ref,
        "fused_ms": t_host,
        "fused_device_ms": t_dev,
        "speedup": t_ref / t_host,
        "parity": parity,
    }


def _tick_cost(windows, reps, rng):
    """Control-tick cost vs cdf_window at C=8 (full windows)."""
    rows = {}
    for W in windows:
        hist = rng.uniform(0, 1, W).astype(np.float32)
        sh = _mk_session(8, "host", hist, cdf_window=W)
        sd = _mk_session(8, "device", hist, cdf_window=W)
        for s in (sh, sd):
            s.report_backend_latency(0.2)
        sd.tick()                          # warm the jit
        rows[f"W{W}"] = {
            "fused_ms": median_ms(sh.tick, n=reps),
            "fused_device_ms": median_ms(sd.tick, n=reps),
        }
    return rows


def run(quick=True):
    rng = np.random.default_rng(BENCH_SEED)
    T = 64
    steps = 6 if quick else 20
    reps = 9 if quick else 30
    rows = []
    with Timer() as t:
        for C in (1, 8, 32):
            rows.append(_parity_and_time(C, T, steps, reps, rng))
        ticks = _tick_cost((1024, 4096) if quick else (1024, 4096, 16384),
                           reps, rng)
    if not all(r["parity"] for r in rows):
        bad = [r["cameras"] for r in rows if not r["parity"]]
        raise AssertionError(
            f"fused step() diverged bitwise from the host-loop reference "
            f"at C={bad}")
    by_c = {f"C{r['cameras']}": {k: r[k] for k in
                                 ("host_loop_ms", "fused_ms",
                                  "fused_device_ms", "speedup")}
            for r in rows}
    c32 = next(r for r in rows if r["cameras"] == 32)
    return {
        "us_per_call": c32["fused_ms"] * 1e3,
        "derived": {
            "parity": all(r["parity"] for r in rows),
            "speedup_c8": next(r for r in rows if r["cameras"] == 8)["speedup"],
            "speedup_c32": c32["speedup"],
            **by_c,
            "tick_cost": ticks,
        },
        "rows": rows,
    }


if __name__ == "__main__":
    import json
    print(json.dumps(run(), indent=2))
