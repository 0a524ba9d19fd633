"""Scalar references for the session's control plane.

The session runs one jitted control plane (``repro.core.control_plane``).
These loops restate what it must compute in plain Python and NumPy,
one camera and one frame at a time: a heapq ``UtilityQueue`` per
camera, a ring of recent utilities per camera, and an exact sort
quantile (Eq. 17) every tick. Arithmetic is float32 throughout, with
the lanes' float32 quantile index, so a session opened with
``exact_tick=True`` must reproduce their decisions and thresholds bit
for bit.

``HostLoopShedder`` is the single-stage serve loop; ``HostLoopCascade``
adds the semantic cascade's second gate and ring.
"""
from __future__ import annotations

from typing import List, Optional

import numpy as np

from repro.core.session import ADMIT, SHED_ADMISSION, SHED_CASCADE, SHED_QUEUE
from repro.core.shed_queue import UtilityQueue, flush_subnormal
from repro.core.threshold import next_above


def exact_threshold(buf: np.ndarray, n: int, r: np.float32) -> np.float32:
    """Eq. 17 on one camera's ring: sort the ``n`` live entries and take
    the rank ``ceil(r * n)`` value, index arithmetic in float32."""
    if n == 0 or r <= 0.0:
        return np.float32(-np.inf)
    v = np.sort(buf[:n])
    idx = int(np.ceil(np.minimum(r, np.float32(1.0)) * np.float32(n))) - 1
    return next_above(v[max(0, min(idx, n - 1))])


class _Ring:
    """One (C, W) ring of recent scores per camera."""

    def __init__(self, C: int, W: int):
        self.buf = np.zeros((C, W), np.float32)
        self.len = np.zeros((C,), np.int32)
        self.pos = np.zeros((C,), np.int32)

    def push(self, c: int, values) -> None:
        W = self.buf.shape[1]
        for v in np.asarray(values, np.float32).reshape(-1):
            self.buf[c, self.pos[c]] = v
            self.pos[c] = (self.pos[c] + 1) % W
            self.len[c] = min(self.len[c] + 1, W)

    def thresholds(self, rates: np.ndarray) -> np.ndarray:
        return np.array([exact_threshold(self.buf[c], int(self.len[c]),
                                         np.float32(rates[c]))
                         for c in range(len(rates))], np.float32)


class HostLoopShedder:
    """The single-stage serve loop: scalar heapq pushes in ``admit`` and
    a per-camera ``np.sort`` + quantile in ``tick``."""

    def __init__(self, num_cameras: int, *, cdf_window: int = 4096,
                 queue_size: int = 8, queue_capacity: int = 64,
                 fps: float = 10.0, latency_bound: float = 1.0,
                 min_proc: float = 1e-6, ewma_alpha: float = 0.2,
                 ewma_alpha_up: float = 0.6):
        C = self.num_cameras = int(num_cameras)
        self.cdf = _Ring(C, cdf_window)
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

    # -- metric feeds (the session's float32 EWMA) ---------------------------

    def report_backend_latency(self, lat: float,
                               cam: Optional[int] = None) -> None:
        x = np.float32(max(float(lat), self.min_proc))
        q = self.proc_q
        a = np.where(x > q, np.float32(self.ewma_alpha_up),
                     np.float32(self.ewma_alpha))
        new = np.where(self.proc_seen, q + a * (x - q), x)
        upd = (np.ones_like(self.proc_seen) if cam is None
               else np.arange(self.num_cameras) == int(cam))
        self.proc_q = np.where(upd, new, q).astype(np.float32)
        self.proc_seen = self.proc_seen | upd

    def seed_cdf(self, us: np.ndarray) -> None:
        for c in range(self.num_cameras):
            self.cdf.push(c, flush_subnormal(us))

    # -- admit + tick ---------------------------------------------------------

    def _push_queues(self, u: np.ndarray, decisions: np.ndarray) -> None:
        """Push every ADMIT slot in order; a same-batch frame a later push
        evicts flips to SHED_QUEUE."""
        for c in range(u.shape[0]):
            pushed = {}
            for i in np.flatnonzero(decisions[c] == ADMIT):
                item = (c, int(i))
                evicted = self.queues[c].push(item, float(u[c, i]))
                pushed[id(item)] = int(i)
                if evicted is not None and id(evicted) in pushed:
                    decisions[c, pushed[id(evicted)]] = SHED_QUEUE

    def admit(self, utilities: np.ndarray) -> np.ndarray:
        u = flush_subnormal(utilities)
        for c in range(self.num_cameras):
            self.cdf.push(c, u[c])
        decisions = np.where(u < self.threshold[:, None],
                             SHED_ADMISSION, ADMIT).astype(np.int8)
        self._push_queues(u, decisions)
        return decisions

    def rates(self) -> np.ndarray:
        """Eq. 19 target drop rates, in the session's float32 form."""
        C = self.num_cameras
        p = np.maximum(self.proc_q, self.min_proc)
        return np.clip(
            1.0 - np.float32(1.0) / (p * C * np.maximum(self.fps_obs, 1e-9)),
            0.0, 1.0).astype(np.float32)

    def _resize(self) -> None:
        p = np.maximum(self.proc_q, self.min_proc)
        cap = np.maximum((self.budget / p + 1e-9).astype(np.int32) - 1, 1)
        self.queue_cap = cap.astype(np.int32)
        for c, q in enumerate(self.queues):
            q.resize(min(int(cap[c]), self.queue_capacity))

    def tick(self) -> None:
        self.threshold = self.cdf.thresholds(self.rates())
        self._resize()

    def step(self, utilities: np.ndarray) -> np.ndarray:
        d = self.admit(utilities)
        self.tick()
        return d


class HostLoopCascade(HostLoopShedder):
    """The two-stage serve loop: the color gate on stage-1 utilities,
    then the stage-2 gate on the survivors' scores, which also key the
    queues. A tick splits the Eq. 19 rate r into the stage-1 share
    ``r1 = g * r`` and the survivors' share ``(r - r1) / (1 - r1)``."""

    def __init__(self, num_cameras: int, *, gate_fraction: float,
                 s2_window: int, **kw):
        super().__init__(num_cameras, **kw)
        self.gate_fraction = float(gate_fraction)
        self.s2 = _Ring(self.num_cameras, s2_window)
        self.s2_threshold = np.full((self.num_cameras,), -np.inf,
                                    np.float32)

    def admit(self, utilities: np.ndarray,
              s2_scores: np.ndarray) -> np.ndarray:
        u = flush_subnormal(utilities)
        s2 = flush_subnormal(s2_scores)
        pass1 = ~(u < self.threshold[:, None])
        for c in range(self.num_cameras):
            self.cdf.push(c, u[c])
            self.s2.push(c, s2[c][pass1[c]])
        shed2 = pass1 & (s2 < self.s2_threshold[:, None])
        decisions = np.where(
            pass1 & ~shed2, ADMIT,
            np.where(pass1, SHED_CASCADE, SHED_ADMISSION)).astype(np.int8)
        self._push_queues(s2, decisions)
        return decisions

    def tick(self) -> None:
        rates = self.rates()
        r1 = (rates * np.float32(self.gate_fraction)).astype(np.float32)
        r2 = ((rates - r1) / np.maximum(np.float32(1.0) - r1,
                                        np.float32(1e-9))).astype(np.float32)
        self.threshold = self.cdf.thresholds(r1)
        self.s2_threshold = self.s2.thresholds(r2)
        self._resize()
