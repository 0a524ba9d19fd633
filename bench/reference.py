"""Plain reference of the shedder's semantics, kept apart from the code
under test: it imports nothing of the program.

Two parts, as the paper (arXiv:2307.02409, Sec. IV) describes them:

``ingest``
    Per camera and frame: RGB -> HSV (OpenCV ranges: hue [0, 180),
    saturation and value [0, 256)); a per-pixel exponential background
    on the value channel, compensated by a global illumination gain
    that is the previous frame's mean value over the mean background;
    foreground = |value / gain - background| above a threshold; for each
    query colour the (saturation, value) histogram of the foreground
    pixels whose hue lies in the colour's ranges, normalised to
    fractions (the PF matrix); utility = PF dotted with the colour's
    trained matrix over its normaliser, composed over colours by max
    (OR) or min (AND). Written once over an array module ``xp`` and a
    dtype, so that it runs in NumPy, or jitted with ``jax.numpy`` on a
    chip, and in a lower precision for the control.

``Control``
    Admission and queues, per camera: a sliding window of the last
    ``W`` utilities approximates the utility CDF; a frame whose utility
    lies below the camera's threshold is shed; admitted frames enter a
    bounded queue that keeps the highest (utility, arrival) entries;
    the transmitter pops the best frame over all cameras (utility, then
    camera, then arrival). A control tick sets each threshold at the
    target drop rate's quantile of the window, counted in ``bins``
    buckets over ``quantile_range`` (the threshold is the upper edge of
    the bucket that holds the rank), and each queue's size from the
    backend latency estimate (Eq. 16-20). Everything is float32, as the
    configuration states.
"""
from __future__ import annotations

from collections import deque
from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

ADMIT, SHED_ADMISSION, SHED_QUEUE = 0, 1, 2
F32 = np.float32
TINY = np.finfo(np.float32).tiny


# ---------------------------------------------------------------------------
# Ingest: features and utility
# ---------------------------------------------------------------------------

def hsv(rgb, xp, dtype):
    """(..., 3) RGB in [0, 255] -> h, s, v in OpenCV ranges."""
    rgb = rgb.astype(dtype)
    r, g, b = rgb[..., 0], rgb[..., 1], rgb[..., 2]
    v = xp.maximum(xp.maximum(r, g), b)
    c = v - xp.minimum(xp.minimum(r, g), b)
    one = xp.ones_like(c)
    s = xp.where(v > 0, c / xp.where(v > 0, v, one) * 255, 0 * c)
    cc = xp.where(c > 0, c, one)
    sector = xp.where(v == r, (g - b) / cc,
                      xp.where(v == g, (b - r) / cc + 2, (r - g) / cc + 4))
    sector = xp.where(sector < 0, sector + 6, sector)
    h = xp.where(c > 0, sector * 30, 0 * c)
    return h, s, v


def utilities(frames, bg, gain, seeded: bool, cfg: dict, m_pos, norm, xp,
              dtype=np.float32):
    """Score a (C, T, H, W, 3) RGB batch against carried state.

    ``bg``: (C, H*W) background, ``gain``: (C,), ``seeded``: whether
    they hold history (otherwise the first frame seeds the background).
    ``m_pos``: (colours, bins_s * bins_v), ``norm``: (colours,).
    Returns (utility (C, T), bg, gain, step (C, T)): ``step`` is how far
    one pixel can move the frame's utility, the largest trained weight
    of a colour over its normaliser and its count of pixels."""
    C, T = frames.shape[:2]
    n = frames.shape[2] * frames.shape[3]
    bs, bv = cfg["bins"]
    lo_g, hi_g = cfg["gain_range"]
    alpha, thr = cfg["background_alpha"], cfg["fg_threshold"]
    bins = xp.arange(bs * bv)
    m_pos = m_pos.astype(dtype)
    norm = xp.maximum(norm.astype(dtype), 1e-9)
    top = m_pos.max(axis=1) / norm
    out, steps = [], []
    for t in range(T):
        h, s, v = hsv(frames[:, t].reshape(C, n, 3), xp, dtype)
        if t == 0 and not seeded:
            bg = v
        g = xp.clip(gain, lo_g, hi_g).astype(dtype)[:, None]
        comp = v / g
        fg = xp.abs(comp - bg) > thr
        new_gain = xp.clip(v.sum(axis=1) / xp.maximum(bg.sum(axis=1), 1e-6),
                           lo_g, hi_g).astype(dtype)
        bg = ((1 - alpha) * bg + alpha * comp).astype(dtype)
        gain = new_gain
        sb = xp.clip((s * (bs / 256.0)).astype(np.int32), 0, bs - 1)
        vb = xp.clip((v * (bv / 256.0)).astype(np.int32), 0, bv - 1)
        joint = sb * bv + vb
        onehot = joint[..., None] == bins                  # (C, n, bins)
        per_colour, per_step = [], []
        for k, ranges in enumerate(cfg["hue_ranges"]):
            in_hue = xp.zeros(h.shape, bool)
            for lo, hi in ranges:
                in_hue = in_hue | ((h >= lo) & (h < hi))
            w = in_hue & fg
            counts = (onehot & w[..., None]).sum(axis=1).astype(dtype)
            total = xp.maximum(w.sum(axis=1).astype(dtype), 1)
            pf = counts / total[:, None]
            per_colour.append((pf * m_pos[k]).sum(axis=1) / norm[k])
            per_step.append(top[k] / total)
        u = xp.stack(per_colour, axis=0)
        out.append(u.min(axis=0) if cfg["query"]["op"] == "and"
                   else u.max(axis=0))
        steps.append(xp.stack(per_step, axis=0).max(axis=0))
    return xp.stack(out, axis=1), bg, gain, xp.stack(steps, axis=1)


def pf_matrices(frames, cfg: dict, xp=np, dtype=np.float32):
    """(F, H, W, 3) consecutive frames of one stream -> (F, colours,
    bins) PF matrices, with the background carried from frame 0."""
    F = frames.shape[0]
    n = frames.shape[1] * frames.shape[2]
    bs, bv = cfg["bins"]
    lo_g, hi_g = cfg["gain_range"]
    alpha, thr = cfg["background_alpha"], cfg["fg_threshold"]
    bins = xp.arange(bs * bv)
    bg, gain, out = None, xp.ones((), dtype), []
    for f in range(F):
        h, s, v = hsv(frames[f].reshape(n, 3), xp, dtype)
        bg = v if bg is None else bg
        comp = v / xp.clip(gain, lo_g, hi_g).astype(dtype)
        fg = xp.abs(comp - bg) > thr
        gain = xp.clip(v.sum() / xp.maximum(bg.sum(), 1e-6), lo_g, hi_g)
        bg = ((1 - alpha) * bg + alpha * comp).astype(dtype)
        sb = xp.clip((s * (bs / 256.0)).astype(np.int32), 0, bs - 1)
        vb = xp.clip((v * (bv / 256.0)).astype(np.int32), 0, bv - 1)
        onehot = (sb * bv + vb)[:, None] == bins
        rows = []
        for ranges in cfg["hue_ranges"]:
            in_hue = xp.zeros(h.shape, bool)
            for lo, hi in ranges:
                in_hue = in_hue | ((h >= lo) & (h < hi))
            w = in_hue & fg
            counts = (onehot & w[:, None]).sum(axis=0).astype(dtype)
            rows.append(counts / xp.maximum(w.sum().astype(dtype), 1))
        out.append(xp.stack(rows))
    return xp.stack(out)


def fit_model(pfs: np.ndarray, labels: np.ndarray):
    """Eq. 12 and the normaliser: per colour, the mean PF of the frames
    labelled positive for it, and the largest training utility.

    pfs: (F, colours, bins); labels: (F, colours) bool."""
    nc = pfs.shape[1]
    m_pos = np.zeros(pfs.shape[1:], np.float32)
    for k in range(nc):
        if labels[:, k].any():
            m_pos[k] = pfs[labels[:, k], k].mean(axis=0)
    raw = (pfs * m_pos[None]).sum(axis=-1)                # (F, colours)
    norm = np.maximum(raw.max(axis=0), 1e-9).astype(np.float32)
    return m_pos, norm


def score(pfs: np.ndarray, m_pos, norm, op: str) -> np.ndarray:
    u = (pfs * m_pos[None]).sum(axis=-1) / np.maximum(norm, 1e-9)
    return (u.min(axis=1) if op == "and" else u.max(axis=1)).astype(F32)


# ---------------------------------------------------------------------------
# Admission, queues, control
# ---------------------------------------------------------------------------

def _flush(u):
    """Utilities as they enter a window or a queue: float32, with
    subnormals and -0.0 read as +0.0 (a chip compares floats with
    subnormals flushed)."""
    u = np.asarray(u, F32)
    return np.where(np.abs(u) < TINY, F32(0.0), u)


class Control:
    """Per-camera admission windows, thresholds and queues (float32)."""

    def __init__(self, cameras: int, cfg: dict, seed_utilities,
                 fps: float, min_proc: float = 1e-6,
                 ewma: Tuple[float, float] = (0.2, 0.6)) -> None:
        self.C = int(cameras)
        self.W = int(cfg["cdf_window"])
        self.K = int(cfg["queue_capacity"])
        self.B = int(cfg["quantile_bins"])
        self.lo, hi = (float(x) for x in cfg["quantile_range"])
        self.width = (hi - self.lo) / self.B
        self.inv_width = self.B / (hi - self.lo)
        self.budget = float(cfg["latency_bound_s"])
        self.min_proc = float(min_proc)
        self.a_down, self.a_up = (F32(a) for a in ewma)
        seed = _flush(np.asarray(seed_utilities).reshape(-1))
        self.window = [deque(seed[-self.W:].tolist(), maxlen=self.W)
                       for _ in range(self.C)]
        self.threshold = np.full(self.C, -np.inf, F32)
        self.cap = np.full(self.C, int(cfg["queue_size"]), np.int64)
        self.queue: List[List[Tuple[float, int, Any]]] = [
            [] for _ in range(self.C)]
        self.next_seq = [0] * self.C
        self.proc = F32(0.0)
        self.proc_seen = False
        self.fps = np.full(self.C, fps, F32)
        self.fps_seen = False

    # -- metric feeds -------------------------------------------------------

    def report_latency(self, x: float) -> None:
        x = F32(max(float(x), self.min_proc))
        if not self.proc_seen:
            self.proc, self.proc_seen = x, True
            return
        a = self.a_up if x > self.proc else self.a_down
        self.proc = F32(self.proc + a * F32(x - self.proc))

    def report_fps(self, total: float) -> None:
        """An aggregate ingress rate, split evenly over the cameras."""
        x = F32(float(total) / self.C)
        if not self.fps_seen:
            self.fps[:] = x
            self.fps_seen = True
            return
        self.fps = (self.fps + self.a_down * (x - self.fps)).astype(F32)

    def rates(self, dtype=F32) -> np.ndarray:
        """Eq. 19: 1 - (supported throughput / C) / fps, in [0, 1],
        computed in ``dtype`` (float32 unless for the control)."""
        one = dtype(1.0)
        p = max(self.proc, F32(self.min_proc)).astype(dtype)
        fps = np.maximum(self.fps, F32(1e-9)).astype(dtype)
        denom = (p * dtype(self.C) * fps).astype(dtype)
        return np.clip(one - one / denom, 0.0, 1.0).astype(dtype).astype(F32)

    def queue_cap(self) -> int:
        """Eq. 20: the largest N with (N + 1) * proc within the bound."""
        p = max(self.proc, F32(self.min_proc))
        return max(int(F32(F32(self.budget) / p) + F32(1e-9)) - 1, 1)

    # -- one serve step ----------------------------------------------------

    def step(self, util, items, tick: bool) -> np.ndarray:
        """Admit a (C, T) batch; returns (C, T) decision codes. With
        ``tick``, re-derive thresholds and queue sizes afterwards."""
        util = _flush(util)
        C, T = util.shape
        dec = np.full((C, T), SHED_ADMISSION, np.int8)
        for c in range(C):
            self.window[c].extend(util[c].tolist())
            entries = list(self.queue[c])
            fresh = {}
            for t in range(T):
                if not util[c, t] < self.threshold[c]:
                    seq = self.next_seq[c]
                    self.next_seq[c] += 1
                    entries.append((float(util[c, t]), seq, items[c][t]))
                    fresh[seq] = t
            keep = self._keep(entries, self.cap[c])
            kept = {e[1] for e in keep}
            for seq, t in fresh.items():
                dec[c, t] = ADMIT if seq in kept else SHED_QUEUE
            self.queue[c] = keep
        if tick:
            self.tick()
        return dec

    def _keep(self, entries, cap: int):
        """The top ``clip(cap, 1, K)`` entries by (utility, arrival)."""
        n = int(np.clip(cap, 1, self.K))
        return sorted(entries, key=lambda e: (e[0], e[1]))[-n:] if n else []

    def tick(self) -> np.ndarray:
        """Thresholds at the Eq. 19 drop rates and queue sizes by Eq. 20;
        returns the rates."""
        r = self.rates()
        for c in range(self.C):
            self.threshold[c] = self._threshold(c, r[c])
        cap = self.queue_cap()
        self.cap[:] = cap
        for c in range(self.C):
            self.queue[c] = self._keep(self.queue[c], cap)
        return r

    def _threshold(self, c: int, r) -> np.float32:
        w = np.asarray(self.window[c], F32)
        n = w.size
        if n == 0 or not r > 0:
            return F32(-np.inf)
        k = int(np.ceil(F32(min(r, F32(1.0))) * F32(n)))
        k = min(max(k, 1), n)
        b = np.floor((w - F32(self.lo)) * F32(self.inv_width))
        b = np.clip(b.astype(np.int32), 0, self.B - 1)
        cum = np.cumsum(np.bincount(b, minlength=self.B))
        at = min(int((cum < k).sum()), self.B - 1)
        return F32(F32(self.lo) + F32(at + 1) * F32(self.width))

    # -- transmission -------------------------------------------------------

    def pop(self, k: int) -> List[Any]:
        """The ``k`` best queued frames over all cameras, best first."""
        pool = [(-e[0], c, e[1], e[2]) for c in range(self.C)
                for e in self.queue[c]]
        pool.sort(key=lambda x: x[:3])
        out = pool[:k]
        gone = {(c, s) for _, c, s, _ in out}
        for c in range(self.C):
            self.queue[c] = [e for e in self.queue[c] if (c, e[1]) not in gone]
        return [x[3] for x in out]

    def queue_state(self) -> List[List[Tuple[float, Any]]]:
        """Per camera, the queued (utility, item) pairs, best first."""
        return [[(e[0], e[2]) for e in sorted(q, key=lambda e: (-e[0], e[1]))]
                for q in self.queue]


# ---------------------------------------------------------------------------
# Quality of result (Eq. 2-3)
# ---------------------------------------------------------------------------

def qor(frame_objects: Sequence[Sequence[int]], kept: Sequence[bool]) -> float:
    """Mean over target objects of the share of their frames kept; 1.0
    when no target object appears."""
    total: Dict[int, int] = {}
    sent: Dict[int, int] = {}
    for objs, k in zip(frame_objects, kept):
        for o in objs:
            total[o] = total.get(o, 0) + 1
            sent[o] = sent.get(o, 0) + bool(k)
    if not total:
        return 1.0
    return float(np.mean([sent[o] / total[o] for o in total]))


__all__ = ["ADMIT", "SHED_ADMISSION", "SHED_QUEUE", "Control", "fit_model",
           "hsv", "pf_matrices", "qor", "score", "utilities"]
