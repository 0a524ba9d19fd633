"""Target-drop-rate -> utility-threshold mapping (paper §IV-C, Eq. 16–17).

A sliding window of recent frame utilities approximates the utility CDF;
the threshold for target drop rate r is the smallest utility u_th with
CDF(u_th) >= r. The window is seeded from the training set and updated
online so the mapping tracks content drift.

Three forms of the same Eq. 17:

``threshold_from_sorted``
    The scalar definition on one sorted array (float64 Python index
    math) — ``UtilityCDF`` and the single-camera ``LoadShedder`` use it.

``thresholds_from_lanes_dev``
    The camera-array form on ``(C, W)`` ring-buffer lanes: ONE batched
    masked sort + per-row quantile gather, pure jnp (traced into the
    session's control-plane programs). It computes the quantile index
    in *float32* (``ceil(f32(r) * f32(n))``); this can differ from the
    scalar float64 path by one rank only when ``r * n`` rounds across
    an integer in float32 — astronomically rare and bounded by one
    sample.

``thresholds_from_counts_dev``
    The O(bins) form on an incrementally-maintained ``(C, bins)``
    bucket-count histogram of the same window (the session carries the
    counts as checkpointed state and updates them with push/evict
    deltas inside the serve step). A tick is then one ``(C, bins)``
    cumsum + rank compare instead of a ``(C, W)`` sort. The returned
    threshold is the *upper edge* of the bucket holding the rank-k
    order statistic, so it always satisfies ``th >= exact_nextafter_th``
    (never sheds less than Eq. 17 asks) and, for utilities inside the
    configured ``[lo, hi)`` range, drifts by at most one bucket width.
    Out-of-range utilities clip into the edge buckets and only coarsen
    resolution there.
"""
from __future__ import annotations

from collections import deque
from typing import Iterable, Optional

import jax.numpy as jnp
import numpy as np


def next_above(v, xp=np):
    """The Eq. 17 threshold just above quantile value ``v``:
    ``nextafter(v, +inf)``, except that above a zero float32 ``v`` it is
    the smallest *normal* float32. XLA flushes subnormals to zero when
    it compares floats (CPU and TPU alike), so a subnormal threshold
    would admit a 0.0 utility on the device and shed it in NumPy. The
    session's float32 lanes take utilities flushed of subnormals
    (``shed_queue.flush_subnormal``), so with this threshold the device
    and the scalar references shed exactly the utilities ``<= v``.
    Float64 values (the NumPy-only :class:`UtilityCDF`) keep plain
    ``nextafter``."""
    v = xp.asarray(v)
    up = xp.nextafter(v, xp.asarray(np.inf, v.dtype))
    if v.dtype != np.float32:
        return up
    return xp.where(v == 0, xp.float32(np.finfo(np.float32).tiny), up)


def threshold_from_sorted(v: np.ndarray, r: float) -> float:
    """Eq. 17 on a sorted utility array: min u_th with CDF(u_th) >= r.

    The single definition of the quantile-index + nextafter formula —
    ``UtilityCDF`` (scalar, float64) and the session's per-camera lanes
    (float32 rows) both follow it, so they cannot drift apart. The
    threshold is the next representable value *in the array's dtype*
    above the r-quantile (:func:`next_above`), dropping everything <= it;
    r <= 0 maps to -inf (shed nothing).
    """
    if len(v) == 0 or r <= 0.0:
        return float(-np.inf)
    idx = int(np.ceil(min(r, 1.0) * len(v))) - 1
    idx = max(0, min(idx, len(v) - 1))
    return float(next_above(v[idx]))


def thresholds_from_lanes_dev(cdf_buf, cdf_len, rates):
    """Batched Eq. 17 over camera lanes — ONE (C, W) device sort.

    cdf_buf: (C, W) float32 ring buffers (valid entries occupy slots
    [0, cdf_len) — the ring writes 0..W-1 before wrapping, and once
    wrapped every slot is live). cdf_len: (C,) int32. rates: (C,)
    float32 target drop rates. Returns (C,) float32 thresholds
    (-inf for empty windows or r <= 0).
    """
    C, W = cdf_buf.shape
    n = cdf_len.astype(jnp.int32)
    live = jnp.arange(W, dtype=jnp.int32)[None, :] < n[:, None]
    v = jnp.sort(jnp.where(live, cdf_buf, jnp.inf), axis=-1)
    r = jnp.asarray(rates, jnp.float32)
    idx = (jnp.ceil(jnp.minimum(r, 1.0) * n.astype(jnp.float32))
           .astype(jnp.int32) - 1)
    idx = jnp.clip(idx, 0, jnp.maximum(n - 1, 0))
    th = next_above(jnp.take_along_axis(v, idx[:, None], axis=-1)[:, 0],
                    jnp)
    return jnp.where((n == 0) | (r <= 0.0), -jnp.inf, th).astype(jnp.float32)


def bucket_index_dev(u, lo: float, inv_width: float, bins: int):
    """Map utilities to bucket indices: ``clip(floor((u - lo) * B/(hi-lo)),
    0, B-1)``, in float32 arithmetic."""
    b = jnp.floor((u - jnp.float32(lo)) * jnp.float32(inv_width))
    return jnp.clip(b.astype(jnp.int32), 0, bins - 1)


def thresholds_from_counts_dev(counts, cdf_len, rates, lo: float,
                               width: float):
    """O(bins) Eq. 17 over incremental bucket counts — no (C, W) sort.

    counts: (C, bins) int32 live-entry histogram of the CDF window.
    cdf_len: (C,) int32 live window lengths (== counts.sum(-1)).
    rates: (C,) float32 target drop rates. Returns (C,) float32
    thresholds: the upper edge of the bucket containing the rank-k
    order statistic, where k is exactly the Eq. 17 float32 rank
    (``clip(ceil(min(r,1) * f32(n)), 1, n)`` — the same index the sort
    path gathers). -inf for empty windows or r <= 0.
    """
    C, B = counts.shape
    n = cdf_len.astype(jnp.int32)
    r = jnp.asarray(rates, jnp.float32)
    k = jnp.ceil(jnp.minimum(r, 1.0) * n.astype(jnp.float32)).astype(jnp.int32)
    k = jnp.clip(k, 1, jnp.maximum(n, 1))
    cum = jnp.cumsum(counts, axis=-1)
    b = jnp.minimum((cum < k[:, None]).sum(axis=-1).astype(jnp.int32), B - 1)
    th = jnp.float32(lo) + (b + 1).astype(jnp.float32) * jnp.float32(width)
    return jnp.where((n == 0) | (r <= 0.0), -jnp.inf, th).astype(jnp.float32)


class UtilityCDF:
    def __init__(self, history: Optional[Iterable[float]] = None,
                 window: int = 4096):
        self._buf = deque(maxlen=window)
        if history is not None:
            self.update(history)
        self._sorted: Optional[np.ndarray] = None

    def __len__(self):
        return len(self._buf)

    def update(self, utilities):
        if hasattr(utilities, "__next__"):      # consume generators once
            utilities = list(utilities)
        us = np.atleast_1d(np.asarray(utilities, np.float64)).reshape(-1)
        w = self._buf.maxlen
        if w is not None and us.size > w:     # only the tail can survive
            us = us[-w:]
        self._buf.extend(us.tolist())
        self._sorted = None

    def _view(self) -> np.ndarray:
        if self._sorted is None:
            self._sorted = np.sort(np.asarray(self._buf, np.float64))
        return self._sorted

    def cdf(self, u: float) -> float:
        """Eq. 16: fraction of history with utility <= u."""
        v = self._view()
        if len(v) == 0:
            return 0.0
        return float(np.searchsorted(v, u, side="right")) / len(v)

    def threshold_for_drop_rate(self, r: float) -> float:
        """Eq. 17: min u_th such that CDF(u_th) >= r.

        The shedder drops frames with utility < u_th, so r=0 maps to
        -inf (shed nothing).
        """
        return threshold_from_sorted(self._view(), r)

    def observed_drop_rate(self, u_th: float) -> float:
        """Fraction of history that would be dropped at threshold u_th."""
        v = self._view()
        if len(v) == 0:
            return 0.0
        return float(np.searchsorted(v, u_th, side="left")) / len(v)


__all__ = ["UtilityCDF", "threshold_from_sorted", "next_above",
           "thresholds_from_lanes_dev", "thresholds_from_counts_dev",
           "bucket_index_dev"]
