"""The session's control plane: the state pytree, its traced cores and
the jitted device programs that run them.

There is one implementation, run through XLA on every backend (the TPU,
and XLA CPU on a CPU). ``repro.core.session`` runs these programs for
one device; ``repro.core.fleet`` runs the same cores shard_map'd over a
camera mesh. Both import this module; it imports neither.

``SessionState``
    The per-camera lanes as one pytree of arrays (see its docstring).

Traced cores (plain functions, inlined into every program that calls
them): ``ring_push`` (CDF ring + bucket counts), ``control_core`` (ring
push -> admission -> queue selection -> optional tick), ``tick_core``
(Eq. 18-20), and the semantic cascade's ``cascade_tick_core`` and
``cascade_finish_core``.

Jitted programs (donated state; their names are what device traces
show): ``_serve_step_dev`` (fused ingest + control), ``_flatten_frames``,
``_control_step_dev``, ``_control_masked_dev``, ``_offer_dev``,
``_pop_any_dev``, ``_pop_cam_dev``, ``_pop_topk_dev``,
``_pop_topk_masked_dev``, ``_tick_dev``, ``_cascade_admit_dev``,
``_cascade_finish_dev`` and ``_cascade_tick_dev``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from dataclasses import dataclass
from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import shed_queue as sq
from repro.core.threshold import (
    bucket_index_dev,
    thresholds_from_counts_dev,
    thresholds_from_lanes_dev,
)
from repro.kernels.hsv_features.ops import ingest_core

# admit() decision codes — (C, T) int8 arrays, vectorized per camera
# (offer_batch marks padding slots that carried no frame with -1)
ADMIT = 0
SHED_ADMISSION = 1
SHED_QUEUE = 2
SHED_CASCADE = 3     # passed the color gate, shed by the stage-2 scorer

DECISION_NAMES = {ADMIT: "queued", SHED_ADMISSION: "shed_admission",
                  SHED_QUEUE: "shed_queue", SHED_CASCADE: "shed_cascade"}

# the span of a caller without a metrics registry: one shared context
# for every site, so an unmetered call allocates nothing and reads no
# clock
_NO_SPAN = contextlib.nullcontext()


def no_span(name: str):
    return _NO_SPAN


class TickConfig(NamedTuple):
    """Static quantile-tick configuration, threaded as ONE hashable
    static through the serve-step programs.

    ``exact=True`` re-derives Eq. 17 thresholds with the full ``(C, W)``
    sort (``thresholds_from_lanes_dev``) — bit-identical to the
    pre-bucket behavior, the escape hatch. ``exact=False`` (the default)
    uses the O(bins) cumsum over the incrementally-maintained ``(C,
    bins)`` count histograms, whose threshold is within one bucket width
    above the exact one for in-range utilities. The bucket geometry
    (``lo``/``width``/``inv_width`` for the stage-1 utility range,
    ``s2_*`` for the cascade scorer's softsign range) is baked in here;
    counts are maintained either way, so flipping ``exact`` never
    desyncs checkpointed state.
    """
    exact: bool = False
    lo: float = 0.0
    width: float = 1.0 / 256.0
    inv_width: float = 256.0
    s2_lo: float = -1.0
    s2_width: float = 2.0 / 256.0
    s2_inv_width: float = 128.0


DEFAULT_TICK_CONFIG = TickConfig()


@jax.tree_util.register_dataclass
@dataclass
class SessionState:
    """Per-camera session state — a pytree whose every leaf is an array
    with a leading camera lane, so C cameras are one device dispatch
    and one checkpointable object.

    Camera lanes (row c belongs to camera c):
      * ``bg (C, N)`` / ``gain (C,)`` — the fused ingest kernel's
        carried background state; ``bg_valid ()`` says whether the lanes
        hold real history yet (frame 0 seeds them otherwise).
      * ``cdf_buf (C, W)`` ring buffers of recent utilities with
        ``cdf_len`` / ``cdf_pos`` — the sliding-window utility CDF
        (Eq. 16) per camera; ``cdf_counts (C, B)`` is its bucket-count
        histogram, maintained incrementally with push/evict deltas so a
        control tick is O(B) instead of a (C, W) sort (``TickConfig``).
      * ``threshold (C,)`` — current admission thresholds (Eq. 17).
      * ``proc_q (C,)`` (+ ``proc_seen``) — asymmetric-EWMA backend
        latency estimates, folded on the host and written here by the
        session (no program writes them); ``fps_obs (C,)`` (+
        ``fps_seen``) — observed per-camera ingress rates (Eq. 18–19
        inputs).
      * ``queue_cap (C,)`` — dynamic queue sizes (Eq. 20).
      * ``q_util`` / ``q_seq (C, K)`` + ``q_next_seq (C,)`` — the
        utility-ordered queues as array lanes (``repro.core.shed_queue``
        ordering contract; empty slots are ``(-inf, -1)``). ``K`` is the
        physical bound; the *effective* size is ``queue_cap`` clipped
        to it.
    """
    bg: Any          # (C, N) float32
    gain: Any        # (C,) float32
    bg_valid: Any    # () bool
    cdf_buf: Any     # (C, W) float32
    cdf_len: Any     # (C,) int32
    cdf_pos: Any     # (C,) int32
    cdf_counts: Any  # (C, B) int32 — live-window bucket histogram
    #                  (always equals a recount of cdf_buf[:, :cdf_len])
    threshold: Any   # (C,) float32
    proc_q: Any      # (C,) float32
    proc_seen: Any   # (C,) bool
    fps_obs: Any     # (C,) float32
    fps_seen: Any    # (C,) bool
    queue_cap: Any   # (C,) int32
    q_util: Any      # (C, K) float32
    q_seq: Any       # (C, K) int32
    q_next_seq: Any  # (C,) int32
    active: Any      # (C,) bool — detached lanes are masked out of
    #                  control (threshold forced +inf so they admit
    #                  nothing); all-True is bit-identical to pre-churn
    rate_floor: Any  # (C,) float32 — degraded-mode floor under the
    #                  Eq. 19 target drop rates; 0 = normal regime
    # stage-2 (semantic cascade) lanes — inert unless the session was
    # opened with cascade=; same ring/threshold machinery as the
    # stage-1 CDF, but over the scorer outputs of frames that PASSED
    # the color gate
    s2_buf: Any        # (C, W2) float32 stage-2 score ring
    s2_len: Any        # (C,) int32
    s2_pos: Any        # (C,) int32
    s2_threshold: Any  # (C,) float32 stage-2 shed thresholds
    s2_counts: Any     # (C, B) int32 stage-2 bucket histogram

    @property
    def num_cameras(self) -> int:
        return self.gain.shape[0]

    def as_dict(self) -> Dict[str, np.ndarray]:
        return {f.name: np.asarray(getattr(self, f.name))
                for f in dataclasses.fields(self)}

    @classmethod
    def fresh(cls, num_cameras: int, npix: int = 0, *,
              cdf_window: int = 4096, fps: float = 10.0,
              queue_size: int = 8, queue_capacity: int = 64,
              s2_window: int = 64,
              quantile_bins: int = 256) -> "SessionState":
        C = int(num_cameras)
        K = max(int(queue_capacity), int(queue_size), 1)
        B = int(quantile_bins)
        q_util, q_seq, q_next = sq.make_lanes(C, K)
        return cls(
            bg=jnp.zeros((C, npix), jnp.float32),
            gain=jnp.ones((C,), jnp.float32),
            bg_valid=jnp.asarray(False),
            cdf_buf=jnp.zeros((C, cdf_window), jnp.float32),
            cdf_len=jnp.zeros((C,), jnp.int32),
            cdf_pos=jnp.zeros((C,), jnp.int32),
            cdf_counts=jnp.zeros((C, B), jnp.int32),
            threshold=jnp.full((C,), -jnp.inf, jnp.float32),
            proc_q=jnp.zeros((C,), jnp.float32),
            proc_seen=jnp.zeros((C,), bool),
            fps_obs=jnp.full((C,), float(fps), jnp.float32),
            fps_seen=jnp.zeros((C,), bool),
            queue_cap=jnp.full((C,), int(queue_size), jnp.int32),
            q_util=q_util, q_seq=q_seq, q_next_seq=q_next,
            active=jnp.ones((C,), bool),
            rate_floor=jnp.zeros((C,), jnp.float32),
            s2_buf=jnp.zeros((C, int(s2_window)), jnp.float32),
            s2_len=jnp.zeros((C,), jnp.int32),
            s2_pos=jnp.zeros((C,), jnp.int32),
            s2_threshold=jnp.full((C,), -jnp.inf, jnp.float32),
            s2_counts=jnp.zeros((C, B), jnp.int32),
        )


# ---------------------------------------------------------------------------
# Traced cores
# ---------------------------------------------------------------------------

def ring_push(buf, pos, ln, counts, us, mask, lo: float, inv_width: float):
    """Append a (C, T) utility batch into the per-camera ring buffers;
    ``mask`` marks real entries (None = all). The (C, B) bucket
    ``counts`` are maintained incrementally (ring-wrap aware: slot s is
    pre-push live iff s < len, regardless of where ``pos`` wrapped), so
    they always equal a recount of the live window. Utilities are stored
    flushed of subnormals (``shed_queue.flush_subnormal``)."""
    C, W = buf.shape
    B = counts.shape[1]
    us = sq.flush_subnormal(us, jnp)
    rows = jnp.arange(C)[:, None]
    if mask is None:
        if us.shape[1] >= W:                   # only the tail can survive
            us = us[:, -W:]
        T = us.shape[1]
        idx = (pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]) % W
        old = jnp.take_along_axis(buf, idx, axis=1)
        evict = idx < ln[:, None]
        buf = buf.at[rows, idx].set(us)
        counts = counts.at[rows, bucket_index_dev(us, lo, inv_width, B)].add(
            jnp.int32(1))
        cnt = jnp.full((C,), T, jnp.int32)
    else:
        kk = jnp.cumsum(mask.astype(jnp.int32), axis=1)
        idx = jnp.where(mask, (pos[:, None] + kk - 1) % W, W)
        old = jnp.take_along_axis(buf, jnp.minimum(idx, W - 1), axis=1)
        evict = mask & (idx < ln[:, None])
        buf = buf.at[rows, idx].set(us, mode="drop")
        counts = counts.at[rows, bucket_index_dev(us, lo, inv_width, B)].add(
            mask.astype(jnp.int32))
        cnt = kk[:, -1]
    counts = counts.at[rows, bucket_index_dev(old, lo, inv_width, B)].add(
        -evict.astype(jnp.int32))
    pos = ((pos + cnt) % W).astype(jnp.int32)
    ln = jnp.minimum(ln + cnt, W).astype(jnp.int32)
    return buf, pos, ln, counts


def tick_core(state: SessionState, min_proc: float, budget: float,
              num_total: Optional[int] = None,
              tick_cfg: Optional[TickConfig] = None):
    """Eq. 18–20 re-derivation: target rates from the metric lanes,
    thresholds via the O(bins) bucket cumsum (or ONE batched (C, W)
    sort under ``tick_cfg.exact``), queue caps + resize.

    ``num_total`` is the number of cameras sharing the backend — Eq. 19's
    service-time multiplier. It defaults to the local lane count; a
    camera-sharded fleet step (repro.core.fleet) passes the GLOBAL count
    so every shard derives the same rates as the unsharded program.
    """
    if tick_cfg is None:
        tick_cfg = DEFAULT_TICK_CONFIG
    C = num_total if num_total is not None else state.threshold.shape[0]
    p = jnp.maximum(state.proc_q, min_proc)
    # single-division form of Eq. 19's 1 - (ST/C)/fps: bit-stable under
    # XLA (the two-division chain gets algebraically rewritten by the
    # compiler, which would break bit parity with the references)
    rates = jnp.clip(
        1.0 - 1.0 / (p * C * jnp.maximum(state.fps_obs, 1e-9)),
        0.0, 1.0).astype(jnp.float32)
    # degraded-mode floor + churn mask: exact elementwise ops AFTER the
    # Eq. 19 expression, so floor=0 / all-active stays bit-identical
    rates = jnp.maximum(rates, state.rate_floor).astype(jnp.float32)
    rates = jnp.where(state.active, rates, jnp.float32(0.0))
    if tick_cfg.exact:
        threshold = thresholds_from_lanes_dev(state.cdf_buf, state.cdf_len,
                                              rates)
    else:
        threshold = thresholds_from_counts_dev(
            state.cdf_counts, state.cdf_len, rates, tick_cfg.lo,
            tick_cfg.width)
    threshold = jnp.where(state.active, threshold, jnp.float32(jnp.inf))
    cap = jnp.maximum((budget / p + 1e-9).astype(jnp.int32) - 1, 1)
    q_util, q_seq, resize_ev = sq.resize_dev(state.q_util, state.q_seq, cap)
    state = dataclasses.replace(
        state, threshold=threshold, queue_cap=cap.astype(jnp.int32),
        q_util=q_util, q_seq=q_seq)
    return state, rates, resize_ev


def control_core(state: SessionState, util, present, *,
                 update_cdf: bool, do_tick: bool,
                 min_proc: float, budget: float,
                 num_total: Optional[int] = None,
                 tick_cfg: Optional[TickConfig] = None):
    """CDF push -> admission -> queue selection -> (optional) tick, all
    traced. Returns (state', outputs-dict of compact arrays)."""
    if tick_cfg is None:
        tick_cfg = DEFAULT_TICK_CONFIG
    util = util.astype(jnp.float32)
    C, T = util.shape
    rows = jnp.arange(C)[:, None]
    cdf_buf, cdf_pos, cdf_len = state.cdf_buf, state.cdf_pos, state.cdf_len
    cdf_counts = state.cdf_counts
    if update_cdf:
        cdf_buf, cdf_pos, cdf_len, cdf_counts = ring_push(
            cdf_buf, cdf_pos, cdf_len, cdf_counts, util, present,
            tick_cfg.lo, tick_cfg.inv_width)
    shed = util < state.threshold[:, None]
    admit = ~shed if present is None else (present & ~shed)
    decisions = jnp.where(admit, ADMIT, SHED_ADMISSION).astype(jnp.int8)
    if present is not None:
        decisions = jnp.where(present, decisions, jnp.int8(-1))
    q_util, q_seq, q_next, pushed_seq, ev_s, ev_b = sq.push_batch_dev(
        state.q_util, state.q_seq, state.q_next_seq, util, admit,
        state.queue_cap)
    # retroactive SHED_QUEUE flips for this batch's evicted frames: a
    # scatter-max (codes are 0 <= 1 <= 2, dummy writes use -1 = no-op)
    flip = ev_b >= 0
    decisions = decisions.at[rows, jnp.where(flip, ev_b, 0)].max(
        jnp.where(flip, jnp.int8(SHED_QUEUE), jnp.int8(-1)))
    state = dataclasses.replace(
        state, cdf_buf=cdf_buf, cdf_pos=cdf_pos, cdf_len=cdf_len,
        cdf_counts=cdf_counts, q_util=q_util, q_seq=q_seq, q_next_seq=q_next)
    out = {
        "decisions": decisions,
        "pushed_seq": pushed_seq,
        "evicted_resident": jnp.where((ev_b < 0) & (ev_s >= 0), ev_s, -1),
        "push_evictions": (ev_s >= 0).sum(axis=-1).astype(jnp.int32),
        "rates": jnp.zeros((C,), jnp.float32),
        "resize_evicted": jnp.full_like(state.q_seq, -1),
    }
    if do_tick:
        state, rates, resize_ev = tick_core(state, min_proc, budget,
                                            num_total, tick_cfg)
        out["rates"] = rates
        out["resize_evicted"] = resize_ev
    return state, out


# ---------------------------------------------------------------------------
# Semantic-cascade cores, split around the host scorer call: phase A
# (stage-1 CDF push + color gate) -> scorer on the survivors -> phase B
# (stage-2 ring push + gate + queue insertion + optional cascade tick).
# The single-stage cores above are untouched, so cascade-off sessions
# stay bit-identical to the pre-cascade pipeline.
# ---------------------------------------------------------------------------

def cascade_rates(rates, gate_fraction):
    """Split the Eq. 19 combined target drop rate r into the stage-1
    share r1 = g*r and the stage-2 CONDITIONAL share r2 = (r-r1)/(1-r1)
    (of the survivors), so r1 + (1-r1)*r2 == r exactly — the combined
    realized rate tracks r and the degraded floor (already folded into
    ``rates``) bounds the combined rate."""
    r1 = (rates * jnp.float32(gate_fraction)).astype(jnp.float32)
    r2 = ((rates - r1)
          / jnp.maximum(1.0 - r1, jnp.float32(1e-9))).astype(jnp.float32)
    return r1, r2


def cascade_tick_core(state: SessionState, min_proc: float, budget: float,
                      gate_fraction: float, num_total: Optional[int] = None,
                      tick_cfg: Optional[TickConfig] = None):
    """Two-threshold tick: the combined Eq. 18-20 rate (floor + churn
    mask applied first, as in ``tick_core``) is split across the
    stages; each stage's threshold comes from ITS ring at ITS share —
    both through the same O(bins) bucket machinery (the s2 geometry
    covers the scorer's softsign range)."""
    if tick_cfg is None:
        tick_cfg = DEFAULT_TICK_CONFIG
    C = num_total if num_total is not None else state.threshold.shape[0]
    p = jnp.maximum(state.proc_q, min_proc)
    rates = jnp.clip(
        1.0 - 1.0 / (p * C * jnp.maximum(state.fps_obs, 1e-9)),
        0.0, 1.0).astype(jnp.float32)
    rates = jnp.maximum(rates, state.rate_floor).astype(jnp.float32)
    rates = jnp.where(state.active, rates, jnp.float32(0.0))
    r1, r2 = cascade_rates(rates, gate_fraction)
    if tick_cfg.exact:
        threshold = thresholds_from_lanes_dev(state.cdf_buf, state.cdf_len,
                                              r1)
        s2_threshold = thresholds_from_lanes_dev(state.s2_buf, state.s2_len,
                                                 r2)
    else:
        threshold = thresholds_from_counts_dev(
            state.cdf_counts, state.cdf_len, r1, tick_cfg.lo, tick_cfg.width)
        s2_threshold = thresholds_from_counts_dev(
            state.s2_counts, state.s2_len, r2, tick_cfg.s2_lo,
            tick_cfg.s2_width)
    threshold = jnp.where(state.active, threshold, jnp.float32(jnp.inf))
    s2_threshold = jnp.where(state.active, s2_threshold,
                             jnp.float32(jnp.inf))
    cap = jnp.maximum((budget / p + 1e-9).astype(jnp.int32) - 1, 1)
    q_util, q_seq, resize_ev = sq.resize_dev(state.q_util, state.q_seq, cap)
    state = dataclasses.replace(
        state, threshold=threshold, s2_threshold=s2_threshold,
        queue_cap=cap.astype(jnp.int32), q_util=q_util, q_seq=q_seq)
    return state, rates, resize_ev


def cascade_finish_core(state: SessionState, s2, present, pass1, *,
                        do_tick: bool, min_proc: float, budget: float,
                        gate_fraction: float,
                        num_total: Optional[int] = None,
                        tick_cfg: Optional[TickConfig] = None):
    """Cascade phase B: stage-2 ring push (survivors only) -> stage-2
    gate -> queue insertion keyed by the SEMANTIC score -> (optional)
    two-threshold tick."""
    if tick_cfg is None:
        tick_cfg = DEFAULT_TICK_CONFIG
    s2 = s2.astype(jnp.float32)
    C, T = s2.shape
    rows = jnp.arange(C)[:, None]
    s2_buf, s2_pos, s2_len, s2_counts = ring_push(
        state.s2_buf, state.s2_pos, state.s2_len, state.s2_counts, s2, pass1,
        tick_cfg.s2_lo, tick_cfg.s2_inv_width)
    shed2 = pass1 & (s2 < state.s2_threshold[:, None])
    admit = pass1 & ~shed2
    decisions = jnp.where(
        admit, ADMIT,
        jnp.where(pass1, SHED_CASCADE, SHED_ADMISSION)).astype(jnp.int8)
    decisions = jnp.where(present, decisions, jnp.int8(-1))
    q_util, q_seq, q_next, pushed_seq, ev_s, ev_b = sq.push_batch_dev(
        state.q_util, state.q_seq, state.q_next_seq, s2, admit,
        state.queue_cap)
    # retro SHED_QUEUE flips: evicted slots were ADMIT (0) and every
    # code is <= 3, so a scatter-max with -1 dummies is exact
    flip = ev_b >= 0
    decisions = decisions.at[rows, jnp.where(flip, ev_b, 0)].max(
        jnp.where(flip, jnp.int8(SHED_QUEUE), jnp.int8(-1)))
    state = dataclasses.replace(
        state, s2_buf=s2_buf, s2_pos=s2_pos, s2_len=s2_len,
        s2_counts=s2_counts, q_util=q_util, q_seq=q_seq, q_next_seq=q_next)
    out = {
        "decisions": decisions,
        "pushed_seq": pushed_seq,
        "evicted_resident": jnp.where((ev_b < 0) & (ev_s >= 0), ev_s, -1),
        "push_evictions": (ev_s >= 0).sum(axis=-1).astype(jnp.int32),
        "rates": jnp.zeros((C,), jnp.float32),
        "resize_evicted": jnp.full_like(state.q_seq, -1),
    }
    if do_tick:
        state, rates, resize_ev = cascade_tick_core(
            state, min_proc, budget, gate_fraction, num_total, tick_cfg)
        out["rates"] = rates
        out["resize_evicted"] = resize_ev
    return state, out


# ---------------------------------------------------------------------------
# Jitted programs
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("update_cdf", "tick_cfg"),
                   donate_argnames=("state",))
def _cascade_admit_dev(state, util, present, *, update_cdf,
                       tick_cfg=DEFAULT_TICK_CONFIG):
    """Cascade phase A: stage-1 CDF push + color gate. Returns (state',
    pass1 (C, T) bool — the frames the scorer sees)."""
    util = util.astype(jnp.float32)
    cdf_buf, cdf_pos, cdf_len = state.cdf_buf, state.cdf_pos, state.cdf_len
    cdf_counts = state.cdf_counts
    if update_cdf:
        cdf_buf, cdf_pos, cdf_len, cdf_counts = ring_push(
            cdf_buf, cdf_pos, cdf_len, cdf_counts, util, present,
            tick_cfg.lo, tick_cfg.inv_width)
    pass1 = present & ~(util < state.threshold[:, None])
    state = dataclasses.replace(state, cdf_buf=cdf_buf, cdf_pos=cdf_pos,
                                cdf_len=cdf_len, cdf_counts=cdf_counts)
    return state, pass1


@functools.partial(
    jax.jit,
    static_argnames=("do_tick", "min_proc", "budget", "gate_fraction",
                     "num_total", "tick_cfg"),
    donate_argnames=("state",))
def _cascade_finish_dev(state, s2, present, pass1, *, do_tick, min_proc,
                        budget, gate_fraction, num_total=None,
                        tick_cfg=DEFAULT_TICK_CONFIG):
    return cascade_finish_core(
        state, s2, present, pass1, do_tick=do_tick, min_proc=min_proc,
        budget=budget, gate_fraction=gate_fraction, num_total=num_total,
        tick_cfg=tick_cfg)


@functools.partial(
    jax.jit,
    static_argnames=("min_proc", "budget", "gate_fraction", "num_total",
                     "tick_cfg"),
    donate_argnames=("state",))
def _cascade_tick_dev(state, *, min_proc, budget, gate_fraction,
                      num_total=None, tick_cfg=DEFAULT_TICK_CONFIG):
    return cascade_tick_core(state, min_proc, budget, gate_fraction,
                             num_total, tick_cfg)


@functools.partial(
    jax.jit,
    static_argnames=("update_cdf", "do_tick", "min_proc", "budget",
                     "num_total", "tick_cfg"),
    donate_argnames=("state",))
def _control_step_dev(state, util, *, update_cdf, do_tick, min_proc, budget,
                      num_total=None, tick_cfg=DEFAULT_TICK_CONFIG):
    return control_core(state, util, None, update_cdf=update_cdf,
                        do_tick=do_tick, min_proc=min_proc, budget=budget,
                        num_total=num_total, tick_cfg=tick_cfg)


@functools.partial(
    jax.jit,
    static_argnames=("update_cdf", "do_tick", "min_proc", "budget",
                     "num_total", "tick_cfg"),
    donate_argnames=("state",))
def _control_masked_dev(state, util, present, *, update_cdf, do_tick,
                        min_proc, budget, num_total=None,
                        tick_cfg=DEFAULT_TICK_CONFIG):
    return control_core(state, util, present, update_cdf=update_cdf,
                        do_tick=do_tick, min_proc=min_proc, budget=budget,
                        num_total=num_total, tick_cfg=tick_cfg)


@functools.partial(
    jax.jit,
    static_argnames=("hue_ranges", "bs", "bv", "alpha", "fg_threshold",
                     "use_fg", "bg_valid", "op", "impl", "interpret",
                     "update_cdf", "do_tick", "min_proc", "budget",
                     "num_total", "tick_cfg"),
    donate_argnames=("state",))
def _serve_step_dev(state, frames, M_pos, norm, *, hue_ranges, bs, bv,
                    alpha, fg_threshold, use_fg, bg_valid, op, impl,
                    interpret, update_cdf, do_tick, min_proc, budget,
                    num_total=None, tick_cfg=DEFAULT_TICK_CONFIG):
    """The fused serve step: ingest -> CDF push -> admission -> queue
    selection -> threshold/queue-size control, ONE jitted dispatch with
    the state pytree's buffers donated. Utilities are produced and
    consumed on device; only the compact decision / eviction arrays and
    the (small) state leaves read by the host ever transfer."""
    bg0 = state.bg if bg_valid else jnp.zeros_like(state.bg)
    gain0 = state.gain if bg_valid else jnp.ones_like(state.gain)
    # the Pallas ingest names its own device work: shed.stage (the
    # planar relayout of the frames) and shed.score (the kernel)
    _, _, _, util, bg, gain = ingest_core(
        frames, bg0, gain0, M_pos, norm, hue_ranges=hue_ranges, bs=bs,
        bv=bv, alpha=alpha, threshold=fg_threshold, use_fg=use_fg,
        bg_valid=bg_valid, op=op, impl=impl, interpret=interpret)
    state = dataclasses.replace(state, bg=bg, gain=gain,
                                bg_valid=jnp.asarray(True))
    with jax.named_scope("shed.control"):
        return control_core(state, util, None, update_cdf=update_cdf,
                            do_tick=do_tick, min_proc=min_proc,
                            budget=budget, num_total=num_total,
                            tick_cfg=tick_cfg)


@jax.jit
def _flatten_frames(frames):
    """(C, T, H, W, 3) -> (C, T, H*W, 3) on the device: the frames'
    first relayout, a program of its own under ``shed.stage``."""
    C, T, H, W, _ = frames.shape
    with jax.named_scope("shed.stage"):
        return frames.reshape(C, T, H * W, 3)


@functools.partial(jax.jit, static_argnames=("update_cdf", "tick_cfg"),
                   donate_argnames=("state",))
def _offer_dev(state, cam, u, *, update_cdf, tick_cfg=DEFAULT_TICK_CONFIG):
    """Single-frame admission: scalar CDF push + threshold compare +
    single queue push for one camera lane."""
    C, W = state.cdf_buf.shape
    B = state.cdf_counts.shape[1]
    u = jnp.asarray(u, jnp.float32)
    cdf_buf, cdf_pos, cdf_len = state.cdf_buf, state.cdf_pos, state.cdf_len
    cdf_counts = state.cdf_counts
    if update_cdf:
        old = cdf_buf[cam, cdf_pos[cam]]
        evict = cdf_pos[cam] < cdf_len[cam]
        cdf_counts = cdf_counts.at[
            cam, bucket_index_dev(old, tick_cfg.lo, tick_cfg.inv_width,
                                  B)].add(-evict.astype(jnp.int32))
        cdf_counts = cdf_counts.at[
            cam, bucket_index_dev(u, tick_cfg.lo, tick_cfg.inv_width,
                                  B)].add(1)
        cdf_buf = cdf_buf.at[cam, cdf_pos[cam]].set(u)
        cdf_pos = cdf_pos.at[cam].set((cdf_pos[cam] + 1) % W)
        cdf_len = cdf_len.at[cam].set(jnp.minimum(cdf_len[cam] + 1, W))
    shed = u < state.threshold[cam]
    do_push = (jnp.arange(C) == cam) & ~shed
    q_util, q_seq, q_next, pushed_seq, evicted_seq, inc_ev = sq.push_one_dev(
        state.q_util, state.q_seq, state.q_next_seq,
        jnp.full((C,), u, jnp.float32), do_push, state.queue_cap)
    code = jnp.where(shed, jnp.int8(SHED_ADMISSION),
                     jnp.where(inc_ev[cam], jnp.int8(SHED_QUEUE),
                               jnp.int8(ADMIT)))
    state = dataclasses.replace(
        state, cdf_buf=cdf_buf, cdf_pos=cdf_pos, cdf_len=cdf_len,
        cdf_counts=cdf_counts, q_util=q_util, q_seq=q_seq, q_next_seq=q_next)
    return state, code, pushed_seq[cam], evicted_seq[cam]


@functools.partial(jax.jit, donate_argnames=("state",))
def _pop_any_dev(state):
    q_util, q_seq, cam, seq = sq.pop_best_dev(state.q_util, state.q_seq)
    return dataclasses.replace(state, q_util=q_util, q_seq=q_seq), cam, seq


@functools.partial(jax.jit, donate_argnames=("state",))
def _pop_cam_dev(state, cam):
    q_util, q_seq, cam, seq = sq.pop_best_dev(state.q_util, state.q_seq, cam)
    return dataclasses.replace(state, q_util=q_util, q_seq=q_seq), cam, seq


@functools.partial(jax.jit, static_argnames=("k",),
                   donate_argnames=("state",))
def _pop_topk_dev(state, *, k):
    q_util, q_seq, cams, seqs = sq.pop_topk_dev(state.q_util, state.q_seq, k)
    return (dataclasses.replace(state, q_util=q_util, q_seq=q_seq),
            cams, seqs)


@functools.partial(jax.jit, static_argnames=("k",),
                   donate_argnames=("state",))
def _pop_topk_masked_dev(state, rows, *, k):
    q_util, q_seq, cams, seqs = sq.pop_topk_dev(state.q_util, state.q_seq, k,
                                                rows)
    return (dataclasses.replace(state, q_util=q_util, q_seq=q_seq),
            cams, seqs)


@functools.partial(jax.jit,
                   static_argnames=("min_proc", "budget", "num_total",
                                    "tick_cfg"),
                   donate_argnames=("state",))
def _tick_dev(state, *, min_proc, budget, num_total=None,
              tick_cfg=DEFAULT_TICK_CONFIG):
    return tick_core(state, min_proc, budget, num_total, tick_cfg)


__all__ = [
    "ADMIT", "SHED_ADMISSION", "SHED_QUEUE", "SHED_CASCADE",
    "DECISION_NAMES", "DEFAULT_TICK_CONFIG", "SessionState", "TickConfig",
    "cascade_finish_core", "cascade_rates", "cascade_tick_core",
    "control_core", "no_span", "ring_push", "tick_core",
]
