"""Unified multi-camera shedding sessions: one query spec, one pytree
state, one fused dispatch per camera array.

The paper's Load Shedder is a per-camera pipeline (utility scoring ->
admission threshold -> dynamic queue -> control loop); edge nodes serve
many cameras at once, so the first-class unit here is the *camera
array*:

``Query``
    Declarative query spec — target colors, OR/AND composition, E2E
    latency budget, per-camera target FPS, feature-bin and
    background-model constants. One compiled shedder per query.

``SessionState`` (``repro.core.control_plane``)
    An explicit JAX pytree of per-camera state lanes: ``(C, N)``
    background rows and ``(C,)`` illumination gains (the fused ingest
    kernel's carried state), per-camera utility-CDF ring buffers and
    admission thresholds (Eq. 16–17), the control loop's EWMAs
    (Eq. 18–20), and the utility-ordered queues as fixed-capacity
    ``(C, K)`` utility/seq lanes. Every leaf is an array, so the whole
    serve path — queues included — checkpoints through
    ``repro.train.checkpoint`` and round-trips across restarts. (Queued
    frame *payloads* are live host objects keyed by seq; a restored
    session falls back to ``(cam, seq)`` index pairs for entries whose
    payloads did not survive.)

``ShedSession``
    The method surface every consumer builds on. ``step`` is the serve
    hot loop: a ``(C, T, H, W, 3)`` camera batch goes from fused ingest
    through CDF maintenance, vectorized admission, queue selection and
    threshold re-derivation without utilities ever leaving the
    compute path — only compact ``(C, T)`` int8 decision codes and
    evicted queue indices come back. ``ingest``/``admit``/``tick`` are
    the split phases of the same machinery; ``offer``/``offer_batch``/
    ``next_frame`` are the frame-at-a-time serving surface the pipeline
    simulator drives; ``checkpoint``/``restore`` persist the state
    pytree.

The control plane is one implementation: the state lanes are jnp
arrays and every control operation is a jitted XLA program with donated
state buffers (``repro.core.control_plane``), on every backend — the
TPU, and XLA CPU on a CPU. A camera-sharded session runs the same
cores shard_map'd over a mesh (``repro.core.fleet``). The host keeps
only bookkeeping: payloads, counters, queue depths and the
backend-latency fold.

``open_session(query, num_cameras, ...)`` is the entry point.
"""
from __future__ import annotations

import heapq
from dataclasses import dataclass
from typing import (TYPE_CHECKING, Any, Dict, List, Optional, Sequence,
                    Tuple, Union)

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import fleet
from repro.core import shed_queue as sq
from repro.core.colors import COLORS, Color
from repro.core.control import LatencyInputs
from repro.core.control_plane import (
    ADMIT,
    DECISION_NAMES,
    SHED_ADMISSION,
    SHED_CASCADE,
    SHED_QUEUE,
    SessionState,
    TickConfig,
    _cascade_admit_dev,
    _cascade_finish_dev,
    _cascade_tick_dev,
    _control_masked_dev,
    _control_step_dev,
    _flatten_frames,
    _offer_dev,
    _pop_any_dev,
    _pop_cam_dev,
    _pop_topk_dev,
    _pop_topk_masked_dev,
    _serve_step_dev,
    _tick_dev,
    no_span,
    ring_push,
)
from repro.core.shedder import ShedderStats
from repro.core.utility import (
    B_S,
    B_V,
    UtilityModel,
    batch_utilities,
    train_utility_model,
)
from repro.kernels.hsv_features.ops import (
    IngestState,
    default_impl,
    ingest_pipeline,
    query_constants,
)

if TYPE_CHECKING:
    from repro.serve.metrics import MetricsRegistry


def _as_color(c: Union[str, Color]) -> Color:
    if isinstance(c, Color):
        return c
    return COLORS[str(c).lower()]


@dataclass(frozen=True)
class Query:
    """Declarative spec of what the camera array is watching for.

    ``colors`` compose with ``op`` (Eq. 15: OR -> max, AND -> min over
    normalized per-color utilities); ``latency_bound`` is the E2E
    budget driving dynamic queue sizing (Eq. 20); ``fps`` is the
    per-camera target ingress rate feeding the target drop rate
    (Eq. 19). The remaining fields are the feature/background constants
    baked into the compiled ingest kernel.
    """
    colors: Tuple[Color, ...]
    op: str = "single"                  # single | or | and
    latency_bound: float = 1.0          # seconds, E2E
    fps: float = 10.0                   # per-camera target ingress FPS
    bs: int = B_S                       # saturation bins
    bv: int = B_V                       # value bins
    alpha: float = 0.05                 # background EMA learning rate
    threshold: float = 18.0             # foreground |diff| threshold
    use_foreground: bool = True

    def __post_init__(self) -> None:
        colors = tuple(_as_color(c) for c in (
            self.colors if isinstance(self.colors, (tuple, list))
            else (self.colors,)))
        object.__setattr__(self, "colors", colors)
        if self.op not in ("single", "or", "and"):
            raise ValueError(f"unknown composition op {self.op!r}")
        if self.op == "single" and len(colors) > 1:
            object.__setattr__(self, "op", "or")

    @classmethod
    def single(cls, color: Union[str, Color], **kw: Any) -> "Query":
        return cls(colors=(_as_color(color),), op="single", **kw)

    @classmethod
    def any_of(cls, *colors: Union[str, Color], **kw: Any) -> "Query":
        return cls(colors=tuple(_as_color(c) for c in colors), op="or", **kw)

    @classmethod
    def all_of(cls, *colors: Union[str, Color], **kw: Any) -> "Query":
        return cls(colors=tuple(_as_color(c) for c in colors), op="and", **kw)

    @property
    def hue_ranges(self) -> Tuple[Tuple[Tuple[int, int], ...], ...]:
        return tuple(tuple(c.hue_ranges) for c in self.colors)

    @property
    def num_colors(self) -> int:
        return len(self.colors)




@dataclass(frozen=True)
class IngestResult:
    """One fused-dispatch result over a camera array."""
    pf: np.ndarray                 # (C, T, nc, bs, bv)
    hue_fraction: np.ndarray       # (C, T, nc)
    utility: Optional[np.ndarray]  # (C, T) — None without a trained model


@dataclass(frozen=True)
class StepResult:
    """Compact host-side outcome of one serve ``step`` — all that
    crosses back from the device program.

    ``decisions``: (C, T) int8 codes (``ADMIT`` / ``SHED_ADMISSION`` /
    ``SHED_QUEUE``; retroactive same-batch queue evictions included).
    ``pushed_seq``: (C, T) int32 queue seq per admitted slot (-1
    otherwise). ``evicted``: per-camera int arrays of seqs of
    *previously queued* frames dropped this step (push evictions of
    residents plus tick resizes). ``target_drop_rate``: (C,) float32
    Eq. 19 rates when the step re-derived thresholds, else None.
    """
    decisions: np.ndarray
    pushed_seq: np.ndarray
    evicted: List[np.ndarray]
    target_drop_rate: Optional[np.ndarray] = None
    # (C, T) stage-2 scores when the step ran the semantic cascade
    # (0 for frames the color gate shed before the scorer saw them)
    s2_scores: Optional[np.ndarray] = None




class ShedSession:
    """A camera array's Load Shedder: fused scoring + per-camera
    admission/queues + shared-backend control loop.

    Use :func:`open_session` to construct one.
    """

    def __init__(self, query: Query, num_cameras: int = 1, *,
                 frame_shape: Optional[Tuple[int, int]] = None,
                 model: Optional[UtilityModel] = None,
                 train_utilities: Optional[Sequence[float]] = None,
                 queue_size: int = 8,
                 queue_capacity: int = 64,
                 latency_inputs: Optional[LatencyInputs] = None,
                 cdf_window: int = 4096,
                 ewma_alpha: float = 0.2, ewma_alpha_up: float = 0.6,
                 min_proc: float = 1e-6,
                 update_cdf_online: bool = True,
                 impl: Optional[str] = None,
                 interpret: Optional[bool] = None,
                 serve: Optional[str] = None,
                 mesh: Optional[Any] = None,
                 shard_cameras: Optional[bool] = None,
                 fleet_aggregate: bool = False,
                 cascade: Optional[Any] = None,
                 exact_tick: bool = False,
                 quantile_bins: int = 256,
                 quantile_range: Tuple[float, float] = (0.0, 1.0),
                 s2_quantile_range: Tuple[float, float] = (-1.0, 1.0),
                 metrics: Optional["MetricsRegistry"] = None,
                 ) -> None:
        if num_cameras < 1:
            raise ValueError("num_cameras must be >= 1")
        # serve= names the one control plane; the keyword stays for
        # callers that still pass serve="device"
        if serve not in (None, "device"):
            raise ValueError(
                f"serve={serve!r}: the NumPy serve path was removed; the "
                f"session runs one jitted control plane on every backend "
                f"(serve=None or 'device')")
        self.query = query
        # host spans and counters of the serve path (session.*); None
        # runs every span site through one shared no-op context
        self.metrics = metrics
        self._span = metrics.span if metrics is not None else no_span
        self.num_cameras = int(num_cameras)
        self.model = model
        # semantic cascade (repro.cascade.Cascade, duck-typed: .scorer /
        # .gate_fraction / .window) — strictly opt-in; None leaves every
        # decision bit-identical to the single-stage pipeline
        self.cascade = cascade
        self._gate_fraction = (float(getattr(cascade, "gate_fraction", 0.5))
                               if cascade is not None else 0.5)
        s2_window = (int(getattr(cascade, "window", 1024))
                     if cascade is not None else 64)
        if cascade is not None and (mesh is not None or shard_cameras):
            raise ValueError(
                "cascade= is not supported with camera sharding yet: the "
                "stage-2 scorer is a host call and the sharded serve plane "
                "is a single device program")
        self.latency_inputs = latency_inputs or LatencyInputs()
        self.ewma_alpha = float(ewma_alpha)
        self.ewma_alpha_up = float(ewma_alpha_up)
        self.min_proc = float(min_proc)
        self.update_cdf_online = bool(update_cdf_online)
        self.impl = impl
        self.interpret = interpret
        # fleet mode: shard the camera lanes over a device mesh
        # (repro.core.fleet). shard_cameras=True without a mesh builds a
        # 1-D mesh over every device; a mesh alone implies sharding.
        if shard_cameras is None:
            shard_cameras = mesh is not None
        self.mesh = None
        self._cam_axis: Optional[Any] = None
        self._shardings: Optional[Dict[str, Any]] = None
        self._frame_sharding: Optional[Any] = None
        self.fleet_aggregate = bool(fleet_aggregate)
        self.last_fleet_stats: Optional[Dict[str, float]] = None
        if shard_cameras:
            self.mesh = mesh if mesh is not None else fleet.fleet_mesh()
            self._cam_axis = fleet.camera_axis(self.mesh, self.num_cameras)
            self._frame_sharding = fleet.frame_sharding(self.mesh,
                                                        self._cam_axis)
        self._queue_size = int(queue_size)
        # quantile-tick mode: O(bins) incremental bucket counts by
        # default, exact (C, W) sort behind exact_tick=True. One
        # hashable static (TickConfig) carries the bucket geometry
        # through every jitted program.
        bins = int(quantile_bins)
        if bins < 2:
            raise ValueError(f"quantile_bins {bins} must be >= 2")
        qlo, qhi = (float(quantile_range[0]), float(quantile_range[1]))
        s2lo, s2hi = (float(s2_quantile_range[0]),
                      float(s2_quantile_range[1]))
        if not (qhi > qlo and s2hi > s2lo):
            raise ValueError("quantile ranges must satisfy hi > lo")
        self.exact_tick = bool(exact_tick)
        self.quantile_bins = bins
        self._tick_cfg = TickConfig(
            exact=self.exact_tick,
            lo=qlo, width=(qhi - qlo) / bins, inv_width=bins / (qhi - qlo),
            s2_lo=s2lo, s2_width=(s2hi - s2lo) / bins,
            s2_inv_width=bins / (s2hi - s2lo))
        npix = frame_shape[0] * frame_shape[1] if frame_shape else 0
        self.state = SessionState.fresh(
            num_cameras, npix, cdf_window=cdf_window, fps=query.fps,
            queue_size=queue_size, queue_capacity=queue_capacity,
            s2_window=s2_window, quantile_bins=bins)
        if self.mesh is not None:
            self._shardings = fleet.state_shardings(
                self.mesh, self.state, self._cam_axis)
            self.state = fleet.shard_state(self.state, self.mesh,
                                           self._cam_axis)
        # host copy of the latency lanes, equal to the fresh state's
        # zeros (see report_backend_latency and _put_latency_lanes)
        self._proc_q = np.zeros((self.num_cameras,), np.float32)
        self._proc_seen = np.zeros((self.num_cameras,), bool)
        self._proc_dirty = False
        self.queue_capacity = int(self.state.q_util.shape[1])
        self._payloads: List[Dict[int, Any]] = [
            {} for _ in range(self.num_cameras)]
        # live queue depths, maintained incrementally from the compact
        # step/offer/pop outputs so __len__/queue_depths never transfer
        # the (C, K) q_seq lanes to host on the sender loop
        self._depths = np.zeros((self.num_cameras,), np.int64)
        self.stats = ShedderStats()
        self.per_camera_offered = np.zeros((self.num_cameras,), np.int64)
        self.per_camera_dropped = np.zeros((self.num_cameras,), np.int64)
        self._lane_of: Dict[Any, int] = {}
        # unmapped lanes, a min-heap: lane() claims the smallest free
        # lane, which reproduces the pre-churn first-seen order exactly
        self._free_lanes: List[int] = list(range(self.num_cameras))
        self._active_host = np.ones((self.num_cameras,), bool)
        self._num_active = self.num_cameras
        self._rate_floor_host = 0.0
        self._consts: Optional[Tuple[Any, Tuple[Any, Any, str]]] = None
        if train_utilities is not None:
            self.seed_cdf(train_utilities)

    # -- camera lanes / churn ------------------------------------------------

    def lane(self, cam_id: Any) -> int:
        """Map an external camera id to a state lane (first-seen order).

        An unknown id claims the lowest free lane; a lane left inactive
        by ``detach_camera`` is reset to fresh per-camera state for the
        newcomer (an implicit ``attach_camera``)."""
        lane = self._lane_of.get(cam_id)
        if lane is None:
            if not self._free_lanes:
                raise ValueError(
                    f"camera id {cam_id!r} exceeds the session's "
                    f"{self.num_cameras} lanes")
            lane = heapq.heappop(self._free_lanes)
            self._lane_of[cam_id] = lane
            if not self._active_host[lane]:
                self._reset_lane(lane, active=True)
                self._active_host[lane] = True
                self._num_active += 1
        return lane

    @property
    def num_active(self) -> int:
        """Live camera count — Eq. 19's backend-sharing multiplier."""
        return self._num_active

    def attach_camera(self, cam_id: Any) -> int:
        """Add a camera to a live session: claim a free lane (fresh
        per-camera state when reclaiming a detached lane) and return
        it. Raises when the id is already attached or no lane is free."""
        if cam_id in self._lane_of:
            raise ValueError(f"camera {cam_id!r} is already attached")
        return self.lane(cam_id)

    def detach_camera(self, cam_id: Any) -> List[Any]:
        """Remove a live camera: its queued frames are drained (returned,
        and counted as queue sheds — they will never transmit), the lane
        is masked out of admission/control (threshold pinned to +inf,
        Eq. 19 excludes it), and the lane is freed for reuse."""
        lane = self._lane_of.pop(cam_id, None)
        if lane is None:
            raise ValueError(f"unknown camera id {cam_id!r}")
        seq_row = np.asarray(self.state.q_seq)[lane]
        drained = [self._payloads[lane].pop(int(s), (lane, int(s)))
                   for s in seq_row[seq_row >= 0]]
        self._payloads[lane] = {}
        self.stats.dropped_queue += len(drained)
        self.per_camera_dropped[lane] += len(drained)
        self._depths[lane] = 0
        self._reset_lane(lane, active=False)
        heapq.heappush(self._free_lanes, lane)
        self._active_host[lane] = False
        self._num_active -= 1
        return drained

    def _write_lane(self, name: str, lane: int, value: Any) -> None:
        """Set one lane row of a state leaf (a functional update,
        re-placed on the fleet sharding when one exists)."""
        st = self.state
        arr = getattr(st, name).at[lane].set(value)
        if self._shardings is not None:
            arr = jax.device_put(arr, self._shardings[name])
        setattr(st, name, arr)

    def _reset_lane(self, lane: int, active: bool) -> None:
        """Fresh per-camera state for one lane. Inactive lanes park at
        threshold=+inf (admit nothing); (re)attached lanes start at
        -inf (admit everything) until their CDF window fills."""
        q = self.query
        K = self.queue_capacity
        B = int(self.state.cdf_counts.shape[1])
        for name, v in (
                ("gain", 1.0), ("cdf_len", 0), ("cdf_pos", 0),
                ("cdf_counts", np.zeros((B,), np.int32)),
                ("threshold", np.float32(-np.inf if active else np.inf)),
                ("fps_obs", float(q.fps)), ("fps_seen", False),
                ("queue_cap", self._queue_size), ("q_next_seq", 0),
                ("q_util", np.full((K,), -np.inf, np.float32)),
                ("q_seq", np.full((K,), -1, np.int32)),
                ("rate_floor", np.float32(self._rate_floor_host)),
                ("s2_len", 0), ("s2_pos", 0),
                ("s2_threshold",
                 np.float32(-np.inf if active else np.inf)),
                ("s2_counts", np.zeros((B,), np.int32)),
                ("active", bool(active))):
            self._write_lane(name, lane, v)
        fresh = np.arange(self.num_cameras) == lane
        self._set_latency_lanes(
            np.where(fresh, np.float32(0.0), self._proc_q),
            self._proc_seen & ~fresh)
        self._depths[lane] = 0
        if self.state.bg.shape[1]:
            self._write_lane(
                "bg", lane,
                np.zeros((self.state.bg.shape[1],), np.float32))

    # -- degraded-mode control (serve/fault.py drives this) ------------------

    @property
    def rate_floor(self) -> float:
        return self._rate_floor_host

    def set_rate_floor(self, floor: float) -> None:
        """Degraded-regime floor under every lane's Eq. 19 target drop
        rate, applied at the next ``tick``/``step``. 0.0 restores the
        normal regime bit-identically (``max(r, 0)`` is the identity on
        the clipped rates)."""
        f = float(floor)
        if not 0.0 <= f <= 1.0:
            raise ValueError(f"rate floor {f} outside [0, 1]")
        self._rate_floor_host = f
        val = jnp.full((self.num_cameras,), f, jnp.float32)
        if self._shardings is not None:
            val = jax.device_put(val, self._shardings["rate_floor"])
        self.state.rate_floor = val

    @property
    def _budget(self) -> float:
        li = self.latency_inputs
        return (self.query.latency_bound - li.net_cam_ls - li.net_ls_q
                - li.proc_cam)

    def _model_constants(self):
        """The (M_pos, norm, op) device constants the serve step bakes
        in — computed once per trained model (fit/restore swap the model
        object, invalidating the cache), not per step."""
        if self._consts is None or self._consts[0] is not self.model:
            q = self.query
            self._consts = (self.model, query_constants(
                self.model, q.num_colors, q.bs, q.bv, q.op))
        return self._consts[1]

    # -- training / scoring --------------------------------------------------

    def fit(self, pfs: np.ndarray, labels: np.ndarray) -> UtilityModel:
        """Train the query's utility function (Eq. 12–13) on PF matrices
        and seed every camera's utility CDF with the train utilities."""
        self.model = train_utility_model(
            np.asarray(pfs, np.float32), labels, self.query.colors,
            op=self.query.op)
        self.seed_cdf(batch_utilities(self.model, np.asarray(pfs, np.float32)))
        return self.model

    def seed_cdf(self, utilities: Union[np.ndarray, Sequence[float]]) -> None:
        """Fill every camera's CDF window with a shared utility history."""
        us = np.asarray(utilities, np.float32).reshape(-1)
        us = np.broadcast_to(us, (self.num_cameras, us.size))
        st = self.state
        cfg = self._tick_cfg
        st.cdf_buf, st.cdf_pos, st.cdf_len, st.cdf_counts = ring_push(
            st.cdf_buf, st.cdf_pos, st.cdf_len, st.cdf_counts,
            jnp.asarray(us), None, cfg.lo, cfg.inv_width)

    # -- fused ingest --------------------------------------------------------

    def _check_frames(self, frames: np.ndarray) -> np.ndarray:
        """Validated float32 frames for the host-scored paths."""
        return self._validate_frames(np.asarray(frames, np.float32))

    def _validate_frames(self, frames: np.ndarray) -> np.ndarray:
        """Check a (C, T, H, W, 3) batch (or a single camera's (T, H, W,
        3)) against the session, sizing a fresh background lane to it;
        the dtype is left as it came."""
        if frames.ndim == 4:
            frames = frames[None]
        if frames.ndim != 5 or frames.shape[0] != self.num_cameras:
            raise ValueError(
                f"expected ({self.num_cameras}, T, H, W, 3) frames, "
                f"got {frames.shape}")
        n = frames.shape[2] * frames.shape[3]
        st = self.state
        if st.bg.shape[1] != n:
            if bool(st.bg_valid):
                raise ValueError(
                    f"frame size {n} px does not match carried background "
                    f"state {st.bg.shape}")
            bg = jnp.zeros((self.num_cameras, n), jnp.float32)
            if self._shardings is not None:
                bg = jax.device_put(bg, self._shardings["bg"])
            st.bg = bg
        return frames

    def ingest(self, frames: np.ndarray, *, impl: Optional[str] = None,
               interpret: Optional[bool] = None) -> IngestResult:
        """Score one frame batch for the whole camera array in ONE fused
        device dispatch, carrying per-camera background state.

        frames: (C, T, H, W, 3) float32 RGB in [0, 255] — or
        (T, H, W, 3) for single-camera sessions.
        """
        frames = self._check_frames(frames)
        st = self.state
        state_in = (IngestState(bg=st.bg, gain=st.gain)
                    if bool(st.bg_valid) else None)
        q = self.query
        pf, hf, util, state_out = ingest_pipeline(
            frames, q.colors, self.model, state=state_in, alpha=q.alpha,
            threshold=q.threshold, use_foreground=q.use_foreground,
            op=q.op, bs=q.bs, bv=q.bv,
            impl=impl if impl is not None else self.impl,
            interpret=interpret if interpret is not None else self.interpret)
        self._set_ingest_lanes(state_out)
        return IngestResult(
            pf=np.asarray(pf), hue_fraction=np.asarray(hf),
            utility=None if util is None else np.asarray(util))

    @property
    def ingest_state(self) -> IngestState:
        """The kernel-facing ``(bg, gain)`` lanes (for host handoff)."""
        return IngestState(bg=self.state.bg, gain=self.state.gain)

    def set_ingest_state(self, state: Optional[IngestState]) -> None:
        if state is None:
            self.state.bg_valid = jnp.asarray(False)
            return
        bg = jnp.asarray(state.bg, jnp.float32)
        if bg.ndim == 1:
            bg = bg[None]
        if bg.shape[0] != self.num_cameras:
            raise ValueError(
                f"state has {bg.shape[0]} camera lanes, session has "
                f"{self.num_cameras}")
        self._set_ingest_lanes(IngestState(bg=bg, gain=state.gain))

    def _set_ingest_lanes(self, state: IngestState) -> None:
        st = self.state
        st.bg = jnp.asarray(state.bg, jnp.float32)
        st.gain = jnp.asarray(state.gain, jnp.float32).reshape(-1)
        st.bg_valid = jnp.asarray(True)

    # -- the fused serve step (tentpole) -------------------------------------

    def step(self, frames: Optional[np.ndarray] = None, *,
             utilities: Optional[np.ndarray] = None,
             s2_utilities: Optional[np.ndarray] = None,
             items: Optional[Sequence[Sequence[Any]]] = None,
             tick: bool = True,
             impl: Optional[str] = None,
             interpret: Optional[bool] = None) -> StepResult:
        """One serve-loop iteration for the whole camera array: score ->
        CDF push -> admission -> queue selection -> (``tick=True``)
        threshold/queue-size re-derivation.

        Give either ``frames`` — a (C, T, H, W, 3) batch scored by the
        fused ingest kernel inside the same dispatch (requires a
        trained model) — or precomputed ``utilities`` (C, T) to run the
        control plane alone. The frames form is ONE jitted XLA program
        with donated state buffers.

        With a session ``cascade``, a frames step additionally runs the
        stage-2 semantic scorer over the color-gate survivors (batched,
        on the foreground-bbox ROIs the ingest kernel computes in the
        same dispatch) and applies the stage-2 threshold before queue
        insertion; queues are then ordered by the SEMANTIC score.
        ``s2_utilities`` (C, T) supplies precomputed stage-2 scores with
        ``utilities`` — the control-plane-only cascade form. A
        utilities-only step on a cascade session runs stage 1 alone.

        ``items[c][t]`` are frame payloads for ``next_frame``; absent,
        queued frames are identified by their ``(cam, t)`` index pair.
        Only compact decision/eviction arrays return to the host — see
        :class:`StepResult`.

        With a session ``metrics`` registry each call is a span
        ``session.step`` and counts ``session.steps`` and its offered
        frames in ``session.frames``. On the frames path its phases are
        the spans ``session.stage`` (the frames' checks on the host),
        ``session.put`` (the hand-off to the device and, on one device,
        the first relayout), ``session.dispatch``, ``session.readback``
        and ``session.absorb`` (host bookkeeping).
        That path hands the frames to the device in the camera's dtype
        (uint8 as it comes) and converts them to float32 there. On a
        camera mesh each camera's frames go straight to the device that
        holds its lanes, and the sharded step flattens them there.
        ``session.staged_bytes`` counts the float32 frames staged for
        the kernel, whatever dtype arrives.
        """
        with self._span("session.step"):
            res = self._step(frames, utilities, s2_utilities, items, tick,
                             impl, interpret)
        if self.metrics is not None:
            self.metrics.counter("session.steps").inc()
            self.metrics.counter("session.frames").inc(
                int((res.decisions >= 0).sum()))
        return res

    def _step(self, frames, utilities, s2_utilities, items, tick, impl,
              interpret) -> StepResult:
        if (frames is None) == (utilities is None):
            raise ValueError("pass exactly one of frames= or utilities=")
        if s2_utilities is not None and self.cascade is None:
            raise ValueError("s2_utilities= needs a session cascade")
        if s2_utilities is not None and frames is not None:
            raise ValueError("s2_utilities= goes with utilities=, not "
                             "frames= (frames are scored by the cascade)")
        self._put_latency_lanes()
        if self.cascade is not None and (frames is not None
                                         or s2_utilities is not None):
            return self._cascade_step(frames, utilities, s2_utilities,
                                      items, tick, impl, interpret)
        kw = dict(update_cdf=self.update_cdf_online, do_tick=bool(tick),
                  min_proc=self.min_proc, budget=self._budget,
                  num_total=self._num_active, tick_cfg=self._tick_cfg)
        if frames is not None:
            if self.model is None:
                raise ValueError("step(frames=...) needs a trained model "
                                 "(call fit() or pass model=)")
            return self._serve_frames(frames, items, tick, impl, interpret,
                                      kw)
        util = jnp.asarray(self._check_utilities(utilities))
        if self.mesh is not None:
            self.state, out, agg = fleet.control_step(
                self.state, util, mesh=self.mesh, axis=self._cam_axis,
                aggregate=self.fleet_aggregate, **kw)
            return self._absorb_control(out, items, tick, agg=agg)
        self.state, out = _control_step_dev(self.state, util, **kw)
        return self._absorb_control(out, items, tick)

    def _check_utilities(self, utilities) -> np.ndarray:
        """A (C, T) float32 utility batch, flushed of subnormals (a
        (T,) vector is accepted for single-camera sessions)."""
        util = sq.flush_subnormal(utilities)
        if util.ndim == 1:
            util = util[None]
        if util.shape[0] != self.num_cameras:
            raise ValueError(
                f"expected ({self.num_cameras}, T) utilities, "
                f"got {util.shape}")
        if util.shape[1] == 0:
            raise ValueError("empty utility batch")
        return util

    def _serve_frames(self, frames, items, tick, impl, interpret,
                      kw) -> StepResult:
        """The frames step: the frames to the device in the camera's
        dtype, converted to float32 there, then ONE fused serve-step
        dispatch (see ``step`` for its spans)."""
        span = self._span
        with span("session.stage"):
            frames = self._validate_frames(np.asarray(frames))
        if frames.shape[1] == 0:
            raise ValueError("empty frame batch")
        if self.metrics is not None:
            self.metrics.counter("session.staged_bytes").inc(4 * frames.size)
        with span("session.put"):
            if self.mesh is None:
                frames = _flatten_frames(jnp.asarray(frames))
            else:
                # each camera's frames straight to the device that holds
                # its lanes; the sharded step flattens them there
                frames = jax.device_put(frames, self._frame_sharding)
        with span("session.dispatch"):
            q = self.query
            M_pos, norm, op = self._model_constants()
            use_impl = impl if impl is not None else self.impl
            if use_impl is None:
                use_impl = default_impl()
            ingest_kw = dict(
                hue_ranges=q.hue_ranges, bs=q.bs, bv=q.bv,
                alpha=q.alpha, fg_threshold=q.threshold,
                use_fg=q.use_foreground,
                bg_valid=bool(self.state.bg_valid), op=op,
                impl=use_impl,
                interpret=(interpret if interpret is not None
                           else self.interpret))
            agg = None
            if self.mesh is not None:
                self.state, out, agg = fleet.serve_step(
                    self.state, frames, M_pos, norm, mesh=self.mesh,
                    axis=self._cam_axis,
                    aggregate=self.fleet_aggregate, **ingest_kw, **kw)
            else:
                self.state, out = _serve_step_dev(
                    self.state, frames, M_pos, norm, **ingest_kw, **kw)
        return self._absorb_control(out, items, tick, agg=agg, span=span)

    def _cascade_step(self, frames, utilities, s2_utilities, items, tick,
                      impl, interpret) -> StepResult:
        """Two-stage serve step: stage-1 gate -> batched stage-2 scoring
        of the survivors -> stage-2 gate -> queue insertion. Three
        dispatches instead of one (the scorer is a host call between two
        jitted control phases); ingest still runs fused, with the
        foreground bbox rider supplying the scorer's ROIs for free."""
        kwt = dict(do_tick=bool(tick), min_proc=self.min_proc,
                   budget=self._budget, gate_fraction=self._gate_fraction,
                   num_total=self._num_active, tick_cfg=self._tick_cfg)
        bbox = None
        if frames is not None:
            if self.model is None:
                raise ValueError("step(frames=...) needs a trained model "
                                 "(call fit() or pass model=)")
            frames = self._check_frames(frames)
            if frames.shape[1] == 0:
                raise ValueError("empty frame batch")
            q = self.query
            st = self.state
            state_in = (IngestState(bg=st.bg, gain=st.gain)
                        if bool(st.bg_valid) else None)
            _, _, util, state_out, bbox = ingest_pipeline(
                frames, q.colors, self.model, state=state_in,
                alpha=q.alpha, threshold=q.threshold,
                use_foreground=q.use_foreground, op=q.op, bs=q.bs,
                bv=q.bv, impl=impl if impl is not None else self.impl,
                interpret=(interpret if interpret is not None
                           else self.interpret),
                with_bbox=True)
            self._set_ingest_lanes(state_out)
            util = np.asarray(util, np.float32)
            bbox = np.asarray(bbox, np.int32)
        else:
            util = self._check_utilities(utilities)
        present = np.ones(util.shape, bool)
        # phase A: stage-1 CDF push + color gate
        self.state, pass1 = _cascade_admit_dev(
            self.state, jnp.asarray(util), jnp.asarray(present),
            update_cdf=self.update_cdf_online, tick_cfg=self._tick_cfg)
        pass1 = np.asarray(pass1)
        # stage-2 scoring — ONE batched scorer call over the survivors
        if s2_utilities is not None:
            s2 = np.asarray(s2_utilities, np.float32).reshape(util.shape)
        else:
            s2 = np.zeros(util.shape, np.float32)
            r, t = np.nonzero(pass1)
            if r.size:
                s2[r, t] = np.asarray(
                    self.cascade.scorer.score(
                        np.ascontiguousarray(frames[r, t]), bbox[r, t]),
                    np.float32)
        s2 = sq.flush_subnormal(s2)
        # phase B: stage-2 ring/gate + queue insertion + optional tick
        self.state, out = _cascade_finish_dev(
            self.state, jnp.asarray(s2), jnp.asarray(present),
            jnp.asarray(pass1), **kwt)
        return self._absorb_control(out, items, tick, s2_scores=s2)

    def _absorb_control(self, out: Dict[str, Any],
                        items: Optional[Sequence[Sequence[Any]]],
                        ticked: bool,
                        s2_scores: Optional[np.ndarray] = None,
                        agg: Optional[Dict[str, Any]] = None,
                        span=no_span) -> StepResult:
        """Fold a control step's compact outputs into host bookkeeping:
        stats, payload registry, per-camera counters. Every read of the
        outputs (the span ``session.readback`` where ``span`` times
        them, waiting for the device) comes before the bookkeeping
        (``session.absorb``). ``agg``: a sharded step's psum aggregate
        tree, if it made one."""
        with span("session.readback"):
            decisions = np.asarray(out["decisions"])
            pushed_seq = np.asarray(out["pushed_seq"])
            ev_res = np.asarray(out["evicted_resident"])
            push_ev = np.asarray(out["push_evictions"])
            rates = rz = None
            if ticked:
                rates = np.asarray(out["rates"])
                rz = np.asarray(out["resize_evicted"])
            if agg is not None:
                self._absorb_fleet(agg)
        with span("session.absorb"):
            return self._absorb(decisions, pushed_seq, ev_res, push_ev,
                                rates, rz, items, s2_scores)

    def _absorb(self, decisions, pushed_seq, ev_res, push_ev, rates,
                     rz, items, s2_scores) -> StepResult:
        C = decisions.shape[0]
        offered = decisions >= 0
        self.stats.offered += int(offered.sum())
        self.stats.dropped_admission += int((decisions == SHED_ADMISSION).sum())
        self.stats.dropped_cascade += int((decisions == SHED_CASCADE).sum())
        self.stats.dropped_queue += int(push_ev.sum())
        self.per_camera_offered += offered.sum(axis=1)
        res_cnt = (ev_res >= 0).sum(axis=1)
        self.per_camera_dropped += (decisions > ADMIT).sum(axis=1) + res_cnt
        # net queue-depth change: frames that survived the batch as
        # ADMIT minus evicted residents (resize evictions below)
        self._depths += (decisions == ADMIT).sum(axis=1) - res_cnt
        evicted: List[np.ndarray] = []
        for c in range(C):
            pl = self._payloads[c]
            for t in np.flatnonzero(decisions[c] == ADMIT):
                item = items[c][t] if items is not None else (c, int(t))
                pl[int(pushed_seq[c, t])] = item
            evs = ev_res[c][ev_res[c] >= 0]
            for s in evs:
                pl.pop(int(s), None)
            evicted.append(evs.astype(np.int64))
        if rz is not None:
            cnt = (rz >= 0).sum(axis=1)
            self.stats.dropped_queue += int(cnt.sum())
            self.per_camera_dropped += cnt
            self._depths -= cnt
            for c in np.flatnonzero(cnt):
                evs = rz[c][rz[c] >= 0]
                pl = self._payloads[c]
                for s in evs:
                    pl.pop(int(s), None)
                evicted[c] = np.concatenate(
                    [evicted[c], evs.astype(np.int64)])
        return StepResult(decisions=decisions, pushed_seq=pushed_seq,
                          evicted=evicted, target_drop_rate=rates,
                          s2_scores=s2_scores)

    # -- fleet observability (sharded sessions) ------------------------------

    def _absorb_fleet(self, agg: Dict[str, Any]) -> None:
        """Keep the latest psum aggregate tree (host view) when the
        sharded step computed one."""
        if self.fleet_aggregate:
            self.last_fleet_stats = fleet.derive_fleet_stats(
                agg, self.num_cameras)

    def fleet_stats(self) -> Dict[str, float]:
        """Global fleet aggregates — queue depth, backend load, mean
        threshold — via ONE small psum over the mesh (the only
        collective in the sharded serve plane)."""
        if self.mesh is None:
            raise ValueError("fleet_stats() needs a camera-sharded "
                             "session (open_session(..., shard_cameras"
                             "=True))")
        self._put_latency_lanes()
        return fleet.aggregates(self.state, mesh=self.mesh,
                                axis=self._cam_axis,
                                num_cameras=self.num_cameras)

    # -- admission + queues --------------------------------------------------

    def admit(self, utilities: np.ndarray,
              items: Optional[Sequence[Sequence[Any]]] = None) -> np.ndarray:
        """Vectorized admission + queue decisions for a scored batch
        (float32; the thresholds are float32 lanes, and using one dtype
        end-to-end keeps batch and frame-at-a-time decisions identical
        on boundary utilities).

        utilities: (C, T) per-camera frame utilities (a (T,) vector is
        accepted for single-camera sessions). ``items[c][t]`` are the
        frame payloads queued for transmission; when omitted, the
        ``(cam, idx)`` index pair is queued instead.

        Returns an (C, T) int8 array of decision codes (``ADMIT``,
        ``SHED_ADMISSION``, ``SHED_QUEUE``); admitted frames have been
        pushed into their camera's utility-ordered queue. A queue
        eviction marks the *evicted* frame: an earlier frame of this
        batch flips to ``SHED_QUEUE`` retroactively, so the returned
        codes describe what actually survived the batch.
        """
        return self.step(utilities=utilities, items=items,
                         tick=False).decisions

    def offer(self, item: Any, utility: float,
              cam: Optional[int] = None) -> str:
        """Frame-at-a-time admission (the simulator/serving surface).

        Returns 'queued' | 'shed_admission' | 'shed_queue'. The camera
        lane comes from ``cam``, else from ``item.cam_id`` (external ids
        are mapped to lanes in first-seen order), else lane 0.
        """
        c = self.lane(getattr(item, "cam_id", 0)) if cam is None else int(cam)
        u = np.float32(sq.flush_subnormal(utility))
        self.stats.offered += 1
        self.per_camera_offered[c] += 1
        self.state, code, pushed, evicted = _offer_dev(
            self.state, c, u, update_cdf=self.update_cdf_online,
            tick_cfg=self._tick_cfg)
        code, pushed, evicted = int(code), int(pushed), int(evicted)
        if code == SHED_ADMISSION:
            self.stats.dropped_admission += 1
            self.per_camera_dropped[c] += 1
            return "shed_admission"
        if evicted >= 0:
            self.stats.dropped_queue += 1
            self.per_camera_dropped[c] += 1
        if code == SHED_QUEUE:
            return "shed_queue"
        self._payloads[c][pushed] = item
        if evicted >= 0:
            self._payloads[c].pop(evicted, None)
        else:
            self._depths[c] += 1        # push without eviction: net +1
        return "queued"

    def offer_batch(self, items: Sequence[Any],
                    utilities: Sequence[float],
                    cams: Optional[Sequence[int]] = None) -> List[str]:
        """Admit several frames that arrived together — ONE vectorized
        control dispatch instead of per-frame ``offer`` calls, with
        identical decisions/state (thresholds only move on ``tick``, so
        coalescing commutes). Lanes come from ``cams`` or each item's
        ``cam_id``; multiple frames may share a camera (kept in order).

        Returns per-item 'queued' | 'shed_admission' | 'shed_queue'.
        """
        if cams is None:
            lanes = [self.lane(getattr(it, "cam_id", 0)) for it in items]
        else:
            lanes = [int(c) for c in cams]
        C = self.num_cameras
        per_cam: List[List[int]] = [[] for _ in range(C)]
        for i, c in enumerate(lanes):
            per_cam[c].append(i)
        T = max((len(v) for v in per_cam), default=0)
        if T == 0:
            return []
        util = np.zeros((C, T), np.float32)
        present = np.zeros((C, T), bool)
        slot_of: Dict[Tuple[int, int], int] = {}
        batch_items: List[List[Any]] = [[None] * T for _ in range(C)]
        for c in range(C):
            for t, i in enumerate(per_cam[c]):
                util[c, t] = np.float32(utilities[i])
                present[c, t] = True
                batch_items[c][t] = items[i]
                slot_of[(c, t)] = i
        util = sq.flush_subnormal(util)
        self._put_latency_lanes()
        kw = dict(update_cdf=self.update_cdf_online, do_tick=False,
                  min_proc=self.min_proc, budget=self._budget,
                  num_total=self._num_active, tick_cfg=self._tick_cfg)
        agg = None
        if self.mesh is not None:
            self.state, out, agg = fleet.control_step(
                self.state, jnp.asarray(util), jnp.asarray(present),
                mesh=self.mesh, axis=self._cam_axis,
                aggregate=self.fleet_aggregate, **kw)
        else:
            self.state, out = _control_masked_dev(
                self.state, jnp.asarray(util), jnp.asarray(present), **kw)
        res = self._absorb_control(out, batch_items, ticked=False, agg=agg)
        codes = [""] * len(items)
        for (c, t), i in slot_of.items():
            codes[i] = DECISION_NAMES[int(res.decisions[c, t])]
        return codes

    def next_frame(self, cam: Optional[int] = None) -> Optional[Any]:
        """Transmission control: send the best queued frame — of one
        camera, or (default) the best across the whole array."""
        if cam is None:
            self.state, c, seqv = _pop_any_dev(self.state)
        else:
            self.state, c, seqv = _pop_cam_dev(self.state, int(cam))
        c, seqv = int(c), int(seqv)
        if seqv < 0:
            return None
        self._depths[c] -= 1
        item = self._payloads[c].pop(seqv, (c, seqv))
        self.stats.sent += 1
        return item

    def next_frames(self, k: int,
                    cams: Optional[Sequence[int]] = None) -> List[Any]:
        """Batched transmission control: pop the ``k`` best queued
        frames in ONE top-k dispatch — the exact frames (and order) a
        loop of ``next_frame()`` calls would send, without a host sync
        per frame. ``cams`` restricts the pool to those camera lanes
        (default: the whole array). Returns up to ``k`` payloads; fewer
        when the eligible queues drain first. With a session
        ``metrics`` registry the call is a span ``session.pop``; on a
        camera mesh its children are ``session.pop_gather`` and
        ``session.pop_merge``, and ``fleet.pop_candidates`` counts the
        candidates read back (see ``fleet.pop_topk``)."""
        if k <= 0:
            return []
        with self._span("session.pop"):
            rows = None
            if cams is not None:
                rows = np.zeros((self.num_cameras,), bool)
                rows[[int(c) for c in cams]] = True
            st = self.state
            if self.mesh is not None:
                # the registry goes along only when there is one: an
                # unmetered pop keeps pop_topk's plain keywords
                metered = ({} if self.metrics is None
                           else {"metrics": self.metrics})
                self.state, pc, ps = fleet.pop_topk(
                    st, mesh=self.mesh, axis=self._cam_axis, k=int(k),
                    rows=None if rows is None else jnp.asarray(rows),
                    **metered)
            elif rows is None:
                self.state, pc, ps = _pop_topk_dev(st, k=int(k))
            else:
                self.state, pc, ps = _pop_topk_masked_dev(
                    st, jnp.asarray(rows), k=int(k))
            pc, ps = np.asarray(pc), np.asarray(ps)
            items: List[Any] = []
            for c, s in zip(pc.tolist(), ps.tolist()):
                if s < 0:               # -1 padding: pool drained
                    break
                self._depths[c] -= 1
                items.append(self._payloads[c].pop(s, (c, s)))
            self.stats.sent += len(items)
            return items

    def __len__(self) -> int:
        return int(self._depths.sum())

    def queue_depths(self) -> np.ndarray:
        """Live per-camera send-queue depths, ``(C,)`` ints — the
        serving layer's queue-depth observability hook (a host-side
        counter maintained by every push/pop/resize, so reading it
        never transfers the ``(C, K)`` queue lanes off-device)."""
        return self._depths.copy()

    def observed_drop_rate(self, cam: int = 0) -> float:
        """Fraction of camera ``cam``'s history below its threshold."""
        st = self.state
        n = int(st.cdf_len[cam])
        if n == 0:
            return 0.0
        buf = np.asarray(st.cdf_buf)
        return float((buf[cam, :n] < np.asarray(st.threshold)[cam]).mean())

    # -- control loop (Eq. 18–20), vectorized over cameras -------------------

    @property
    def latency_bound(self) -> float:
        return self.query.latency_bound

    def expected_proc(self, cam: Optional[int] = None) -> float:
        """Current backend per-frame latency estimate: camera ``cam``'s
        lane, or (default) the worst lane — the conservative shared
        value every lane carries under scalar reporting."""
        if cam is not None:
            return float(self._proc_q[int(cam)])
        return float(self._proc_q.max(initial=0.0))

    def report_backend_latency(self, proc_latency: float,
                               cam: Optional[int] = None) -> None:
        """Backend-latency metric feed: asymmetric EWMA (overload must
        be detected fast, recovery can be smoothed) on ``(C,)`` lanes.

        A scalar call (``cam=None``) broadcasts to every lane — the
        shared-backend form. Pass ``cam`` to update one camera's lane,
        so heterogeneous backends and sharded fleets estimate latency
        per camera. A lane's first report sets it; later ones move it
        by ``ewma_alpha_up`` (up) or ``ewma_alpha`` (down), every
        operation in float32.

        The fold runs in NumPy on the session's host copy of the lanes
        and makes no device call; the next program that reads the lanes
        (a step, ``offer_batch``, ``tick``, ``fleet_stats``,
        ``checkpoint``) uploads them once, however many reports came
        between. With a session ``metrics`` registry the call is a span
        ``session.report_latency``, and the gauges
        ``session.latency_reports`` and ``session.latency_flushes`` hold
        the running totals of reports and of uploads."""
        with self._span("session.report_latency"):
            x = np.float32(max(float(proc_latency), self.min_proc))
            q, seen = self._proc_q, self._proc_seen
            a = np.where(x > q, np.float32(self.ewma_alpha_up),
                         np.float32(self.ewma_alpha))
            new = np.where(seen, q + a * (x - q), x)
            if cam is None:
                self._set_latency_lanes(new, np.ones_like(seen))
            else:
                upd = np.arange(self.num_cameras) == int(cam)
                self._set_latency_lanes(np.where(upd, new, q), seen | upd)
        self._tally("session.latency_reports")

    def _set_latency_lanes(self, proc_q: np.ndarray,
                           proc_seen: np.ndarray) -> None:
        """Take new host latency lanes (fresh arrays, never written in
        place); they wait for ``_put_latency_lanes``."""
        self._proc_q, self._proc_seen = proc_q, proc_seen
        self._proc_dirty = True

    def _put_latency_lanes(self) -> None:
        """Upload the host latency lanes if a report or a lane reset
        moved them since the last upload: one ``device_put`` of both, on
        their own shardings (the compiled programs' signatures stay as
        they are). Every path that hands the state to a program reading
        the lanes calls this first."""
        if not self._proc_dirty:
            return
        self._proc_dirty = False
        sh = self._shardings
        st = self.state
        st.proc_q, st.proc_seen = jax.device_put(
            (self._proc_q, self._proc_seen),
            None if sh is None else (sh["proc_q"], sh["proc_seen"]))
        self._tally("session.latency_flushes")

    def _tally(self, name: str) -> None:
        """Add one to the running total ``name``, a gauge of the session
        registry (its counters are the per-step set that
        ``bench/session_split.py`` reports)."""
        if self.metrics is not None:
            g = self.metrics.gauge(name)
            g.set(g.value + 1)

    def report_ingress_fps(self, fps: float, cam: Optional[int] = None) -> None:
        """Observed ingress rate: per camera, or an aggregate rate split
        evenly across the array's lanes."""
        st = self.state
        if cam is None:
            x = jnp.full((self.num_cameras,), float(fps) / self.num_cameras)
            upd = jnp.ones((self.num_cameras,), bool)
        else:
            x = jnp.where(jnp.arange(self.num_cameras) == cam, float(fps),
                          st.fps_obs)
            upd = jnp.arange(self.num_cameras) == cam
        ew = st.fps_obs + self.ewma_alpha * (x - st.fps_obs)
        st.fps_obs = jnp.where(upd, jnp.where(st.fps_seen, ew, x),
                               st.fps_obs).astype(jnp.float32)
        st.fps_seen = st.fps_seen | upd

    def tick(self) -> Dict[str, Any]:
        """Re-derive per-camera thresholds (Eq. 17–19) and queue sizes
        (Eq. 20) from the current metric lanes — one batched quantile +
        queue resize over all C camera lanes."""
        self._put_latency_lanes()
        kw = dict(min_proc=self.min_proc, budget=self._budget,
                  num_total=self._num_active, tick_cfg=self._tick_cfg)
        if self.cascade is not None:
            self.state, rates, resize_ev = _cascade_tick_dev(
                self.state, gate_fraction=self._gate_fraction, **kw)
        elif self.mesh is not None:
            self.state, rates, resize_ev = fleet.tick(
                self.state, mesh=self.mesh, axis=self._cam_axis, **kw)
        else:
            self.state, rates, resize_ev = _tick_dev(self.state, **kw)
        rates, resize_ev = np.asarray(rates), np.asarray(resize_ev)
        cnt = (resize_ev >= 0).sum(axis=1)
        self.stats.dropped_queue += int(cnt.sum())
        self.per_camera_dropped += cnt
        self._depths -= cnt
        # one flat pass over the eviction events instead of a nested
        # per-camera Python loop (resize_ev is (C, K), -1 padded)
        ev_c, ev_k = np.nonzero(resize_ev >= 0)
        for c, s in zip(ev_c.tolist(), resize_ev[ev_c, ev_k].tolist()):
            self._payloads[c].pop(int(s), None)
        st = self.state
        threshold = np.asarray(st.threshold)
        # report the EFFECTIVE queue sizes: Eq. 20's cap clipped to the
        # physical (C, K) lane bound the queues actually honor
        queue_cap = np.minimum(np.asarray(st.queue_cap), self.queue_capacity)
        finite = np.isfinite(threshold)
        # aggregate over LIVE lanes only — detached lanes carry rate 0 /
        # threshold +inf and would skew the means (all-active: identical)
        act = self._active_host
        snap = {
            "target_drop_rate": float(rates[act].mean()) if act.any()
            else 0.0,
            "threshold": float(threshold[finite].mean()) if finite.any()
            else -np.inf,
            "queue_size": int(queue_cap.max()),
            "per_camera": {
                "target_drop_rate": rates.tolist(),
                "threshold": threshold.tolist(),
                "queue_size": queue_cap.tolist(),
            },
        }
        if self.cascade is not None:
            s2_th = np.asarray(st.s2_threshold)
            fin2 = np.isfinite(s2_th)
            snap["s2_threshold"] = (float(s2_th[fin2].mean())
                                    if fin2.any() else -np.inf)
            snap["per_camera"]["s2_threshold"] = s2_th.tolist()
        return snap

    # -- checkpoint / restore (serve-path state) -----------------------------

    def _model_arrays(self) -> Dict[str, np.ndarray]:
        """The trained utility model as fixed-shape arrays (zeros when
        untrained) so one checkpoint template covers both cases."""
        q = self.query
        nc = q.num_colors
        if self.model is not None:
            return {"model_M_pos": np.asarray(self.model.M_pos, np.float32),
                    "model_M_neg": np.asarray(self.model.M_neg, np.float32),
                    "model_norm": np.asarray(self.model.norm, np.float32)}
        return {"model_M_pos": np.zeros((nc, q.bs, q.bv), np.float32),
                "model_M_neg": np.zeros((nc, q.bs, q.bv), np.float32),
                "model_norm": np.zeros((nc,), np.float32)}

    def checkpoint(self, path, step: int = 0, *, async_: bool = False):
        """Persist the SessionState pytree (plus the trained utility
        model) via ``repro.train.checkpoint`` (atomic, async-capable).
        Queue lanes persist; queued frame *payloads* are live host
        objects and do not — restored queue entries fall back to
        ``(cam, seq)`` pairs. Camera-sharded lanes are gathered to host
        as global ``(C, ...)`` arrays, so the checkpoint is
        mesh-independent: ``restore`` re-shards onto the restoring
        session's mesh, whatever its device count."""
        from repro.train import checkpoint as ckpt
        self._put_latency_lanes()
        meta = {
            "kind": "shed_session",
            "num_cameras": self.num_cameras,
            "colors": [c.name for c in self.query.colors],
            "op": self.query.op,
            "npix": int(self.state.bg.shape[1]),
            "has_model": self.model is not None,
            "model_op": self.model.op if self.model is not None else "",
            # camera-id -> lane map, restored so a resumed session keeps
            # serving the same external ids (ids must be msgpack-able —
            # ints/strings; np ints are coerced)
            "lane_map": [[int(k) if isinstance(k, (int, np.integer))
                          else k, int(v)]
                         for k, v in sorted(self._lane_of.items(),
                                            key=lambda kv: kv[1])],
        }
        tree = {**self.state.as_dict(), **self._model_arrays()}
        return ckpt.save(path, step, tree, metadata=meta, async_=async_)

    def restore(self, path,
                step: Optional[int] = None) -> Tuple[int, Dict[str, Any]]:
        """Load a SessionState checkpoint into this session. The session
        must have matching lane shapes (same ``num_cameras``; pass
        ``frame_shape`` to ``open_session`` so the background lanes are
        allocated before restoring)."""
        from repro.train import checkpoint as ckpt
        tree = {**self.state.as_dict(), **self._model_arrays()}
        template = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                    for k, v in tree.items()}
        out, step, meta = ckpt.restore(path, template, step=step)
        # queued payloads are live host objects of the PREVIOUS life of
        # this session; restored queue entries must not alias them (seq
        # numbers restart/collide across checkpoints)
        self._payloads = [{} for _ in range(self.num_cameras)]
        for k in self.state.as_dict():
            if self._shardings is not None:
                # re-shard the global (C, ...) checkpoint arrays onto
                # THIS session's mesh — which may hold a different
                # device count than the mesh that saved them
                leaf = jax.device_put(np.asarray(out[k]),
                                      self._shardings[k])
            else:
                leaf = jnp.asarray(out[k])
            setattr(self.state, k, leaf)
        self._set_latency_lanes(np.array(out["proc_q"], np.float32),
                                np.array(out["proc_seen"], bool))
        if meta.get("has_model"):
            self.model = UtilityModel(
                self.query.colors, np.asarray(out["model_M_pos"]),
                np.asarray(out["model_M_neg"]),
                np.asarray(out["model_norm"]),
                meta.get("model_op") or self.query.op)
        # rebuild the churn bookkeeping from the restored state + meta
        lane_map = meta.get("lane_map")
        if lane_map is not None:
            self._lane_of = {k: int(v) for k, v in lane_map}
            used = set(self._lane_of.values())
            self._free_lanes = [l for l in range(self.num_cameras)
                                if l not in used]
            heapq.heapify(self._free_lanes)
        self._active_host = np.asarray(self.state.active, bool).copy()
        self._num_active = int(self._active_host.sum())
        self._depths = (np.asarray(self.state.q_seq) >= 0).sum(
            axis=1).astype(np.int64)
        floors = np.asarray(self.state.rate_floor)
        self._rate_floor_host = float(floors.max()) if floors.size else 0.0
        return step, meta


def open_session(query: Query, num_cameras: int = 1, **kw: Any) -> ShedSession:
    """Open a ShedSession for ``num_cameras`` cameras running ``query``.

    Keyword options: ``frame_shape=(H, W)`` (pre-allocates background
    lanes, required before ``restore``), ``model`` (a trained
    UtilityModel; or call ``session.fit``), ``train_utilities`` (seeds
    the admission CDFs), ``queue_size`` (initial per-camera queue cap),
    ``queue_capacity`` (the physical (C, K) lane bound the dynamic cap
    is clipped to), ``latency_inputs``, ``cdf_window``,
    and ``impl``/``interpret`` (ingest dispatch overrides). The control
    plane is one set of jitted XLA programs with donated state buffers
    on every backend; ``serve`` is accepted as ``None`` or
    ``"device"``.

    Fleet scale-out: ``shard_cameras=True`` (or ``mesh=some_mesh``)
    shards the camera lanes over a device mesh via ``repro.core.fleet``
    — ``step``/``tick``/``offer_batch`` become shard_map'd programs with
    zero cross-device collectives on the hot path, bit-identical to the
    unsharded device step; ``fleet_aggregate=True`` adds one small psum
    of global shed/queue/backend stats per step (``last_fleet_stats``,
    ``fleet_stats()``). ``num_cameras`` must divide evenly over the
    mesh's camera axis.

    Observability: ``metrics=MetricsRegistry()``
    (``repro.serve.metrics``) times the serve path in host spans
    (``session.step`` and its phases, ``session.pop``,
    ``session.report_latency``; their ``span.*`` histograms reach
    ``metrics.report()``) and counts ``session.steps``,
    ``session.frames`` and ``session.staged_bytes`` (the float32
    frames a device frames step stages for the kernel; the frames
    cross in the camera's dtype and are converted on the chip). A
    ``ServeService`` over the session reports into the same registry.
    Spans only read
    the clock around calls that block anyway; without a registry
    (the default) none is taken.
    """
    return ShedSession(query, num_cameras, **kw)


__all__ = [
    "ADMIT", "SHED_ADMISSION", "SHED_QUEUE", "SHED_CASCADE",
    "IngestResult", "Query", "SessionState", "ShedSession", "StepResult",
    "open_session",
]
