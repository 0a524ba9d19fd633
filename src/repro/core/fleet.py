"""Fleet-scale sharded serving: the camera axis of a ShedSession laid
out over a device mesh.

A ``SessionState`` is an all-array pytree of per-camera lanes — ``(C,
N)`` backgrounds, ``(C, W)`` CDF rings, ``(C, K)`` queue lanes, ``(C,)``
thresholds/EWMAs — and every hot-path operation (admission, CDF
maintenance, queue selection, the Eq. 17–20 control tick) is row-local:
camera ``c``'s outputs depend only on camera ``c``'s lanes. That makes
the serve plane embarrassingly parallel over cameras, which is exactly
the shape ``shard_map`` wants: shard the leading ``C`` dimension over a
mesh axis and run the *same* per-camera program shard-locally with
**zero cross-device collectives on the hot path**.

The one quantity that is NOT shard-local is Eq. 19's service-time
multiplier — the target drop rate ``r = 1 - 1/(p * C * fps)`` uses the
number of cameras sharing the backend, which is the GLOBAL camera
count. It is a static constant of the session, so it is baked into the
shard program (``num_total``) rather than communicated; every shard
derives bit-identical rates to the unsharded program.

The only collective is one small optional ``psum`` tree (fleet
aggregates: global offered/admitted/shed counts, queue depth, backend
load, threshold stats) appended to the step for fleet-level
observability and the control loop's measured-latency feed.

Physical layout goes through the ``repro.sharding.api`` rules table:
the logical ``"camera"`` axis resolves to a dedicated ``"camera"`` mesh
axis (``fleet_mesh``), or falls back to a pure-DP axis so a fleet can
ride an existing training mesh. Scalar leaves (``bg_valid``) replicate.

Checkpoints are mesh-independent: ``ShedSession.checkpoint`` gathers
every lane to host (global ``(C, ...)`` arrays), and ``restore``
re-shards onto whatever mesh the restoring session holds — including a
*different* device count than the one that saved.

Entry point: ``open_session(query, C, shard_cameras=True)`` or
``open_session(query, C, mesh=my_mesh)``; everything here is the
machinery behind it.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.control_plane import (
    ADMIT,
    SessionState,
    control_core,
    no_span,
    tick_core,
)
from repro.kernels.hsv_features.ops import ingest_core
from repro.sharding.api import auto_mesh, resolve_axis

AxisName = Union[str, Tuple[str, ...]]

CAMERA_AXIS = "camera"

# SessionState leaves WITHOUT a leading camera lane (replicated).
_SCALAR_LEAVES = ("bg_valid",)


def fleet_mesh(num_devices: Optional[int] = None,
               axis_name: str = CAMERA_AXIS) -> Mesh:
    """A 1-D mesh over ``num_devices`` (default: all) devices whose
    single axis carries the camera dimension."""
    n = len(jax.devices()) if num_devices is None else int(num_devices)
    return auto_mesh((n,), (axis_name,))


def mesh_axis_size(mesh: Mesh, axis: AxisName) -> int:
    axes = (axis,) if isinstance(axis, str) else tuple(axis)
    return int(np.prod([mesh.shape[a] for a in axes]))


def camera_axis(mesh: Mesh, num_cameras: int, rules=None) -> AxisName:
    """Resolve the physical mesh axis (or axis tuple) carrying the
    logical ``"camera"`` dimension, via the sharding rules table.

    Raises if no mesh axis divides ``num_cameras`` — camera sharding
    needs an even split (pad the session's camera count to a multiple
    of the mesh size; idle lanes are cheap, uneven shards are not
    expressible as one shard_map program).
    """
    axis = resolve_axis("camera", int(num_cameras), mesh, set(), rules)
    if axis is None:
        raise ValueError(
            f"cannot shard {num_cameras} cameras over mesh "
            f"{dict(mesh.shape)}: no axis divides the camera count "
            f"(pad num_cameras to a multiple of the mesh axis size)")
    return axis


def state_pspecs(state_or_cls, axis: AxisName = CAMERA_AXIS):
    """A SessionState-shaped pytree of PartitionSpecs: every camera-lane
    leaf sharded on ``axis`` along dim 0, scalar leaves replicated."""
    fields = dataclasses.fields(state_or_cls)
    cls = state_or_cls if isinstance(state_or_cls, type) \
        else type(state_or_cls)
    return cls(**{f.name: (P() if f.name in _SCALAR_LEAVES else P(axis))
                  for f in fields})


def state_shardings(mesh: Mesh, state,
                    axis: AxisName = CAMERA_AXIS) -> Dict[str, NamedSharding]:
    """Per-leaf NamedShardings, keyed by SessionState field name."""
    specs = state_pspecs(state, axis)
    return {f.name: NamedSharding(mesh, getattr(specs, f.name))
            for f in dataclasses.fields(state)}


def shard_state(state, mesh: Mesh, axis: AxisName = CAMERA_AXIS):
    """Lay a SessionState out over the mesh (host or device input)."""
    sh = state_shardings(mesh, state, axis)
    return type(state)(**{
        name: jax.device_put(jnp.asarray(getattr(state, name)), s)
        for name, s in sh.items()})


def frame_sharding(mesh: Mesh, axis: AxisName = CAMERA_AXIS) -> NamedSharding:
    """Where a ``(C, T, ...)`` frame window goes: each camera's frames on
    the device that holds its lanes."""
    return NamedSharding(mesh, P(axis))


def gather_state(state):
    """Pull every lane back to host as global NumPy arrays (the
    checkpoint form; mesh-independent)."""
    return type(state)(**{
        f.name: np.asarray(getattr(state, f.name))
        for f in dataclasses.fields(state)})


# ---------------------------------------------------------------------------
# Fleet aggregates — the ONE collective (small psum tree, off the
# row-local hot path)
# ---------------------------------------------------------------------------

def _local_aggregates(state, axis: AxisName, decisions=None):
    """Shard-local stats reduced with one psum each — global scalars,
    replicated across the mesh."""
    psum = functools.partial(jax.lax.psum, axis_name=axis)
    finite = jnp.isfinite(state.threshold)
    agg = {
        "queue_depth": psum((state.q_seq >= 0).sum().astype(jnp.int32)),
        "cdf_fill": psum(state.cdf_len.sum().astype(jnp.int32)),
        "proc_q_sum": psum(state.proc_q.sum().astype(jnp.float32)),
        "fps_obs_sum": psum(state.fps_obs.sum().astype(jnp.float32)),
        "threshold_finite": psum(finite.sum().astype(jnp.int32)),
        "threshold_sum": psum(jnp.where(finite, state.threshold, 0.0)
                              .sum().astype(jnp.float32)),
    }
    if decisions is not None:
        agg["offered"] = psum((decisions >= 0).sum().astype(jnp.int32))
        agg["admitted"] = psum((decisions == ADMIT).sum().astype(jnp.int32))
        agg["shed"] = psum((decisions > ADMIT).sum().astype(jnp.int32))
    return agg


def _empty_aggregates(with_decisions: bool):
    z32, zf = jnp.int32(0), jnp.float32(0)
    agg = {"queue_depth": z32, "cdf_fill": z32, "proc_q_sum": zf,
           "fps_obs_sum": zf, "threshold_finite": z32, "threshold_sum": zf}
    if with_decisions:
        agg.update(offered=z32, admitted=z32, shed=z32)
    return agg


def derive_fleet_stats(agg: Dict[str, Any],
                       num_cameras: int) -> Dict[str, float]:
    """Host-side view of a psum aggregate tree: global rates/means."""
    a = {k: float(np.asarray(v)) for k, v in agg.items()}
    out = {
        "queue_depth": int(a["queue_depth"]),
        "cdf_fill": int(a["cdf_fill"]),
        "proc_q_mean": a["proc_q_sum"] / num_cameras,
        "fps_obs_mean": a["fps_obs_sum"] / num_cameras,
        "threshold_mean": (a["threshold_sum"] / a["threshold_finite"]
                           if a["threshold_finite"] else -np.inf),
    }
    if "offered" in a:
        out.update(
            offered=int(a["offered"]), admitted=int(a["admitted"]),
            shed=int(a["shed"]),
            shed_rate=(a["shed"] / a["offered"] if a["offered"] else 0.0))
    return out


# ---------------------------------------------------------------------------
# The sharded serve plane — the control plane's cores shard_map'd over
# the camera axis. Row-local math only; num_total keeps Eq. 19 global.
# ---------------------------------------------------------------------------

def _out_pspecs(axis: AxisName, with_decisions: bool):
    ctrl = {"decisions": P(axis), "pushed_seq": P(axis),
            "evicted_resident": P(axis), "push_evictions": P(axis),
            "rates": P(axis), "resize_evicted": P(axis)}
    agg = {k: P() for k in _empty_aggregates(with_decisions)}
    return ctrl, agg


@functools.partial(
    jax.jit,
    static_argnames=("mesh", "axis", "num_total", "masked", "update_cdf",
                     "do_tick", "min_proc", "budget", "aggregate",
                     "tick_cfg"),
    donate_argnames=("state",))
def _fleet_control(state, util, present, *, mesh, axis, num_total, masked,
                   update_cdf, do_tick, min_proc, budget, aggregate,
                   tick_cfg=None):
    """Sharded control step: CDF push -> admission -> queue selection ->
    (optional) tick, each camera shard running the identical row-local
    program; one optional psum aggregate tree rides along."""
    st_spec = state_pspecs(SessionState, axis)
    ctrl_spec, agg_spec = _out_pspecs(axis, True)

    def local(st, u, pres):
        st, out = control_core(
            st, u, pres if masked else None, update_cdf=update_cdf,
            do_tick=do_tick, min_proc=min_proc, budget=budget,
            num_total=num_total, tick_cfg=tick_cfg)
        agg = (_local_aggregates(st, axis, out["decisions"]) if aggregate
               else _empty_aggregates(True))
        return st, out, agg

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(st_spec, P(axis), P(axis)),
        out_specs=(st_spec, ctrl_spec, agg_spec),
        check_vma=False)(state, util, present)


@functools.partial(
    jax.jit,
    static_argnames=("mesh", "axis", "num_total", "hue_ranges", "bs", "bv",
                     "alpha", "fg_threshold", "use_fg", "bg_valid", "op",
                     "impl", "interpret", "update_cdf", "do_tick",
                     "min_proc", "budget", "aggregate", "tick_cfg"),
    donate_argnames=("state",))
def _fleet_serve_step(state, frames, M_pos, norm, *, mesh, axis, num_total,
                      hue_ranges, bs, bv, alpha, fg_threshold, use_fg,
                      bg_valid, op, impl, interpret, update_cdf, do_tick,
                      min_proc, budget, aggregate, tick_cfg=None):
    """The sharded tentpole program: fused ingest -> control, each
    camera shard one self-contained device program (the ingest kernel's
    per-camera background/gain lanes are row-local too). ``frames``:
    ``(C, T, H, W, 3)`` laid out as ``frame_sharding`` places them, or
    already flattened to ``(C, T, H*W, 3)``; any dtype (the ingest's
    first relayout converts to float32)."""
    st_spec = state_pspecs(SessionState, axis)
    ctrl_spec, agg_spec = _out_pspecs(axis, True)

    def local(st, fr, mp, nm):
        # (c, T, H, W, 3) -> (c, T, H*W, 3) on this shard's own frames
        # (a no-op for frames that come flattened)
        with jax.named_scope("shed.stage"):
            fr = fr.reshape(fr.shape[0], fr.shape[1], -1, 3)
        bg0 = st.bg if bg_valid else jnp.zeros_like(st.bg)
        gain0 = st.gain if bg_valid else jnp.ones_like(st.gain)
        _, _, _, util, bg, gain = ingest_core(
            fr, bg0, gain0, mp, nm, hue_ranges=hue_ranges, bs=bs, bv=bv,
            alpha=alpha, threshold=fg_threshold, use_fg=use_fg,
            bg_valid=bg_valid, op=op, impl=impl, interpret=interpret)
        st = dataclasses.replace(st, bg=bg, gain=gain,
                                 bg_valid=jnp.asarray(True))
        with jax.named_scope("shed.control"):
            st, out = control_core(
                st, util, None, update_cdf=update_cdf, do_tick=do_tick,
                min_proc=min_proc, budget=budget, num_total=num_total,
                tick_cfg=tick_cfg)
        agg = (_local_aggregates(st, axis, out["decisions"]) if aggregate
               else _empty_aggregates(True))
        return st, out, agg

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(st_spec, P(axis), P(), P()),
        out_specs=(st_spec, ctrl_spec, agg_spec),
        check_vma=False)(state, frames, M_pos, norm)


@functools.partial(
    jax.jit,
    static_argnames=("mesh", "axis", "num_total", "min_proc", "budget",
                     "tick_cfg"),
    donate_argnames=("state",))
def _fleet_tick(state, *, mesh, axis, num_total, min_proc, budget,
                tick_cfg=None):
    """Sharded Eq. 18–20 tick: per-shard batched quantile (O(bins) on
    the incremental bucket counts by default) + queue resize; rates use
    the GLOBAL camera count."""
    st_spec = state_pspecs(SessionState, axis)

    def local(st):
        st, rates, resize_ev = tick_core(st, min_proc, budget, num_total,
                                         tick_cfg=tick_cfg)
        return st, rates, resize_ev

    return jax.shard_map(
        local, mesh=mesh, in_specs=(st_spec,),
        out_specs=(st_spec, P(axis), P(axis)),
        check_vma=False)(state)


# ---------------------------------------------------------------------------
# Sharded batched pop — per-shard-local top-k candidate selection, one
# small host gather to pick the global best, one donated scatter to
# clear the popped slots. Top-k is NOT row-local (the global best k
# frames may all live on one shard), so each shard over-produces
# min(k, C_local*K) candidates — a superset of its contribution to the
# global top-k — and the merge is exact.
# ---------------------------------------------------------------------------

def _shard_offset(mesh: Mesh, axis: AxisName, c_local: int):
    """Global camera index of this shard's lane 0 (traced, inside
    shard_map): shard index along ``axis`` (row-major over axis tuples)
    times the local camera count."""
    axes = (axis,) if isinstance(axis, str) else tuple(axis)
    idx = jnp.int32(0)
    for a in axes:
        idx = idx * jnp.int32(mesh.shape[a]) + \
            jax.lax.axis_index(a).astype(jnp.int32)
    return idx * jnp.int32(c_local)


@functools.partial(jax.jit, static_argnames=("mesh", "axis", "kk"))
def _fleet_pop_candidates(q_util, q_seq, rows, *, mesh, axis, kk):
    """Per-shard top-kk candidates: (S*kk,) sort keys + global camera /
    seq / slot ids, shard-local sort only (no collectives)."""

    def local(util, seq, rowmask):
        cl, K = util.shape
        valid = (seq >= 0) & rowmask[:, None]
        # canonicalize ±0.0 (u + 0.0) so the float total order used by
        # lax.sort matches pop_best's IEEE == tiebreak on signed zeros
        nu = jnp.where(valid, -(util + jnp.float32(0.0)),
                       jnp.inf).reshape(-1)
        off = _shard_offset(mesh, axis, cl)
        cams = (jnp.broadcast_to(
            jnp.arange(cl, dtype=jnp.int32)[:, None], (cl, K))
            .reshape(-1) + off)
        seqs = jnp.where(valid, seq,
                         jnp.int32(2**31 - 1)).reshape(-1)
        slots = jnp.broadcast_to(
            jnp.arange(K, dtype=jnp.int32)[None, :], (cl, K)).reshape(-1)
        nu_s, cam_s, seq_s, slot_s = jax.lax.sort(
            (nu, cams, seqs, slots), num_keys=3)
        return nu_s[:kk], cam_s[:kk], seq_s[:kk], slot_s[:kk]

    with jax.named_scope("shed.pop"):
        return jax.shard_map(
            local, mesh=mesh, in_specs=(P(axis), P(axis), P(axis)),
            out_specs=(P(axis), P(axis), P(axis), P(axis)),
            check_vma=False)(q_util, q_seq, rows)


@functools.partial(jax.jit, static_argnames=("mesh", "axis"),
                   donate_argnames=("state",))
def _fleet_pop_clear(state, gcam, slot, *, mesh, axis):
    """Clear the popped (global camera, slot) entries shard-locally:
    the (gcam, slot) lists are replicated; each shard scatters only the
    rows it owns (out-of-range rows drop)."""
    st_spec = state_pspecs(SessionState, axis)

    def local(st, gc, sl):
        cl, K = st.q_util.shape
        lc = gc - _shard_offset(mesh, axis, cl)
        ok = (lc >= 0) & (lc < cl) & (sl >= 0)
        ic = jnp.where(ok, lc, cl)          # OOB -> dropped scatter
        isl = jnp.where(ok, sl, K)
        q_util = st.q_util.at[ic, isl].set(-jnp.inf, mode="drop")
        q_seq = st.q_seq.at[ic, isl].set(-1, mode="drop")
        return dataclasses.replace(st, q_util=q_util, q_seq=q_seq)

    with jax.named_scope("shed.pop"):
        return jax.shard_map(
            local, mesh=mesh, in_specs=(st_spec, P(), P()),
            out_specs=st_spec, check_vma=False)(state, gcam, slot)


def pop_topk(state, *, mesh, axis, k, rows=None, metrics=None):
    """Pop the global best ``k`` queued frames from a camera-sharded
    session — the exact frames (and order) ``pop_best`` would produce
    sequentially. Returns ``(new_state, cams, seqs)`` with ``(k,)``
    int32 outputs, -1 padded when the eligible queues drain.

    ``rows``: optional global ``(C,)`` bool lane mask. ``metrics``: the
    session's ``MetricsRegistry``; with one, the pop's halves are the
    spans ``session.pop_gather`` (the per-shard candidates program and
    their read-back) and ``session.pop_merge`` (the host merge and the
    clear's dispatch), and ``fleet.pop_candidates`` counts the
    ``S * kk`` candidates read back."""
    span = metrics.span if metrics is not None else no_span
    C, K = state.q_util.shape
    S = mesh_axis_size(mesh, axis)
    k = int(k)
    kk = min(k, (C // S) * K)
    if rows is None:
        rows = jnp.ones((C,), bool)
    with span("session.pop_gather"):
        nu, gcam, seq, slot = _fleet_pop_candidates(
            state.q_util, state.q_seq, rows, mesh=mesh, axis=axis, kk=kk)
        nu, gcam = np.asarray(nu), np.asarray(gcam)
        seq, slot = np.asarray(seq), np.asarray(slot)
    with span("session.pop_merge"):
        fin = np.flatnonzero(nu < np.inf)
        # exact global pop order: utility desc (nu asc; ±0 canonicalized
        # on device), then camera asc, then seq asc — lexsort's IEEE
        # compare agrees with the device total order on this key set
        order = fin[np.lexsort((seq[fin], gcam[fin], nu[fin]))]
        m = min(k, order.size)
        sel = order[:m]
        cams_out = np.full((k,), -1, np.int32)
        seqs_out = np.full((k,), -1, np.int32)
        cams_out[:m], seqs_out[:m] = gcam[sel], seq[sel]
        gc = np.full((k,), C, np.int32)       # OOB pad -> dropped scatter
        sl = np.full((k,), K, np.int32)
        gc[:m], sl[:m] = gcam[sel], slot[sel]
        new_state = _fleet_pop_clear(state, jnp.asarray(gc),
                                     jnp.asarray(sl), mesh=mesh, axis=axis)
    if metrics is not None:
        metrics.counter("fleet.pop_candidates").inc(S * kk)
    return new_state, cams_out, seqs_out


@functools.partial(jax.jit, static_argnames=("mesh", "axis"))
def _fleet_aggregates(state, *, mesh, axis):
    st_spec = state_pspecs(SessionState, axis)
    agg_spec = {k: P() for k in _empty_aggregates(False)}
    return jax.shard_map(
        lambda st: _local_aggregates(st, axis), mesh=mesh,
        in_specs=(st_spec,), out_specs=agg_spec,
        check_vma=False)(state)


# -- python-facing wrappers (keyword plumbing, mesh/axis hashability) -------

def control_step(state, util, present=None, *, mesh, axis, num_total,
                 update_cdf, do_tick, min_proc, budget, aggregate=False,
                 tick_cfg=None):
    masked = present is not None
    if present is None:
        present = jnp.ones(util.shape, bool)
    return _fleet_control(
        state, util, present, mesh=mesh, axis=axis, num_total=num_total,
        masked=masked, update_cdf=update_cdf, do_tick=do_tick,
        min_proc=min_proc, budget=budget, aggregate=aggregate,
        tick_cfg=tick_cfg)


def serve_step(state, frames, M_pos, norm, **kw):
    return _fleet_serve_step(state, frames, M_pos, norm, **kw)


def tick(state, *, mesh, axis, num_total, min_proc, budget, tick_cfg=None):
    return _fleet_tick(state, mesh=mesh, axis=axis, num_total=num_total,
                       min_proc=min_proc, budget=budget, tick_cfg=tick_cfg)


def aggregates(state, *, mesh, axis, num_cameras: int) -> Dict[str, float]:
    """Run the standalone observability psum over the sharded state."""
    return derive_fleet_stats(
        _fleet_aggregates(state, mesh=mesh, axis=axis), num_cameras)


__all__ = [
    "CAMERA_AXIS", "aggregates", "camera_axis", "control_step",
    "derive_fleet_stats", "fleet_mesh", "frame_sharding", "gather_state",
    "mesh_axis_size", "pop_topk", "serve_step", "shard_state", "state_pspecs",
    "state_shardings", "tick",
]
