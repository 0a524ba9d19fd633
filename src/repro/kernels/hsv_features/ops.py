"""Public wrappers around the fused HSV ingest kernels.

``ingest_pipeline`` is the camera-side hot path: a ``(T, H, W, 3)`` RGB
frame batch — or a whole camera array ``(C, T, H, W, 3)`` — goes
device-side *once* and comes back as PF matrices, hue fractions and
(when a trained model is supplied) utility scores, with the per-camera
background-subtraction state ``IngestState`` carried explicitly across
calls (chunked streaming scores identically to one long batch).

Implementation dispatch is backend-aware: the Pallas kernel on TPU, the
jitted pure-jnp oracle (one XLA computation, same math) elsewhere —
Pallas has no compiled CPU lowering, and interpret mode is a debugging
tool, not a serving path. ``impl``/``interpret`` can be forced for
testing.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from repro.core.colors import Color
from repro.core.utility import B_S, B_V, UtilityModel
from repro.kernels import default_interpret
from repro.kernels.hsv_features.kernel import hsv_hist, ingest_batch
from repro.kernels.hsv_features.ref import ingest_batch_ref, pf_from_counts


def frame_pf(rgb, fg, colors: Sequence[Color], bs: int = B_S, bv: int = B_V,
             interpret: Optional[bool] = None):
    """One frame -> (pf (nc, bs, bv), hue_fraction (nc,)).

    rgb: (H, W, 3) float32 (0..255); fg: (H, W) bool.
    """
    hue_ranges = tuple(tuple(c.hue_ranges) for c in colors)
    n = rgb.shape[0] * rgb.shape[1]
    counts, totals, fgtot = hsv_hist(rgb.reshape(n, 3), fg.reshape(n),
                                     hue_ranges, bs, bv, interpret=interpret)
    pf = pf_from_counts(counts, totals, bs, bv)
    hf = totals / jnp.maximum(fgtot, 1.0)
    return pf, hf


def batch_pf(rgb, fg, colors: Sequence[Color], bs: int = B_S, bv: int = B_V,
             interpret: Optional[bool] = None):
    """(T, H, W, 3) -> (pf (T, nc, bs, bv), hf (T, nc)) via vmap."""
    f = functools.partial(frame_pf, colors=colors, bs=bs, bv=bv,
                          interpret=interpret)
    return jax.vmap(lambda a, b: f(a, b))(rgb, fg)


# ---------------------------------------------------------------------------
# Fused batched ingest
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class IngestState:
    """Background-model state carried across ingest batches.

    Single-camera states are ``bg (N,), gain ()``; a camera array
    carries one state lane per camera: ``bg (C, N), gain (C,)``.
    """
    bg: jax.Array          # (N,) / (C, N) Value-channel background
    gain: jax.Array        # () / (C,) illumination gain estimate

    @property
    def num_cameras(self) -> Optional[int]:
        """Camera-lane count, or None for a single-camera state."""
        return self.bg.shape[0] if self.bg.ndim == 2 else None


def ingest_core(rgb, bg0, gain0, M_pos, norm, *, hue_ranges, bs, bv,
                alpha, threshold, use_fg, bg_valid, op, impl, interpret,
                width: int = 0):
    """Traceable fused-ingest dispatch — the raw kernel/oracle call with
    NO host-side jit wrapper of its own, so callers building larger
    device programs (e.g. the session's fused serve step) can trace it
    inline and keep everything in ONE dispatch.

    rgb: (T, N, 3) or (C, T, N, 3) frames flattened to pixels, RGB in
    [0, 255], in float32 or the camera's dtype: both implementations
    convert to float32 on the device before any arithmetic (exact for
    uint8, so both score alike). Returns
    the kernel tuple (counts, totals, fg_total, utility, bg, gain);
    ``width > 0`` appends the per-frame foreground bounding box (the
    cascade's ROI — see ``foreground_bbox``).
    """
    if impl == "pallas":
        return ingest_batch(
            rgb, bg0, gain0, M_pos, norm, hue_ranges, bs, bv, alpha=alpha,
            threshold=threshold, use_fg=use_fg, bg_valid=bg_valid, op=op,
            interpret=interpret, width=width)
    if impl == "jnp":
        return ingest_batch_ref(
            rgb, bg0, gain0, M_pos, norm, hue_ranges, bs, bv, alpha=alpha,
            threshold=threshold, use_fg=use_fg, bg_valid=bg_valid, op=op,
            width=width)
    raise ValueError(f"unknown ingest impl {impl!r}")


_ingest_jnp = jax.jit(
    functools.partial(ingest_core, impl="jnp", interpret=None),
    static_argnames=("hue_ranges", "bs", "bv", "alpha", "threshold",
                     "use_fg", "bg_valid", "op", "width"))


def default_impl() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "jnp"


def query_constants(model, nc: int, bs: int, bv: int, op: Optional[str]):
    """Resolve the (M_pos, norm, op) constants a compiled shedder bakes
    in: the trained model's matrices and composition op when present,
    inert zeros/ones (utilities identically 0) otherwise.
    """
    if model is not None:
        M_pos = jnp.asarray(model.M_pos, jnp.float32).reshape(nc, bs * bv)
        norm = jnp.asarray(model.norm, jnp.float32)
        # the trained model defines how per-color utilities compose; a
        # caller-supplied op (e.g. the label op) must not override it
        op = model.op
    else:
        M_pos = jnp.zeros((nc, bs * bv), jnp.float32)
        norm = jnp.ones((nc,), jnp.float32)
        op = op or "or"
    if op == "single":
        op = "or"
    if op not in ("or", "and"):
        raise ValueError(f"unknown composition op {op!r}")
    return M_pos, norm, op


def ingest_pipeline(rgb, colors: Sequence[Color],
                    model: Optional[UtilityModel] = None, *,
                    state: Optional[IngestState] = None,
                    alpha: float = 0.05, threshold: float = 18.0,
                    use_foreground: bool = True, op: Optional[str] = None,
                    bs: int = B_S, bv: int = B_V,
                    impl: Optional[str] = None,
                    interpret: Optional[bool] = None,
                    with_bbox: bool = False):
    """Fused ingest for one frame batch — one device dispatch.

    rgb: (T, H, W, 3) float32 RGB in [0, 255], or (C, T, H, W, 3) for a
    C-camera array (state then carries per-camera ``(bg, gain)`` lanes).
    Returns (pf (T, nc, bs, bv), hf (T, nc), util (T,) | None, state'),
    each with a leading camera lane iff the input had one. ``util`` is
    None when no trained ``model`` is supplied. ``with_bbox=True``
    appends the per-frame foreground bounding box (``(T, 4)`` int32,
    all -1 when the mask is empty) — the semantic cascade's free ROI.
    """
    impl = impl or default_impl()
    hue_ranges = tuple(tuple(c.hue_ranges) for c in colors)
    nc = len(hue_ranges)
    has_cams = rgb.ndim == 5
    lead = rgb.shape[:2] if has_cams else rgb.shape[:1]
    n = rgb.shape[-3] * rgb.shape[-2]
    width = int(rgb.shape[-2]) if with_bbox else 0
    rgb_flat = jnp.asarray(rgb, jnp.float32).reshape(*lead, n, 3)
    bg_shape = (lead[0], n) if has_cams else (n,)

    bg_valid = state is not None
    bg0 = state.bg if bg_valid else jnp.zeros(bg_shape, jnp.float32)
    gain0 = (state.gain if bg_valid
             else jnp.ones(bg_shape[:-1], jnp.float32))

    M_pos, norm, op = query_constants(model, nc, bs, bv, op)

    if impl == "pallas":
        res = ingest_core(
            rgb_flat, bg0, gain0, M_pos, norm, hue_ranges=hue_ranges,
            bs=bs, bv=bv, alpha=alpha, threshold=threshold,
            use_fg=use_foreground, bg_valid=bg_valid, op=op,
            impl="pallas", interpret=interpret, width=width)
    elif impl == "jnp":
        res = _ingest_jnp(
            rgb_flat, bg0, gain0, M_pos, norm, hue_ranges=hue_ranges,
            bs=bs, bv=bv, alpha=alpha, threshold=threshold,
            use_fg=use_foreground, bg_valid=bg_valid, op=op, width=width)
    else:
        raise ValueError(f"unknown ingest impl {impl!r}")
    counts, totals, fgtot, util, bg, gain = res[:6]

    pf = pf_from_counts(counts, totals, bs, bv)
    hf = totals / jnp.maximum(fgtot, 1.0)[..., None]
    new_state = IngestState(bg=bg, gain=gain)
    out = (pf, hf, (util if model is not None else None), new_state)
    if with_bbox:
        return out + (res[6],)
    return out


__all__ = ["frame_pf", "batch_pf", "ingest_pipeline", "ingest_core",
           "query_constants", "IngestState", "default_impl",
           "default_interpret"]
