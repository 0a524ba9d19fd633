"""Fused camera-side ingest — Pallas TPU kernels.

Two entry points:

``hsv_hist``
    The original per-frame kernel: RGB pixels (+ a *precomputed*
    foreground mask) -> per-color (sat, val) histograms. Kept as the
    building block for callers that bring their own background model.

``ingest_batch``
    The batched end-to-end ingest pipeline (this repo's hot path). It
    takes a ``(T, N, 3)`` frame batch — or a whole camera array
    ``(C, T, N, 3)`` with per-camera ``(bg, gain)`` state lanes — and
    runs one ``pallas_call`` a frame over every camera, per pixel tile,

      HBM -> VMEM tile -> RGB->HSV -> EMA background subtraction
          -> per-color hue masks x joint (sat, val) bin one-hot (MXU)
          -> per-frame PF counts + totals + in-kernel utility score

    over a 2D grid ``(camera, pixel-tile)``. TPU grid execution is
    sequential per core; the background and output blocks are indexed
    by the camera dimension only, so within their grid span they stay
    VMEM-resident and read-modify-write across grid steps is race-free,
    while each camera gets its own state lane.

    Background-model state is *explicit kernel state carried across
    batches*: the caller passes ``(bg, gain)`` in and receives the
    updated ``(bg, gain)`` out, so consecutive ``ingest_batch`` calls
    over a video stream behave exactly like one long call. The model is
    a per-pixel EMA on the Value channel with global-gain compensation:
    ``gain`` is the mean-ratio illumination estimate of the *previous*
    frame (the paper's drift is slow, so the lag is negligible), the
    frame is divided by it before differencing, and the background
    absorbs the compensated frame with learning rate ``alpha``. The
    wrapper sums each frame's values and background for that estimate
    with XLA between the kernel calls, in the order a plain jnp ingest
    sums them.

Tile layout (Mosaic's (8, 128) rule): the wrappers hand the kernels
*planar* pixels, each ``BLOCK``-pixel tile a ``(SUB, LANES)`` slab per
channel, so every elementwise stage runs on full vregs. Per-frame
results leave through camera-indexed blocks whose last two dims
are whole arrays — counts ``(NCP, bins)`` and an ``(NCP, 128)`` aux slab
(lane 0 totals, 1 foreground total, 2 utility) — and the wrappers slice
them back to the public shapes. The gain comes in a broadcast
``(SUB, 128)`` vector slab; nothing stores scalars to
VMEM and no store has a dynamic lane offset.

The histogram is, for each of a tile's ``SUB`` pixel rows, one
``(NCP, LANES) x (bins, LANES)^T`` matmul of the stacked hue x
foreground masks against the bin one-hot — MXU work, no scatter (TPU
has no fast scatter).

Hue ranges, bin counts, EMA constants and the composition op are all
*static* (baked into the kernel at trace time), matching the deployment
model: one compiled shedder per query.

VMEM contract: each camera's whole background lane (``4 * N`` bytes) is
resident, twice in and twice out under Pallas' double buffering — about
14 MiB at 1280x720, 32 MiB at 1920x1080 — plus about 1 MiB of per-step
blocks and tile temporaries. ``ingest_batch`` asks for that estimate
(``_ingest_vmem_bytes``, at least ``VMEM_FLOOR``) as the kernel's VMEM
limit and refuses frames whose estimate exceeds ``VMEM_CAP``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.utility import B_S, B_V, joint_bin_index
from repro.data.background import GAIN_MAX, GAIN_MIN
from repro.kernels import resolve_interpret

BLOCK = 4096          # pixels per VMEM tile
SUB = 8               # sublane rows of a tile
LANES = BLOCK // SUB  # lanes of a tile row (a multiple of 128)
AUX = 128             # lanes of the per-frame aux slab
# VMEM a kernel may ask for. The chips this repo targets have at least
# 64 MiB per core (v5e and v6e 128 MiB, v7x 64 MiB); older generations
# have less, and frames there need the tiled background (ROADMAP R2).
VMEM_CAP = 64 * 2 ** 20
# Floor of the requested limit. The estimate below counts the buffers
# and temporaries the kernel names, not Mosaic's internal scratch, so
# small frames get headroom; 720p (an estimate of about 15 MiB) compiled
# and ran on a v5e with this limit.
VMEM_FLOOR = 32 * 2 ** 20


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _rgb_to_hsv_block(r, g, b):
    v = jnp.maximum(jnp.maximum(r, g), b)
    mn = jnp.minimum(jnp.minimum(r, g), b)
    c = v - mn
    s = jnp.where(v > 0, c / jnp.maximum(v, 1e-9) * 255.0, 0.0)
    safe_c = jnp.where(c > 0, c, 1.0)
    # where v == r, |g - b| <= c, so the hue sector x lies in [-1, 1]
    # and ``x % 6`` is exactly ``x + 6`` for negative x
    x = (g - b) / safe_c
    h = jnp.where(
        v == r, jnp.where(x < 0, x + 6.0, x),
        jnp.where(v == g, (b - r) / safe_c + 2.0, (r - g) / safe_c + 4.0))
    h = jnp.where(c > 0, h * 30.0, 0.0)
    return h, s, v


def _planar(rgb, npad: int):
    """(..., n, 3) interleaved pixels -> (..., 3, npad/BLOCK, SUB, LANES)
    zero-padded planar tiles."""
    pad = npad - rgb.shape[-2]
    if pad:
        rgb = jnp.pad(rgb, [(0, 0)] * (rgb.ndim - 2) + [(0, pad), (0, 0)])
    planes = jnp.moveaxis(rgb.astype(jnp.float32), -1, -2)
    return planes.reshape(*planes.shape[:-1], npad // BLOCK, SUB, LANES)


def _tiles(x, npad: int):
    """(..., n) per-pixel lane -> (..., npad/BLOCK, SUB, LANES)."""
    pad = npad - x.shape[-1]
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    return x.reshape(*x.shape[:-1], npad // BLOCK, SUB, LANES)


def _pixel_index(j):
    """Flat pixel index of every element of tile ``j``. (SUB, LANES)."""
    return (j * BLOCK
            + jax.lax.broadcasted_iota(jnp.int32, (SUB, LANES), 0) * LANES
            + jax.lax.broadcasted_iota(jnp.int32, (SUB, LANES), 1))


def _tile_hist(h, s, v, fgf, *, hue_ranges, ncp, bs, bv):
    """One tile's per-color histograms. h/s/v/fgf: (SUB, LANES).

    Returns (counts (ncp, bs*bv), totals (ncp, 1)); rows >= nc are 0.
    """
    nb = bs * bv
    joint = joint_bin_index(s, v, bs, bv)
    masks = []
    for ranges in hue_ranges:
        m = jnp.zeros(h.shape, bool)
        for lo, hi in ranges:
            m |= (h >= lo) & (h < hi)
        masks.append(m.astype(jnp.float32) * fgf)
    color = jax.lax.broadcasted_iota(jnp.int32, (ncp, LANES), 0)
    bins = jax.lax.broadcasted_iota(jnp.int32, (nb, LANES), 0)
    counts = jnp.zeros((ncp, nb), jnp.float32)
    totals = jnp.zeros((ncp, 1), jnp.float32)
    for i in range(SUB):
        rows = jnp.zeros((ncp, LANES), jnp.float32)
        for c, m in enumerate(masks):
            rows = jnp.where(color == c, m[i:i + 1, :], rows)
        onehot = (bins == joint[i:i + 1, :]).astype(jnp.float32)
        counts += jax.lax.dot_general(
            rows, onehot, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        totals += jnp.sum(rows, axis=1, keepdims=True)
    return counts, totals


def _whole(fn, x, rows: int = SUB):
    """Whole-slab reduction ``fn`` (jnp.sum/min/max) as a (rows, 1)
    column; Mosaic broadcasts along one of sublanes or lanes at a
    time, so results stay columns until they meet a full slab."""
    r = fn(fn(x, axis=1, keepdims=True), axis=0, keepdims=True)
    return jnp.broadcast_to(r, (rows, 1))


def _lanes(shape=(SUB, AUX)):
    return jax.lax.broadcasted_iota(jnp.int32, shape, 1)


# ---------------------------------------------------------------------------
# Per-frame histogram kernel (precomputed foreground mask)
# ---------------------------------------------------------------------------

def _hsv_hist_kernel(rgb_ref, fg_ref, counts_ref, aux_ref,
                     *, hue_ranges, ncp, bs, bv):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        counts_ref[...] = jnp.zeros_like(counts_ref)
        aux_ref[...] = jnp.zeros_like(aux_ref)

    fgf = fg_ref[...]
    h, s, v = _rgb_to_hsv_block(rgb_ref[0], rgb_ref[1], rgb_ref[2])
    counts, totals = _tile_hist(h, s, v, fgf, hue_ranges=hue_ranges,
                                ncp=ncp, bs=bs, bv=bv)
    lane = _lanes((ncp, AUX))
    counts_ref[...] += counts
    aux_ref[...] += jnp.where(lane == 0, totals,
                              jnp.where(lane == 1, _whole(jnp.sum, fgf, ncp),
                                        0.0))


@functools.partial(jax.jit, static_argnames=("hue_ranges", "bs", "bv",
                                             "interpret"))
def hsv_hist(rgb, fg, hue_ranges, bs: int = B_S, bv: int = B_V,
             interpret: bool | None = None):
    """rgb: (N, 3) float32; fg: (N,) bool/float. N padded to BLOCK here.

    Returns (counts (nc, bs*bv), totals (nc,), fg_total ()).
    interpret=None resolves backend-aware (compiled only on TPU).
    """
    interpret = resolve_interpret(interpret)
    npad = _round_up(rgb.shape[0], BLOCK)
    nc = len(hue_ranges)
    ncp = _round_up(nc, SUB)
    nb = bs * bv
    counts, aux = pl.pallas_call(
        functools.partial(_hsv_hist_kernel, hue_ranges=hue_ranges, ncp=ncp,
                          bs=bs, bv=bv),
        grid=(npad // BLOCK,),
        in_specs=[
            pl.BlockSpec((3, None, SUB, LANES), lambda i: (0, i, 0, 0)),
            pl.BlockSpec((None, SUB, LANES), lambda i: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((ncp, nb), lambda i: (0, 0)),
            pl.BlockSpec((ncp, AUX), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((ncp, nb), jnp.float32),
            jax.ShapeDtypeStruct((ncp, AUX), jnp.float32),
        ],
        interpret=interpret,
    )(_planar(rgb, npad), _tiles(fg.astype(jnp.float32), npad))
    return counts[:nc], aux[:nc, 0], aux[0, 1]


# ---------------------------------------------------------------------------
# Batched end-to-end ingest kernel
# ---------------------------------------------------------------------------

def _ingest_kernel(rgb_ref, bg0_ref, gain_ref, m_ref, norm_ref,
                   counts_ref, aux_ref, bg_ref, *rest,
                   hue_ranges, ncp, bs, bv, alpha, threshold, npix,
                   use_fg, bg_valid, op, num_tiles, width=0):
    # one frame over the grid (camera, tile): the bg blocks are indexed
    # by camera only, so each camera's span reuses its own lane, and the
    # counts/aux/bbox blocks accumulate over the tile loop
    bbox_ref = rest[0] if width else None
    j = pl.program_id(1)        # pixel tile (inner)
    nc = len(hue_ranges)
    lane = _lanes()

    h, s, v = _rgb_to_hsv_block(rgb_ref[0], rgb_ref[1], rgb_ref[2])
    pidx = _pixel_index(j)
    validf = (pidx < npix).astype(jnp.float32)

    # --- EMA background subtraction (state carried across frames/batches)
    # no prior state: the frame seeds the background with itself, so its
    # |comp - base| is 0 -> all-background, matching the host model
    base = bg0_ref[j] if bg_valid else v
    gain = jnp.clip(gain_ref[:, 0:1], GAIN_MIN, GAIN_MAX)
    comp = v / gain
    fgf = ((jnp.abs(comp - base) > threshold).astype(jnp.float32)
           if use_fg else jnp.ones_like(v)) * validf
    bg_ref[j] = (1.0 - alpha) * base + alpha * comp

    counts_t, totals_t = _tile_hist(h, s, v, fgf, hue_ranges=hue_ranges,
                                    ncp=ncp, bs=bs, bv=bv)
    lane_c = _lanes((ncp, AUX))
    aux_t = jnp.where(lane_c == 0, totals_t,
                      jnp.where(lane_c == 1, _whole(jnp.sum, fgf, ncp),
                                0.0))

    # --- foreground bounding box (the cascade's free ROI): per-tile
    # masked min/max over (row, col) of the flattened pixel index,
    # min-combined across tiles; empty frames finalize to all -1
    if width:
        # exact pixel row: float estimate, then one integer correction
        row = (pidx.astype(jnp.float32) * (1.0 / width)).astype(jnp.int32)
        row = jnp.where(row * width > pidx, row - 1, row)
        row = jnp.where((row + 1) * width <= pidx, row + 1, row)
        col = pidx - row * width
        on = fgf > 0
        big = jnp.float32(npix)
        rowf, colf = row.astype(jnp.float32), col.astype(jnp.float32)

        def red(fn, x, empty):
            return _whole(fn, jnp.where(on, x, empty))

        vals = jnp.where(
            lane == 0, red(jnp.min, rowf, big),
            jnp.where(lane == 1, red(jnp.max, rowf, -1.0),
                      jnp.where(lane == 2, red(jnp.min, colf, big),
                                red(jnp.max, colf, -1.0))))
        is_min = (lane == 0) | (lane == 2)

        @pl.when(j == 0)
        def _bbox_first():
            bbox_ref[...] = vals

        @pl.when(j > 0)
        def _bbox_accum():
            prev = bbox_ref[...]
            bbox_ref[...] = jnp.where(is_min, jnp.minimum(prev, vals),
                                      jnp.maximum(prev, vals))

        @pl.when(j == num_tiles - 1)
        def _bbox_final():
            cur = bbox_ref[...]
            rmax = jnp.max(jnp.where(lane == 1, cur, -1.0), axis=1,
                           keepdims=True)
            bbox_ref[...] = jnp.where(rmax < 0, -1.0, cur)

    @pl.when(j == 0)
    def _first_tile():
        counts_ref[...] = counts_t
        aux_ref[...] = aux_t

    @pl.when(j > 0)
    def _accumulate():
        counts_ref[...] += counts_t
        aux_ref[...] += aux_t

    # --- in-kernel utility (Eq. 14-15) once this frame's counts are final
    @pl.when(j == num_tiles - 1)
    def _finalize_utility():
        aux = aux_ref[...]
        pf = counts_ref[...] / jnp.maximum(aux[:, 0:1], 1.0)
        u = jnp.sum(pf * m_ref[...], axis=1, keepdims=True)     # (ncp, 1)
        u = u / jnp.maximum(norm_ref[:, 0:1], 1e-9)
        real = jax.lax.broadcasted_iota(jnp.int32, (ncp, 1), 0) < nc
        if op == "and":
            util = jnp.min(jnp.where(real, u, jnp.inf), axis=0,
                           keepdims=True)
        else:                                           # single / or
            util = jnp.max(jnp.where(real, u, -jnp.inf), axis=0,
                           keepdims=True)
        aux_ref[...] = jnp.where(lane_c == 2,
                                 jnp.broadcast_to(util, (ncp, 1)), aux)


# (SUB, LANES) values the kernel body names, counted as if all were live
# at once: HSV conversion 12 (r, g, b and 9 intermediates), pixel index
# and validity 4, background/foreground update 6, joint bin 4, bbox
# row/column math 12, one mask per colour row (up to SUB), and 2 spare
TILE_TEMPS = 48


def _ingest_vmem_bytes(npad: int, ncp: int, nb: int) -> int:
    """VMEM the ingest kernel needs: the double-buffered background lane
    in and out, the double-buffered per-step blocks (RGB tile, counts
    and aux slabs, gain/bbox slabs), one row's bin one-hot and the tile
    temporaries, all float32."""
    lane = 4 * npad
    tile = 4 * BLOCK
    blocks = 2 * (3 * tile + 4 * ncp * (nb + AUX) + 3 * 4 * SUB * AUX)
    work = 4 * nb * LANES + TILE_TEMPS * tile
    return 4 * lane + blocks + work


@functools.partial(jax.jit, static_argnames=(
    "hue_ranges", "bs", "bv", "alpha", "threshold", "use_fg", "bg_valid",
    "op", "interpret", "width"))
def ingest_batch(rgb, bg0, gain0, M_pos, norm, hue_ranges,
                 bs: int = B_S, bv: int = B_V, *, alpha: float = 0.05,
                 threshold: float = 18.0, use_fg: bool = True,
                 bg_valid: bool = True, op: str = "or",
                 interpret: bool | None = None, width: int = 0):
    """Fused batched ingest: one pallas_call a frame for a whole camera
    array, the gain between frames from XLA row sums.

    rgb:   (T, N, 3) float32 RGB in [0, 255] (frames flattened to
           pixels), or (C, T, N, 3) for a C-camera array; the
           camera's uint8 is padded as it is and converted to float32
           in the planar relayout
    bg0:   (N,) / (C, N) float32 — per-camera background Value-channel
           state (ignored when ``bg_valid=False``: frame 0 then seeds it
           and yields no fg)
    gain0: () / (C,) float32 — illumination gain state (1.0 when fresh)
    M_pos: (nc, bs*bv) trained utility matrices (zeros -> utilities are 0)
    norm:  (nc,) per-color normalizers

    Returns (counts (T, nc, bs*bv), totals (T, nc), fg_total (T,),
             utility (T,), bg (N,), gain ()) — each with a leading
    camera lane iff the input had one. ``width > 0`` (the frame's
    pixel-row stride) appends a per-frame foreground bounding box
    ``(T, 4)`` int32 ``(row_min, row_max, col_min, col_max)``, all
    ``-1`` for empty masks — the in-kernel ROI for the semantic
    cascade, accumulated tile-by-tile at zero extra passes.
    """
    interpret = resolve_interpret(interpret)
    has_cams = rgb.ndim == 4
    if not has_cams:
        rgb = rgb[None]
    C, T, n = rgb.shape[0], rgb.shape[1], rgb.shape[2]
    npad = _round_up(n, BLOCK)
    num_tiles = npad // BLOCK
    nc = len(hue_ranges)
    ncp = _round_up(nc, SUB)
    nb = bs * bv
    vmem = _ingest_vmem_bytes(npad, ncp, nb)
    if vmem > VMEM_CAP:
        raise ValueError(
            f"{n}-pixel frames need {vmem} B of VMEM for the resident "
            f"background lane, over the {VMEM_CAP} B cap")
    # device scopes a profile reads: shed.stage is the planar relayout
    # of the frames, shed.score the kernel and its small operands
    with jax.named_scope("shed.score"):
        bg_in_plain = bg0
        bg0 = _tiles(jnp.asarray(bg0, jnp.float32).reshape(C, n), npad)
        # a scalar gain broadcasts to every camera lane, as the oracle's
        gain0 = jnp.broadcast_to(
            jnp.asarray(gain0, jnp.float32).reshape(-1, 1, 1), (C, SUB, AUX))
        M_pos = jnp.pad(M_pos.astype(jnp.float32), ((0, ncp - nc), (0, 0)))
        norm = jnp.broadcast_to(
            jnp.pad(norm.astype(jnp.float32), (0, ncp - nc),
                    constant_values=1.0)[:, None], (ncp, AUX))

    cam_block = lambda c, j: (c, 0, 0)            # noqa: E731
    lane_block = lambda c, j: (c, 0, 0, 0)        # noqa: E731
    const_block = lambda c, j: (0, 0)             # noqa: E731
    out_specs = [
        pl.BlockSpec((None, ncp, nb), cam_block),
        pl.BlockSpec((None, ncp, AUX), cam_block),
        pl.BlockSpec((None, num_tiles, SUB, LANES), lane_block),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((C, ncp, nb), jnp.float32),
        jax.ShapeDtypeStruct((C, ncp, AUX), jnp.float32),
        jax.ShapeDtypeStruct((C, num_tiles, SUB, LANES), jnp.float32),
    ]
    if width:
        out_specs.append(pl.BlockSpec((None, SUB, AUX), cam_block))
        out_shape.append(jax.ShapeDtypeStruct((C, SUB, AUX), jnp.float32))

    with jax.named_scope("shed.stage"):
        planes = _planar(rgb, npad)

    def frame(t, bg_in, gain_in, valid):
        """The kernel over frame ``t`` of every camera."""
        return pl.pallas_call(
            functools.partial(
                _ingest_kernel, hue_ranges=hue_ranges, ncp=ncp, bs=bs, bv=bv,
                alpha=alpha, threshold=threshold, npix=n, use_fg=use_fg,
                bg_valid=valid, op=op, num_tiles=num_tiles,
                width=int(width)),
            grid=(C, num_tiles),
            in_specs=[
                pl.BlockSpec((None, None, 3, None, SUB, LANES),
                             lambda c, j: (c, t, 0, j, 0, 0)),
                pl.BlockSpec((None, num_tiles, SUB, LANES), lane_block),
                pl.BlockSpec((None, SUB, AUX), cam_block),
                pl.BlockSpec((ncp, nb), const_block),
                pl.BlockSpec((ncp, AUX), const_block),
            ],
            out_specs=out_specs,
            out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
                vmem_limit_bytes=max(vmem, VMEM_FLOOR)),
            interpret=interpret,
            name="ingest_batch",
        )(planes, bg_in, gain_in, M_pos, norm)

    # one kernel call a frame. Between calls the next frame's gain is
    # sum(v) / sum(background), both summed by XLA over (C, N) rows as
    # a plain jnp ingest sums them, on a copy of the background that
    # XLA advances by the kernel's own update: the kernel's estimate,
    # summed tile by tile, differs in the last bits, and at 1080p a
    # pixel on the foreground threshold then flips
    with jax.named_scope("shed.score"):
        bg, gain, parts = bg0, gain0, []
        plain = jnp.asarray(bg_in_plain, jnp.float32).reshape(C, n)
        for t in range(T):
            results = frame(t, bg, gain, bg_valid or t > 0)
            parts.append(results)
            x = rgb[:, t].astype(jnp.float32)
            v = jnp.maximum(jnp.maximum(x[..., 0], x[..., 1]), x[..., 2])
            if t == 0 and not bg_valid:
                plain = v
            g_now = jnp.clip(gain[:, 0, 0], GAIN_MIN, GAIN_MAX)[:, None]
            g = jnp.clip(v.sum(axis=1) / jnp.maximum(plain.sum(axis=1), 1e-6),
                         GAIN_MIN, GAIN_MAX)
            plain = (1.0 - alpha) * plain + alpha * (v / g_now)
            bg = results[2]
            gain = jnp.broadcast_to(g[:, None, None], (C, SUB, AUX))
        counts, aux = (jnp.stack([r[i] for r in parts], axis=1)
                       for i in (0, 1))
        # the state carries XLA's copy of the background, so that the
        # next batch's first sum starts from the plain ingest's values
        out = [counts[:, :, :nc], aux[:, :, :nc, 0], aux[:, :, 0, 1],
               aux[:, :, 0, 2], plain, gain[:, 0, 0]]
        if width:
            out.append(jnp.stack([r[3] for r in parts], axis=1)
                       [:, :, 0, :4].astype(jnp.int32))
    if has_cams:
        return tuple(out)
    return tuple(o[0] for o in out)
