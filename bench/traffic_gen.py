"""Seeded city-camera scenes: the benchmark's own frame generator.

A stream is a static HSV background (greenish-grey scene, a darker
road band, clutter patches that share the query hues at low saturation)
with per-frame illumination drift, a sliding shadow, moving vehicle
rectangles of saturated colours, and sensor noise on saturation and value. Target objects are vehicles of a
query colour whose visible area reaches ``min_blob_frac`` of the frame;
a frame holding one is ``busy`` (the backend would run its DNN stage
on it) and lists the object's id.

The vehicle schedule, clutter layout and per-frame illumination are
drawn on the host from the seed with NumPy; the pixels are rendered on
the device in one jitted call per stream and copied back once as
uint8 RGB, the form a camera delivers. The same seed gives the same
frames and the same ground truth.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import List, Tuple

import numpy as np

# name -> (hue centre, hue spread, (sat lo, hi), (val lo, hi)); hue in
# [0, 180), saturation and value in [0, 256), as OpenCV counts them
VEHICLE_PALETTE = {
    "red": (4.0, 3.0, (200, 252), (150, 235)),
    "yellow": (27.0, 3.0, (200, 252), (160, 240)),
    "blue": (112.0, 6.0, (180, 245), (120, 225)),
    "white": (20.0, 10.0, (0, 28), (200, 250)),
    "gray": (90.0, 40.0, (0, 35), (70, 150)),
    "black": (90.0, 40.0, (0, 50), (10, 55)),
}
# low-saturation clutter sharing a target's hue (brown walls, dust)
CLUTTER = {
    "red": (5.0, 4.0, (20, 130), (40, 160)),
    "yellow": (28.0, 4.0, (20, 120), (50, 170)),
    "blue": (110.0, 8.0, (20, 100), (100, 210)),
}
# vehicle fields, one row per vehicle in the device schedule
_FIELDS = ("t_enter", "t_exit", "y", "h", "w", "speed", "x0", "hue", "sat",
           "val")


@dataclass
class Scene:
    """Rendered streams and their ground truth.

    ``frames``: (S, P, H, W, 3) uint8 RGB. ``busy``: (S, P) bool.
    ``labels``: (S, P, targets) bool, a target of each query colour in
    sight. ``objects[s][p]``: ids of the target objects visible in
    frame p of stream s."""
    frames: np.ndarray
    busy: np.ndarray
    labels: np.ndarray
    objects: List[List[Tuple[int, ...]]]


def _vehicles(rng, P: int, H: int, W: int, p: dict, first_id: int):
    """Host-drawn vehicle schedule: (V, len(_FIELDS)) float32 rows, the
    colour names and the ids."""
    names = list(p["color_mix"])
    probs = np.asarray([p["color_mix"][n] for n in names], np.float64)
    probs /= probs.sum()
    road_top = int(H * 0.58)
    rows, colors = [], []
    t = 0
    while True:
        t += int(rng.geometric(min(p["vehicle_rate"], 0.999)))
        if t >= P:
            break
        name = str(rng.choice(names, p=probs))
        hc, hs, (slo, shi), (vlo, vhi) = VEHICLE_PALETTE[name]
        h = max(2, int(rng.integers(H // 10, H // 5)))
        w = max(3, int(rng.integers(W // 8, W // 4)))
        speed = float(rng.uniform(W / 80, W / 25)) * (
            1 if rng.random() < 0.5 else -1)
        dur = int(abs((W + w) / speed)) + 1
        rows.append((t, min(P, t + dur), int(rng.integers(road_top, H - h)),
                     h, w, speed, -w if speed > 0 else W,
                     float(np.clip(rng.normal(hc, hs), 0, 179.9)),
                     int(rng.integers(slo, shi)), int(rng.integers(vlo, vhi))))
        colors.append(name)
    table = np.asarray(rows, np.float32).reshape(-1, len(_FIELDS))
    return table, colors, first_id + np.arange(len(rows))


def _clutter(rng, H: int, W: int, p: dict) -> np.ndarray:
    """Clutter patches: (n, 10) float32 rows (y, x, h, w, hue centre,
    hue spread, sat lo, hi, val lo, hi)."""
    road_top = int(H * 0.55)
    out = []
    for cname in p["target_colors"]:
        if cname not in CLUTTER:
            continue
        hc, hs, (slo, shi), (vlo, vhi) = CLUTTER[cname]
        for _ in range(int(p["clutter_density"] * 12)):
            ph = int(rng.integers(H // 8, H // 3))
            pw = int(rng.integers(W // 10, W // 3))
            out.append((int(rng.integers(0, road_top)),
                        int(rng.integers(0, W - pw)), ph, pw, hc, hs, slo, shi,
                        vlo, vhi))
    return np.asarray(out, np.float32).reshape(-1, 10)


def _visible(table: np.ndarray, P: int, W: int):
    """(V, P) visible pixel widths, by the same float32 arithmetic the
    renderer uses."""
    f = np.arange(P, dtype=np.float32)[None, :]
    t0, t1 = table[:, 0:1], table[:, 1:2]
    x = (table[:, 6:7] + table[:, 5:6] * (f - t0)).astype(np.int32)
    x1 = np.maximum(x, 0)
    x2 = np.minimum(x + table[:, 4:5].astype(np.int32), W)
    on = (f >= t0) & (f < t1) & (x2 > x1)
    return np.where(on, x2 - x1, 0)


def _render_fn(H: int, W: int, P: int):
    import jax
    import jax.numpy as jnp

    def hsv_to_rgb(h, s, v):
        c = v * (s / 255.0)
        hp = h * 2.0 / 60.0
        x = c * (1 - jnp.abs(hp % 2 - 1))
        z = jnp.zeros_like(c)
        sector = jnp.clip(jnp.floor(hp), 0, 5).astype(jnp.int32)
        r = jnp.select([sector == 0, sector == 1, sector == 2, sector == 3,
                        sector == 4], [c, x, z, z, x], c)
        g = jnp.select([sector == 0, sector == 1, sector == 2, sector == 3,
                        sector == 4], [x, c, c, x, z], z)
        b = jnp.select([sector == 0, sector == 1, sector == 2, sector == 3,
                        sector == 4], [z, z, x, c, c], x)
        m = v - c
        rgb = jnp.stack([r + m, g + m, b + m], axis=-1)
        return jnp.clip(rgb, 0, 255).astype(jnp.uint8)

    def box(rows, cols, y, x, h, w):
        return (rows >= y) & (rows < y + h) & (cols >= x) & (cols < x + w)

    @jax.jit
    def render(key, clutter, vehicles, gains, drift):
        kb, kc, kf = jax.random.split(key, 3)
        rows = jax.lax.broadcasted_iota(jnp.int32, (H, W), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (H, W), 1)
        k = jax.random.split(kb, 5)
        hue = jax.random.uniform(k[0], (H, W), minval=60, maxval=100)
        sat = jax.random.uniform(k[1], (H, W), minval=10, maxval=60)
        val = jax.random.uniform(k[2], (H, W), minval=90, maxval=170)
        road = rows >= int(H * 0.55)
        sat = jnp.where(road, jax.random.uniform(k[3], (H, W), maxval=25), sat)
        val = jnp.where(road, jax.random.uniform(k[4], (H, W), minval=60,
                                                 maxval=110), val)

        def paint(i, hsv):
            hu, sa, va = hsv
            y, x, h, w, hc, hs, slo, shi, vlo, vhi = (clutter[i, j]
                                                      for j in range(10))
            kk = jax.random.split(jax.random.fold_in(kc, i), 3)
            m = box(rows, cols, y.astype(jnp.int32), x.astype(jnp.int32),
                    h.astype(jnp.int32), w.astype(jnp.int32))
            hu = jnp.where(m, jnp.clip(hc + hs * jax.random.normal(
                kk[0], (H, W)), 0, 179.9), hu)
            sa = jnp.where(m, jax.random.uniform(kk[1], (H, W), minval=slo,
                                                 maxval=shi), sa)
            va = jnp.where(m, jax.random.uniform(kk[2], (H, W), minval=vlo,
                                                 maxval=vhi), va)
            return hu, sa, va

        hue, sat, val = jax.lax.fori_loop(0, clutter.shape[0], paint,
                                          (hue, sat, val))

        def frame(f, gain, fkey):
            kn = jax.random.split(fkey, 5)
            fv = jnp.clip(val * gain, 0, 255)
            sh_w = W // 4
            sx = (jnp.floor(f * drift) % (W + sh_w)).astype(jnp.int32) - sh_w
            fv = jnp.where((cols >= sx) & (cols < sx + sh_w), fv * 0.9, fv)
            noise = [jax.random.normal(kn[j], (H, W)) for j in range(3)]
            fh, fs = hue, sat
            ff = f.astype(jnp.float32)

            def car(i, hsv):
                hu, sa, va = hsv
                t0, t1, y, h, w, speed, x0, hc, sc, vc = (
                    vehicles[i, j] for j in range(len(_FIELDS)))
                x = (x0 + speed * (ff - t0)).astype(jnp.int32)
                m = (box(rows, cols, y.astype(jnp.int32), x,
                         h.astype(jnp.int32), w.astype(jnp.int32))
                     & (ff >= t0) & (ff < t1))
                hu = jnp.where(m, jnp.clip(hc + noise[0], 0, 179.9), hu)
                sa = jnp.where(m, jnp.clip(sc + 6 * noise[1], 0, 255), sa)
                va = jnp.where(m, jnp.clip(vc + 6 * noise[2], 0, 255), va)
                return hu, sa, va

            fh, fs, fv = jax.lax.fori_loop(0, vehicles.shape[0], car,
                                           (fh, fs, fv))
            fs = jnp.clip(fs + 2.0 * jax.random.normal(kn[3], (H, W)), 0, 255)
            fv = jnp.clip(fv + 2.0 * jax.random.normal(kn[4], (H, W)), 0, 255)
            return hsv_to_rgb(fh, fs, fv)

        fkeys = jax.random.split(kf, P)
        return jax.vmap(frame)(jnp.arange(P), gains, fkeys)

    return render


@functools.lru_cache(maxsize=4)
def _renderer(H: int, W: int, P: int):
    return _render_fn(H, W, P)


def render_scene(seed: int, streams: int, frames: int, height: int,
                 width: int, params: dict) -> Scene:
    """Render ``streams`` camera streams of ``frames`` frames each.

    ``params``: vehicle_rate (new vehicles per frame), color_mix
    (vehicle colour -> weight), target_colors, clutter_density,
    illumination_drift (relative amplitude), shadow_speed (pixels per
    frame), min_blob_frac."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    H, W, P = int(height), int(width), int(frames)
    render = _renderer(H, W, P)
    targets = tuple(params["target_colors"])
    min_blob = params["min_blob_frac"] * H * W
    out = np.empty((streams, P, H, W, 3), np.uint8)
    busy = np.zeros((streams, P), bool)
    labels = np.zeros((streams, P, len(targets)), bool)
    objects: List[List[Tuple[int, ...]]] = []
    next_id = 0
    for s in range(streams):
        table, colors, ids = _vehicles(rng, P, H, W, params, next_id)
        next_id += len(ids)
        clutter = _clutter(rng, H, W, params)
        period = max(120, P // 3)
        gains = (1.0 + params["illumination_drift"] * np.sin(
            2 * np.pi * np.arange(P) / period)
            + rng.normal(0, 0.015, P)).astype(np.float32)
        key = jax.random.key(int(rng.integers(0, 2 ** 31 - 1)))
        # at most one vehicle enters per frame: pad the table to P rows
        # of inert vehicles (never on screen), so every seed renders
        # with the same compiled program
        dev_table = np.zeros((P, len(_FIELDS)), np.float32)
        dev_table[:, :2] = -1.0
        dev_table[:len(table)] = table
        out[s] = np.asarray(render(key, jnp.asarray(clutter),
                                   jnp.asarray(dev_table), jnp.asarray(gains),
                                   jnp.float32(params["shadow_speed"])))
        vis = _visible(table, P, W) * table[:, 3:4].astype(np.int32)
        target = np.asarray([c in targets for c in colors], bool)
        seen = (vis >= min_blob) & target[:, None]
        busy[s] = seen.any(axis=0)
        for k, name in enumerate(targets):
            hit = np.asarray([c == name for c in colors], bool)
            labels[s, :, k] = (seen & hit[:, None]).any(axis=0)
        objects.append([tuple(int(i) for i in ids[seen[:, f]])
                        for f in range(P)])
    return Scene(out, busy, labels, objects)


__all__ = ["Scene", "render_scene", "VEHICLE_PALETTE", "CLUTTER"]
