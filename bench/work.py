"""The ingest kernel's necessary work, counted from its shapes.

The count is what the algorithm must move, whatever implements it: a
camera delivers 3 bytes (uint8 RGB) per pixel of each frame; each
camera's float32 background lane is read and written once per call,
however many frames the call holds (4 + 4 bytes per pixel); and each
frame leaves its outputs (per colour a ``bins``-long float32 histogram
and its total, plus the foreground total and the utility). The
kernel's arithmetic (HSV, per-pixel compares, a few dozen operations a
pixel) is far below the chip's peak for these bytes, so the bound is
the memory traffic: the least time of a call is its bytes at the HBM
peak.
"""
from __future__ import annotations

FRAME_BYTES_PER_PIXEL = 3         # uint8 RGB, as the camera delivers
BACKGROUND_BYTES_PER_PIXEL = 4 + 4   # float32 lane, read and written


def ingest_bytes(cameras: int, frames: int, pixels: int, colours: int,
                 bins: int) -> int:
    """Bytes one ingest call over a (cameras, frames, pixels) batch must
    move."""
    frame_in = cameras * frames * pixels * FRAME_BYTES_PER_PIXEL
    lanes = cameras * pixels * BACKGROUND_BYTES_PER_PIXEL
    outputs = cameras * frames * 4 * (colours * (bins + 1) + 2)
    state = cameras * 4 * 2          # the illumination gain, in and out
    return frame_in + lanes + outputs + state


def least_seconds(nbytes: int, peaks: dict) -> float:
    """The least time the chip can move ``nbytes`` in: bytes-bound, at
    the HBM peak."""
    return nbytes / float(peaks["hbm_bytes_per_s"])


__all__ = ["ingest_bytes", "least_seconds"]
