"""Reduce a profiler trace of one measured window to the numbers the
per-layer metrics read.

The window is traced with the host tracer off: at camera resolution the
runtime's host events (its relayout of every frame) would slow the host
about threefold and fill its memory. So the device planes are all the
trace holds. The host side comes from the benchmark's own clock: the
window's two instants and its host spans (``bench.*``, what the host
was doing), on ``time.perf_counter``. Just outside each end of the
window the harness runs a marker program (``MARK``) on every chip and
notes the host instants around it; the marker's place on a device's
timeline gives the offset between the two clocks.

Input: the ``.xplane.pb`` the JAX profiler wrote. On each device plane
(``/device:TPU:<n>``) the line of operations (``XLA Ops``) gives the
device's busy intervals.

Output (all seconds, device numbers averaged over the chips used):
``window_s``, ``busy_s`` (union of operation intervals inside the
window), ``kernel_s`` and ``kernel_calls`` (operations whose name
matches the kernel's pattern), ``device_ops`` (the ten operations that
took most time) and ``idle_gaps`` (device idle time inside the window,
summed by the innermost benchmark host span that covers it, ten
largest; time no span covers is ``host: outside spans``).
"""
from __future__ import annotations

import glob
import re
from pathlib import Path
from typing import Dict, List, Optional, Tuple

MARK = "bench_window_mark"
OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
UNCOVERED = "host: outside spans"


def find_xplane(trace_dir: Path) -> Path:
    found = sorted(glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return Path(found[-1])


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(iv, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def _gaps(busy, lo: float, hi: float):
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def _attribute(gaps, spans) -> Dict[str, float]:
    """Split each gap over the host spans covering it, innermost (the
    latest-starting) span winning where spans nest."""
    out: Dict[str, float] = {}
    spans = sorted(spans, key=lambda x: (x[0], -x[1]))
    for g0, g1 in gaps:
        near = [x for x in spans if x[1] > g0 and x[0] < g1]
        cuts = sorted({g0, g1, *(t for s, e, _ in near for t in (s, e)
                                 if g0 < t < g1)})
        for a, b in zip(cuts, cuts[1:]):
            mid = 0.5 * (a + b)
            owner = None
            for s, e, name in near:
                if s <= mid < e:
                    owner = name          # later starts are inner spans
            key = owner or UNCOVERED
            out[key] = out.get(key, 0.0) + (b - a)
    return out


def _is_mark(event) -> bool:
    if MARK in event.name:
        return True
    return any(k == "hlo_module" and MARK in str(v) for k, v in event.stats)


def load(xplane: Path) -> dict:
    """Each TPU device's operations and marker runs, in seconds on the
    device's timeline."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(xplane))
    devices, marks, lines = {}, {}, set()
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        d = int(m.group(1))
        ops, mk = [], []
        for line in plane.lines:
            lines.add(line.name)
            for e in line.events:
                iv = (e.start_ns * 1e-9, e.end_ns * 1e-9)
                if _is_mark(e):
                    mk.append(iv)
                elif line.name == OPS_LINE:
                    ops.append((*iv, e.name))
        devices[d], marks[d] = ops, _union(mk)
    if not devices:
        raise ValueError(f"{xplane}: no /device:TPU plane")
    if not all(len(v) >= 2 for v in marks.values()):
        raise ValueError(f"{xplane}: marker runs per device "
                         f"{ {d: len(v) for d, v in marks.items()} }, want 2 "
                         f"or more; device lines: {sorted(lines)}")
    return {"devices": devices, "marks": marks}


def _offset(host: Tuple[float, float], dev: Tuple[float, float]) -> float:
    """Device time minus host time, from one marker run: it ran on the
    device inside the host interval around it."""
    (a, b), (s, e) = host, dev
    return 0.5 * ((e - b) + (s - a))


def align(trace: dict, host: dict) -> dict:
    """The device trace with the host's window and spans moved onto the
    timeline of the first device. ``host``: ``window`` (start, end),
    ``marks`` (the host interval around the first and the last marker
    run) and ``spans`` [(start, end, name)], all on the host's clock.
    ``clock_drift_s`` is how far the offsets at the two ends differ."""
    d0 = min(trace["marks"])
    dev = sorted(trace["marks"][d0])
    first, last = host["marks"][0], host["marks"][-1]
    off0 = _offset(first, dev[0])
    off1 = _offset(last, dev[-1])
    off = 0.5 * (off0 + off1)
    lo, hi = host["window"]
    return {"window": (lo + off, hi + off),
            "spans": [(s + off, e + off, n) for s, e, n in host["spans"]],
            "devices": trace["devices"], "clock_drift_s": off1 - off0}


def summarize(trace: dict, chips: int, kernel: Optional[str]) -> dict:
    """The numbers of a loaded trace over its window (seconds)."""
    lo, hi = trace["window"]
    spans = [tuple(x) for x in trace["spans"]]
    devices = {int(k): v for k, v in trace["devices"].items()}
    used = sorted(devices)[:chips]
    pat = re.compile(kernel) if kernel else None
    busy = kern = calls = 0.0
    op_time: Dict[str, float] = {}
    idle: Dict[str, float] = {}
    for d in used:
        ops = [(s, e, n) for s, e, n in devices[d] if e > lo and s < hi]
        iv = _union(_clip([(s, e) for s, e, _ in ops], lo, hi))
        busy += sum(e - s for s, e in iv)
        for s, e, n in ops:
            dur = min(e, hi) - max(s, lo)
            op_time[n] = op_time.get(n, 0.0) + dur
            if pat is not None and pat.search(n):
                kern += dur
                calls += 1
        for k, v in _attribute(_gaps(iv, lo, hi), spans).items():
            idle[k] = idle.get(k, 0.0) + v
    n = len(used)
    top = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:10]
    return {"window_s": hi - lo, "busy_s": busy / n,
            "kernel_s": kern / n if pat is not None else None,
            "kernel_calls": calls / n, "chips": n,
            "device_ops": [[k, v / n] for k, v in top],
            "idle_gaps": [[k, v / n] for k, v in gaps]}


def reduce(xplane: Path, host: dict, chips: int,
           kernel: Optional[str]) -> dict:
    """``summarize`` of the trace aligned with the host's stamps."""
    aligned = align(load(xplane), host)
    return dict(summarize(aligned, chips, kernel),
                clock_drift_s=aligned["clock_drift_s"])


__all__ = ["align", "find_xplane", "load", "reduce", "summarize"]
