"""Open loop through the streaming service on the wall clock.

C synchronized cameras each offer one uint8 frame per period at a fixed
per-camera ``fps``; the frames carry no precomputed utility, so every
window the coalescer forms goes through the session's fused serve step.
``max_batch`` sits above any window the rate forms and only deadline
flushes (``max_wait_s``) fire, so each window is ``(C, 1)``. Admitted
frames wait in the session's queues for a ``tokens``-token mock backend;
every completion feeds its latency back into the control loop, which
ticks every ``control_period_s``.

A delegating proxy stands between the service and the session: it
wraps each call into a layer in a host span, stamps the instant each
frame's admission decision reaches the host, and logs the calls the
reference replays.

Traffic parameters (``bench/traffic/<mix>.json``): ``fps``,
``pool_frames``, ``warm_windows`` (the window lengths compiled before
the clock starts), ``tokens``, ``max_batch``,
``max_wait_s``, ``control_period_s``, ``backend`` (mock backend
latencies), ``scene`` and ``train``.
"""
from __future__ import annotations

import numpy as np

from bench import harness as h
from bench import reference as ref
from bench.traffic_gen import render_scene


class Proxy:
    """The session as the service sees it, with stamps, spans and a log
    of every call into the control surface."""

    def __init__(self, session, log: "h.Log") -> None:
        self._s = session
        self.log = log
        self.clock = None
        self.decided = {}          # frame key -> decision instant
        self.arrived = []          # instants the service took arrivals in
        self.stepped = []          # (cameras, frames) of each window

    def __getattr__(self, name):
        return getattr(self._s, name)

    def __len__(self) -> int:
        return len(self._s)

    def lane(self, cam):
        if self.clock is not None:
            self.arrived.append(self.clock.now())
        return self._s.lane(cam)

    def step(self, frames=None, *, items=None, tick=True, **kw):
        with h.span("bench.step"):
            res = self._s.step(frames=frames, items=items, tick=tick, **kw)
        if self.clock is not None:
            now = self.clock.now()
            for row in items:
                for it in row:
                    self.decided.setdefault(it.key, []).append(now)
        self.stepped.append(np.asarray(frames).shape[:2])
        self.log.step(None, items, res, tick)
        return res

    def tick(self):
        with h.span("bench.tick"):
            snap = self._s.tick()
        self.log.events.append(("tick", np.asarray(
            snap["per_camera"]["target_drop_rate"], np.float32)))
        return snap

    def next_frame(self, cam=None):
        with h.span("bench.next_frame"):
            it = self._s.next_frame(cam)
        self.log.events.append(("pop", 1, [] if it is None else [it]))
        return it

    def next_frames(self, k, cams=None):
        with h.span("bench.next_frames"):
            items = self._s.next_frames(k, cams)
        self.log.events.append(("pop", k, list(items)))
        return items

    def report_backend_latency(self, x, cam=None):
        self.log.events.append(("latency", float(x)))
        with h.span("bench.report_latency"):
            return self._s.report_backend_latency(x, cam)

    def report_ingress_fps(self, x, cam=None):
        self.log.events.append(("fps", float(x)))
        return self._s.report_ingress_fps(x, cam)

    def offer_batch(self, *a, **kw):
        self.log.events.append(("unfused",))
        return self._s.offer_batch(*a, **kw)


def arrivals(scene, order, times, first: int):
    """One synchronized instant per entry of ``times``, one frame per
    camera each; camera c replays stream ``order.stream[c]`` from pool
    frame ``order.offset[c]`` on, frame ``first`` first. A target object
    is named by its camera, the pass over the pool and its id in the
    scene, so each camera's and each pass's objects are new."""
    from repro.serve import Arrival
    P = scene.frames.shape[1]
    out, frames = [], []
    for i, t in enumerate(times, start=first):
        for c, (s, o) in enumerate(zip(order.stream, order.offset)):
            s, p = int(s), int(o + i) % P
            lap = int(o + i) // P
            it = h.Frame(c, i, s, p, bool(scene.busy[s, p]),
                         tuple((c, lap, x) for x in scene.objects[s][p]), t)
            out.append(Arrival(t=t, cam=c, record=it, utility=None,
                               frame=scene.frames[s, p]))
            frames.append(it)
    return out, frames


def p95_ms(seconds) -> "float | None":
    """The 95th percentile in ms; None (no number) when nothing was
    measured, as in a run that delivered no frame."""
    return float(np.percentile(seconds, 95) * 1e3) if len(seconds) else None


def warm_times(lengths, fps: float):
    """Instants that the coalescer groups into one window of each of
    ``lengths`` frames per camera: a burst of that many instants 10 ms
    apart, each burst as long after the last as its instants take at
    ``fps``, so that the control loop sees the cell's own ingress rate."""
    times, t = [], 0.0
    for n in lengths:
        times += [t + 0.01 * k for k in range(n)]
        t += n / fps
    return times


def run(ctx) -> dict:
    cfg, traffic = ctx.config, ctx.traffic
    from repro.serve import MockBackend, ServeService, VirtualClock, WallClock
    C, H, W = cfg["cameras"], cfg["height"], cfg["width"]
    S, P, fps = cfg["rendered_streams"], traffic["pool_frames"], traffic["fps"]
    order = h.Order(ctx.seed, C, S, P, traffic["scene_seed"])
    scene = render_scene(traffic["scene_seed"], S, P, H, W, traffic["scene"])
    ctx.mark("render")
    model = h.fit_model(traffic["train_seed"], cfg, traffic)
    ctx.mark("model fit")
    session = h.open_session(cfg, model)
    log = h.Log(session)
    proxy = Proxy(session, log)
    ctx.mark("session")
    backend = MockBackend(seed=order.backend_seed, **traffic["backend"])
    kw = dict(tokens=traffic["tokens"], max_batch=traffic["max_batch"],
              max_wait=traffic["max_wait_s"],
              control_period=traffic["control_period_s"])
    # warm-up on the virtual clock: windows of every length the cell may
    # form (a host hiccup merges two instants into one window), the
    # first without background and the rest with it, the tick, the pops
    # and the feeds, so that nothing compiles inside the window
    times = warm_times(traffic["warm_windows"], fps)
    arr, _ = arrivals(scene, order, times, 0)
    ServeService(proxy, backend, clock=VirtualClock(), **kw).run(arr)
    counter = ctx.compiles
    clock = WallClock()
    t0 = clock.now() + 0.2
    arr, offered = arrivals(
        scene, order, [t0 + k / fps for k in range(int(ctx.seconds * fps))],
        len(times))
    service = ServeService(proxy, backend, clock=clock, **kw)
    proxy.clock = clock
    proxy.arrived.clear()
    n_steps = len(log.steps)
    ctx.window_start()
    c0 = counter.n
    res = service.run(arr)
    ctx.window_end()
    compiles = counter.n - c0
    proxy.clock = None
    memory = h.peak_memory(ctx.devices)
    log.snapshot()
    final = h.final_state(session)
    del session
    # -- end-to-end metrics -------------------------------------------------
    decide = np.asarray([proxy.decided[f.key][0] - f.t_gen for f in offered
                         if f.key in proxy.decided])
    e2e = res.e2e_latencies()
    bound = cfg["latency_bound_s"]
    on_time = {id(p.record) for p in res.processed if p.e2e <= bound}
    kept = [id(f) in on_time for f in offered]
    lateness = np.asarray(proxy.arrived) - np.asarray([a.t for a in arr])
    counters = res.metrics["counters"]
    # -- correctness --------------------------------------------------------
    ingest = h.ingest_on_device(cfg, model)
    control = (h.ingest_on_device(cfg, model, ctx.control)
               if ctx.control is not None else None)

    def frames_of(s):
        return np.stack([np.stack([scene.frames[it.stream, it.pool]
                                   for it in row]) for row in s["items"]])

    numbers = h.compare(cfg, model, log, final, frames_of, ingest,
                        cfg["camera_fps"], control)
    counts = [len(proxy.decided.get(f.key, ())) for f in offered]
    numbers["undecided"] = sum(n != 1 for n in counts)
    popped = {id(it) for ev in log.events if ev[0] == "pop" for it in ev[2]}
    numbers["delivered_unpopped"] = sum(id(p.record) not in popped
                                        for p in res.processed)
    numbers["unfused_windows"] = sum(ev[0] == "unfused" for ev in log.events)
    window_shapes = proxy.stepped[n_steps:]
    return {
        "end_to_end": {
            "decide_p95_ms": p95_ms(decide),
            "e2e_p95_ms": p95_ms(e2e),
            "qor": ref.qor([f.objects for f in offered], kept),
        },
        "attempted": len(offered),
        "failed": int(numbers["undecided"]),
        "numbers": numbers,
        "memory_peak_bytes": memory,
        "compiles_in_window": compiles,
        "record": {
            "offered": len(offered), "delivered": len(res.processed),
            "gen_late_s": lateness.tolist(),
            "counters": counters,
            "windows": len(window_shapes),
            "window_shapes": sorted({tuple(int(x) for x in s)
                                     for s in window_shapes}),
            "steps": len(window_shapes),
        },
    }
