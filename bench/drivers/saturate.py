"""Closed loop on the session's fused serve step: the chip's capacity.

Every step hands the session one ``(C, T, H, W, 3)`` uint8 window of
every camera's next ``T`` frames (``tick=True``), pops ``C`` frames with
one ``next_frames``, runs each popped frame through a seeded mock
backend and reports its latency back. The next step starts as soon as
the last one's decisions reached the host: a camera array whose
backlog never runs dry. ``frames_per_s`` is every frame scored and
decided over the whole window's time. A configuration with ``chips``
above 1 opens the session over a camera mesh of that many chips.

Traffic parameters (``bench/traffic/<mix>.json``): ``frames_per_step``
(T), ``pool_frames`` (frames rendered per stream and replayed in a
loop, each camera at its own offset), ``scene`` (the generator's
parameters), ``train`` (the model's training renders) and ``backend``
(the mock backend's latencies).
"""
from __future__ import annotations

import time

import numpy as np

from bench import harness as h
from bench.traffic_gen import render_scene


def build_windows(scene, order, T: int):
    """Camera c replays stream ``order.stream[c]`` from block
    ``order.offset[c] // T`` on; returns the ``P / T`` distinct (C, T, H,
    W, 3) windows, each contiguous, and per window the (C, T) pool
    indices."""
    P = scene.frames.shape[1]
    nb = P // T
    wins, index = [], []
    for k in range(nb):
        blocks = [(o // T + k) % nb for o in order.offset]
        wins.append(np.stack([scene.frames[s, b * T:(b + 1) * T]
                              for s, b in zip(order.stream, blocks)]))
        index.append(np.asarray([[b * T + t for t in range(T)]
                                 for b in blocks]))
    return wins, index


def run(ctx) -> dict:
    cfg, traffic = ctx.config, ctx.traffic
    C, H, W = cfg["cameras"], cfg["height"], cfg["width"]
    T, P = traffic["frames_per_step"], traffic["pool_frames"]
    S = cfg["rendered_streams"]
    from repro.serve.transport import MockBackend

    order = h.Order(ctx.seed, C, S, P, traffic["scene_seed"])
    scene = render_scene(traffic["scene_seed"], S, P, H, W, traffic["scene"])
    ctx.mark("render")
    model = h.fit_model(traffic["train_seed"], cfg, traffic)
    ctx.mark("model fit")
    mesh = None
    if cfg["chips"] > 1:
        from repro.core.fleet import fleet_mesh
        mesh = fleet_mesh(cfg["chips"])
    session = h.open_session(cfg, model, mesh)
    wins, index = build_windows(scene, order, T)
    ctx.mark("session and windows")
    backend = MockBackend(seed=order.backend_seed, **traffic["backend"])
    log = h.Log(session)
    counter = ctx.compiles
    frame_no = np.zeros(C, np.int64)
    step_s: list = []

    def items_for(k):
        out = []
        for c in range(C):
            s = int(order.stream[c])
            row = []
            for t in range(T):
                p = int(index[k][c, t])
                row.append(h.Frame(c, int(frame_no[c]) + t, s, p,
                                   bool(scene.busy[s, p]),
                                   scene.objects[s][p]))
            out.append(row)
        frame_no[:] += T
        return out

    def one_step(n: int) -> None:
        k = n % len(wins)
        items = items_for(k)
        t0 = time.perf_counter()
        with h.span("bench.step"):
            res = session.step(frames=wins[k], items=items, tick=True)
        step_s.append(time.perf_counter() - t0)
        log.step(k, items, res, True)
        with h.span("bench.next_frames"):
            popped = session.next_frames(C)
        log.events.append(("pop", C, popped))
        with h.span("bench.backend"):
            for it in popped:
                lat = backend.process(it)
                session.report_backend_latency(lat)
                log.events.append(("latency", lat))

    # warm-up: the first window (no background yet) and the next one
    # (carried background) compile the two serve-step programs, the pop
    # and the latency feed
    for n in range(traffic["warmup_steps"]):
        one_step(n)
    step_s.clear()
    ctx.window_start()
    c0 = counter.n
    t0 = time.perf_counter()
    n = traffic["warmup_steps"]
    steps = 0
    while True:
        one_step(n)
        n += 1
        steps += 1
        elapsed = time.perf_counter() - t0
        if elapsed >= ctx.seconds:
            break
    ctx.window_end()
    compiles = counter.n - c0
    frames = steps * C * T
    memory = h.peak_memory(ctx.devices)
    log.snapshot()
    final = h.final_state(session)
    del session
    ingest = h.ingest_on_device(cfg, model)
    control = (h.ingest_on_device(cfg, model, ctx.control)
               if ctx.control is not None else None)
    numbers = h.compare(cfg, model, log, final, lambda s: wins[s["index"]],
                        ingest, cfg["camera_fps"], control)
    return {
        "end_to_end": {"frames_per_s": frames / elapsed},
        "attempted": frames,
        "failed": 0,
        "numbers": numbers,
        "memory_peak_bytes": memory,
        "compiles_in_window": compiles,
        "record": {"steps": steps, "step_s": step_s, "window_s": elapsed,
                   "frames": frames, "frames_per_step": C * T,
                   "kernel_calls": steps,
                   "shape": {"cameras": C, "frames": T, "height": H,
                             "width": W}},
    }
