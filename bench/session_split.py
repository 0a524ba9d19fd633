#!/usr/bin/env python3
"""Split the saturate cell's serve step by the program's own spans and
device scopes, on the chip.

    python3 bench/session_split.py --seed <n> [--workload <cell>]
        [--steps 12] [--trace-steps 10] [--out <json>]

Builds the cell's run as ``bench/drivers/saturate.py`` does: the same
scene, model, camera order and windows from ``--seed``. It opens two
sessions over them, one with ``metrics=MetricsRegistry()`` and one
without, and steps them in turn for ``--steps`` steps each after the
traffic's warm-up steps. A step is the saturate loop's: one window with
a tick, one ``next_frames`` of C frames and one latency report per
popped frame. ``--trace-steps`` then traces that many more steps of the
metered session with the profiler (host tracer off).

The last line of standard output is one JSON object:

- ``host_ms``: per measured step, the mean of each ``session.*`` span
  of the metered session (``session.pop`` per call;
  ``session.report_latency`` summed over the step);
- ``counters``: the ``session.*`` counters per measured step;
- ``step_self_share``: the share of ``session.step`` that its phases
  leave uncovered;
- ``step_ms``: each session's step (the ``step`` call alone, as the
  cell's ``step_ms``) on the caller's clock, median over the measured
  steps; ``tracing_cost`` is the metered median over the plain one,
  less 1;
- with ``--trace-steps``: ``device_ms`` (device time per step by
  top-level ``shed.*`` scope, see ``device_split``), ``device_total_ms``,
  ``scoped_share`` (the ``shed.*`` scopes and the pop program over the
  total) and ``ingest_calls_per_step``.
"""
from __future__ import annotations

import argparse
import functools
import glob
import json
import re
import shutil
import statistics
import sys
import time
from pathlib import Path
from unittest import mock

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

SCOPE = "shed."
# the frames' first relayout is a program of its own; the copy of its
# argument carries the argument's name, not the scope around the body
STAGE_PROGRAM = "_flatten_frames"
POP_PROGRAM = "_pop_topk_dev"


def _program(tf_op: str) -> str:
    m = re.match(r"jit\(([^)]*)\)", tf_op or "")
    return m.group(1) if m else ""


def device_split(rows, steps: int) -> dict:
    """Reduce xprof's ``hlo_stats`` rows (dicts with ``program_id``,
    ``hlo_op_name``, ``tf_op_name`` (the ``named_scope`` path),
    ``occurrences`` and ``total_self_time`` in us) to per-step device
    milliseconds. An op counts under the first ``shed.*`` component of
    its path; one without goes under ``shed.stage`` if its program is
    the named reshape program, else under ``program:<jit name>``."""
    programs: dict = {}
    for r in rows:
        name = _program(r["tf_op_name"])
        if name:
            programs.setdefault(r["program_id"], set()).add(name)
    per: dict = {}
    calls = 0.0
    for r in rows:
        parts = (r["tf_op_name"] or "").split("/")
        names = programs.get(r["program_id"], set())
        key = next((p for p in parts if p.startswith(SCOPE)), None)
        if key is None:
            key = ("shed.stage" if STAGE_PROGRAM in names else
                   "program:" + ",".join(sorted(names) or ["?"]))
        per[key] = per.get(key, 0.0) + r["total_self_time"] * 1e-3 / steps
        if "ingest" in r["hlo_op_name"]:
            calls += r["occurrences"]
    total = sum(per.values())
    scoped = sum(v for k, v in per.items()
                 if k.startswith(SCOPE) or k == "program:" + POP_PROGRAM)
    return {"device_ms": dict(sorted(per.items(), key=lambda kv: -kv[1])),
            "device_total_ms": total,
            "scoped_share": scoped / total if total else None,
            "ingest_calls_per_step": calls / steps}


def hlo_stats(xplane: str) -> list:
    """The ``hlo_stats`` table of a profile, one dict per HLO op."""
    from xprof.convert import raw_to_tool_data
    data, _ = raw_to_tool_data.xspace_to_tool_data([xplane], "hlo_stats", {})
    table = json.loads(data)
    ids = [c["id"] for c in table["cols"]]
    return [dict(zip(ids, (c.get("v") for c in row["c"])))
            for row in table["rows"]]


def _open_metered(h, cfg, model, metrics):
    # the harness opens the cell's session without a registry
    from repro.core import open_session
    with mock.patch("repro.core.open_session",
                    functools.partial(open_session, metrics=metrics)):
        return h.open_session(cfg, model)


def measure(cfg: dict, traffic: dict, seed: int, steps: int,
            trace_steps: int = 0, trace_dir: Path = None) -> dict:
    """The split (see the module docstring) of one run."""
    import jax
    from bench import harness as h
    from bench.drivers.saturate import build_windows
    from bench.traffic_gen import render_scene
    from repro.serve.metrics import MetricsRegistry
    from repro.serve.transport import MockBackend

    C, H, W = cfg["cameras"], cfg["height"], cfg["width"]
    T, P = traffic["frames_per_step"], traffic["pool_frames"]
    order = h.Order(seed, C, cfg["rendered_streams"], P, traffic["scene_seed"])
    scene = render_scene(traffic["scene_seed"], cfg["rendered_streams"], P,
                         H, W, traffic["scene"])
    model = h.fit_model(traffic["train_seed"], cfg, traffic)
    wins, index = build_windows(scene, order, T)
    reg = MetricsRegistry()
    runs = {"plain": h.open_session(cfg, model),
            "metered": _open_metered(h, cfg, model, reg)}
    backend = MockBackend(seed=order.backend_seed, **traffic["backend"])
    step_s = {k: [] for k in runs}

    def frame(c: int, t: int, n: int, k: int):
        st, p = int(order.stream[c]), int(index[k][c, t])
        return h.Frame(c, n * T + t, st, p, bool(scene.busy[st, p]),
                       scene.objects[st][p])

    def one_step(name: str, n: int) -> None:
        s, k = runs[name], n % len(wins)
        items = [[frame(c, t, n, k) for t in range(T)] for c in range(C)]
        t0 = time.perf_counter()
        s.step(frames=wins[k], items=items, tick=True)
        step_s[name].append(time.perf_counter() - t0)
        for it in s.next_frames(C):
            s.report_backend_latency(backend.process(it))

    for n in range(traffic["warmup_steps"]):
        for name in runs:
            one_step(name, n)
    for v in step_s.values():
        v.clear()
    snap0 = reg.snapshot()
    n0 = traffic["warmup_steps"]
    for n in range(n0, n0 + steps):
        for name in runs:
            one_step(name, n)
    snap1 = reg.snapshot()
    spans = {}
    for k, x in snap1["histograms"].items():
        x0 = snap0["histograms"].get(k, {"count": 0, "mean": 0.0})
        spans[k[len("span."):]] = (x["count"] - x0["count"],
                                   x["count"] * x["mean"]
                                   - x0["count"] * x0["mean"])
    per_step = {"session.report_latency"}
    host_ms = {k: 1e3 * tot / (steps if k in per_step else max(cnt, 1))
               for k, (cnt, tot) in sorted(spans.items())}
    phases = sum(tot for k, (_, tot) in spans.items()
                 if k not in ("session.step", "session.pop",
                              "session.report_latency"))
    med = {k: 1e3 * statistics.median(v) for k, v in step_s.items()}
    out = {"steps": steps, "host_ms": host_ms,
           "counters": {k: (v - snap0["counters"].get(k, 0)) / steps
                        for k, v in snap1["counters"].items()},
           "step_self_share": 1.0 - phases / spans["session.step"][1],
           "step_ms": med,
           "tracing_cost": med["metered"] / med["plain"] - 1.0}
    if trace_steps:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        for n in range(n0 + steps, n0 + steps + trace_steps):
            one_step("metered", n)
        np.asarray(runs["metered"].state.proc_q)   # the last report's ops
        jax.profiler.stop_trace()
        xplane = sorted(glob.glob(str(Path(trace_dir) / "**" /
                                      "*.xplane.pb"), recursive=True))[-1]
        out.update(device_split(hlo_stats(xplane), trace_steps))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="detrac24_540p.saturate")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--steps", type=int, default=12)
    ap.add_argument("--trace-steps", type=int, default=10)
    ap.add_argument("--out", default=None,
                    help="also write the result to this JSON file")
    args = ap.parse_args(argv)
    from bench import harness
    from bench.run import load_cell
    _, cell, cfg, traffic = load_cell(args.workload)
    try:
        harness.require_devices(cell["chips"])
    except harness.NoChip as e:
        print(e, file=sys.stderr)
        return 3
    trace_dir = ROOT / "chiprun_out" / "session_split_trace"
    res = measure(cfg, traffic, args.seed, args.steps, args.trace_steps,
                  trace_dir)
    res.update(workload=args.workload, seed=args.seed)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(res, indent=1))
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
