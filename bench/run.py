#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``workloads`` in ``BENCHMARK.json``) names a configuration
(``bench/configs/<name>.json``) and a traffic mix
(``bench/traffic/<mix>.json``); the mix names its driver
(``bench/drivers/<driver>.py``), which builds the run from the seed,
warms up every shape it will use, measures for ``--seconds`` and then
compares what the timed path produced with the plain reference
(``bench/reference.py``). With ``--trace 0`` the result carries the
cell's end-to-end metrics; with ``--trace 1`` the window's device
operations are traced by the profiler (the host tracer off) and the
result carries the per-layer metrics, each read by
``bench/layer_metrics/<metric>.py`` from the run's record (the same
window, on the host's clock and the program's counters) and the reduced
trace (``bench/trace.py``).

The last line of standard output is the result as one JSON object; the
numbers compared, each beside its limit, are the last lines of standard
error and the result's last key. Without a TPU, or with fewer chips
than the cell asks for, the run exits with code 3 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
# the compile cache lives at a fixed path inside the checkout, so that
# every run after a cell's first finds its programs there
CACHE = ROOT / ".bench_cache" / "jax"


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str):
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"bench: unknown workload {workload!r}; "
                         f"cells: {sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    config = json.loads((ROOT / conf["file"]).read_text())
    traffic = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json")
                         .read_text())
    return spec, cell, config, traffic


def cell_metrics(spec: dict, cell: str, kind: str):
    """The metrics of ``kind`` ("end_to_end" or "per_layer") this cell
    reports."""
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    if kind == "end_to_end":
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


def bench_window_mark(x):
    """The marker program run on each chip at both ends of a traced
    window (``bench/trace.py``)."""
    return x + 1


class Context:
    """What a driver gets: the parsed files, the run's arguments, the
    devices, a compile counter, and the window's two marks."""

    def __init__(self, config, traffic, seed, seconds, trace, devices,
                 trace_dir: Path):
        from bench.harness import CompileCounter
        self.config, self.traffic = config, traffic
        self.seed, self.seconds = int(seed), float(seconds)
        self.trace = bool(trace)
        self.devices = devices
        self.compiles = CompileCounter()
        self.trace_dir = trace_dir
        self.control = None        # a dtype: the control's precision
        self.setup_s = None
        self.window_s = None
        self.window = None         # (start, end) on time.perf_counter
        self.host_marks = []       # host intervals around the marker runs
        self.setup_parts = {}
        self._last = T_START
        self._t0 = None

    def mark(self, part: str) -> None:
        """Close one named part of the set-up (seconds since the last)."""
        now = time.perf_counter()
        self.setup_parts[part] = now - self._last
        self._last = now

    def _run_marks(self) -> None:
        import jax
        outs = [self._mark_fn(x) for x in self._mark_in]
        jax.block_until_ready(outs)

    def _stamp_marks(self) -> None:
        a = time.perf_counter()
        self._run_marks()
        self.host_marks.append((a, time.perf_counter()))

    def window_start(self) -> None:
        from bench.harness import SPANS
        if self.trace:
            import jax
            import jax.numpy as jnp
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            self._mark_fn = jax.jit(bench_window_mark)
            self._mark_in = [jax.device_put(jnp.zeros((8,), jnp.float32), d)
                             for d in self.devices]
            self._run_marks()
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 0
            jax.profiler.start_trace(str(self.trace_dir),
                                     profiler_options=opts)
            self._stamp_marks()
        SPANS.out.clear()
        self._t0 = time.perf_counter()
        self.setup_parts["warm-up"] = self._t0 - self._last
        self.setup_s = self._t0 - T_START

    def window_end(self) -> None:
        t1 = time.perf_counter()
        self.window_s = t1 - self._t0
        self.window = (self._t0, t1)
        if self.trace:
            import jax
            self._stamp_marks()
            jax.profiler.stop_trace()

    def host_stamps(self) -> dict:
        """The window, the marker runs and the window's host spans, on
        the host's clock (``bench.trace.align``)."""
        from bench.harness import SPANS
        lo, hi = self.window
        return {"window": self.window, "marks": self.host_marks,
                "spans": [x for x in SPANS.out if x[1] > lo and x[0] < hi]}


def run_cell(spec, cell, config, traffic, seed, seconds, trace,
             device_check, control=None):
    """One run of one cell; returns the result dict (the printed line).
    ``control`` (a dtype) also reads the control's numbers, under the
    result's ``control`` key; the benchmark's own runs never do."""
    from bench import harness
    devices = device_check(cell["chips"])
    driver = load_module(BENCH / "drivers" / f"{traffic['driver']}.py")
    trace_dir = ROOT / ".bench_cache" / "trace" / cell["name"]
    ctx = Context(config, traffic, seed, seconds, trace, devices, trace_dir)
    ctx.control = control
    ctx.mark("start")
    out = driver.run(ctx)
    control_numbers = out["numbers"].pop("control", None)
    checks = harness.judge(out["numbers"])
    metrics, breakdown, device = {}, None, {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices), "memory_peak_bytes": out["memory_peak_bytes"]}
    if not trace:
        values = dict(out["end_to_end"], setup_s=ctx.setup_s)
        for m in cell_metrics(spec, cell["name"], "end_to_end"):
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        from bench import trace as tr
        red = tr.reduce(tr.find_xplane(trace_dir), ctx.host_stamps(),
                        chips=cell["chips"], kernel=traffic.get("kernel_op"))
        shutil.rmtree(trace_dir, ignore_errors=True)
        device.update(busy_s=red["busy_s"], window_s=red["window_s"],
                      clock_drift_s=red["clock_drift_s"])
        breakdown = {"device_ops": red["device_ops"],
                     "idle_gaps": red["idle_gaps"]}
        record = dict(out["record"], config=config, traffic=traffic,
                      peaks=load_peaks(devices[0].device_kind))
        for m in cell_metrics(spec, cell["name"], "per_layer"):
            reader = load_module(BENCH / "layer_metrics" / f"{m['name']}.py")
            v = reader.read(record, red)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    result = {"correct": harness.correct(checks),
              "attempted": out["attempted"], "failed": out["failed"],
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compiles_in_window"] = out["compiles_in_window"]
    result["setup_parts_s"] = ctx.setup_parts
    result["readings"] = out["numbers"]
    if control_numbers is not None:
        result["control"] = control_numbers
    result["checks"] = checks
    return result


def load_peaks(kind: str) -> dict:
    peaks = json.loads((BENCH / "peaks.json").read_text())["chips"]
    if kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in "
                       f"bench/peaks.json")
    return peaks[kind]


def use_cache() -> None:
    """JAX's persistent compilation cache at its fixed path."""
    import jax
    CACHE.mkdir(parents=True, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE)
    jax.config.update("jax_compilation_cache_dir", str(CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None, device_check=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec, cell, config, traffic = load_cell(args.workload)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    from bench import harness
    try:
        devices = (device_check or harness.require_devices)(cell["chips"])
    except harness.NoChip as e:
        print(e, file=sys.stderr)
        return 3
    use_cache()
    result = run_cell(spec, cell, config, traffic, args.seed, args.seconds,
                      bool(args.trace), lambda chips: devices)
    print(f"compilations inside the window: {result['compiles_in_window']}",
          flush=True)
    print(f"set-up parts (s): {json.dumps(result['setup_parts_s'])}",
          flush=True)
    print(json.dumps(result), flush=True)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
