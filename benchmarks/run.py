"""Benchmark runner — one entry per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (plus saves full JSON to
results/benchmarks/).

  PYTHONPATH=src python -m benchmarks.run [--full | --quick] [--only NAME]

``--quick`` (also the default) runs test-scale sizes — the CI smoke
invocation documented in ROADMAP.md; ``--full`` runs paper-scale sizes.
"""
from __future__ import annotations

import argparse
import json
import sys
import traceback
from pathlib import Path

BENCHES = [
    ("fig5_hue_fraction", "benchmarks.bench_hue_fraction"),
    ("fig9_11_12_utility_separation", "benchmarks.bench_utility_separation"),
    ("fig10_qor_tradeoff", "benchmarks.bench_qor_tradeoff"),
    ("fig13a_control_loop", "benchmarks.bench_control_loop"),
    ("fig13b_14_multicam", "benchmarks.bench_multicam"),
    ("fig15_overhead", "benchmarks.bench_overhead"),
    ("fleet_sharded", "benchmarks.bench_fleet"),
    ("service_streaming", "benchmarks.bench_service"),
    ("scenarios_resilience", "benchmarks.bench_scenarios"),
    ("cascade_qor", "benchmarks.bench_cascade"),
    ("roofline_summary", "benchmarks.roofline"),
]

# consolidated machine-readable results: per-bench name -> metrics
# dict, merged across (possibly partial --only) runs so the perf
# trajectory is tracked in one file across PRs instead of eyeballed
# from stdout
CONSOLIDATED = Path("BENCH_serve.json")
# robustness scenarios land in their own consolidated file — they are
# pass/fail acceptance facts + QoR-under-stress, not perf trajectory
SCENARIO_FILE = Path("BENCH_scenarios.json")
# two-stage cascade QoR comparison: acceptance facts (cascade >= color
# at equal shed rate) in their own file, same reasoning
CASCADE_FILE = Path("BENCH_cascade.json")


def _write_consolidated(results: dict, path: Path = CONSOLIDATED) -> None:
    merged = {}
    if path.exists():
        try:
            merged = json.loads(path.read_text())
        except (OSError, json.JSONDecodeError):
            merged = {}
    merged.update(results)
    path.write_text(
        json.dumps(merged, indent=2, sort_keys=True) + "\n")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale sizes (slower)")
    ap.add_argument("--quick", action="store_true",
                    help="test-scale sizes (the default; explicit flag "
                         "for CI smoke invocations)")
    ap.add_argument("--only", nargs="+", default=None,
                    help="run only benchmarks whose name contains any of "
                         "these substrings")
    args = ap.parse_args()
    if args.full and args.quick:
        ap.error("--full and --quick are mutually exclusive")
    from repro.launch.jax_cache import enable_compile_cache
    enable_compile_cache()

    outdir = Path("results/benchmarks")
    outdir.mkdir(parents=True, exist_ok=True)
    print("name,us_per_call,derived")
    failures = 0
    consolidated = {}
    for name, mod_name in BENCHES:
        if args.only and not any(sub in name for sub in args.only):
            continue
        try:
            import importlib
            mod = importlib.import_module(mod_name)
            res = mod.run(quick=not args.full)
            (outdir / f"{name}.json").write_text(json.dumps(res, indent=2))
            entry = {"us_per_call": res["us_per_call"],
                     "derived": res["derived"],
                     "mode": "full" if args.full else "quick"}
            if "scenarios" in res:
                _write_consolidated(
                    {name: {**entry, "scenarios": res["scenarios"]}},
                    SCENARIO_FILE)
            elif "cascade" in res:
                _write_consolidated(
                    {name: {**entry, "cascade": res["cascade"]}},
                    CASCADE_FILE)
            else:
                consolidated[name] = entry
            derived = json.dumps(res["derived"], sort_keys=True)
            print(f'{name},{res["us_per_call"]:.1f},"{derived}"', flush=True)
        except Exception as e:  # noqa: BLE001
            failures += 1
            err = {"error": f"{type(e).__name__}: {e}"}
            if name.startswith("scenarios"):
                _write_consolidated({name: err}, SCENARIO_FILE)
            elif name.startswith("cascade"):
                _write_consolidated({name: err}, CASCADE_FILE)
            else:
                consolidated[name] = err
            print(f"{name},ERROR,{type(e).__name__}: {e}", flush=True)
            traceback.print_exc(file=sys.stderr)
    if consolidated:
        _write_consolidated(consolidated)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
