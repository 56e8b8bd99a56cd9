"""Benchmark harness — one module per paper claim/table (DESIGN.md §6).

Prints ``name,us_per_call,derived`` CSV.  Each bench module imports
independently: an import failure (missing optional dep, broken
accelerator stack) reports a ``SKIP(import)`` row and a failed run an
``ERROR`` row, the rest of the suite still runs, and the harness then
exits nonzero, naming the modules that did not run.

The executor/multipod/serve benches additionally embed a
``run_report_md`` block (``telemetry.report.RunReport`` rendered to
markdown — per-phase device times, per-hop bytes, cache state, latency
percentiles) in their ``BENCH_*.json`` sidecars, so the checked-in perf
trajectory carries the phase decomposition, not just wall times.

Run:
  PYTHONPATH=src python -m benchmarks.run [--only substring]
"""

import argparse
import importlib
import sys
import traceback

#: name → module path; imported lazily one at a time so a single broken
#: import cannot take down the whole harness
MODULES = {
    "async_vs_sync": "benchmarks.bench_async_vs_sync",
    "staleness": "benchmarks.bench_staleness",
    "admm": "benchmarks.bench_admm",
    "compression": "benchmarks.bench_compression",
    "fit_executors": "benchmarks.bench_fit_executors",
    "multipod": "benchmarks.bench_multipod",
    "faults": "benchmarks.bench_faults",
    "serve": "benchmarks.bench_serve",
    "cascade_svm": "benchmarks.bench_cascade_svm",
    "gp_experts": "benchmarks.bench_gp_experts",
    "clustering": "benchmarks.bench_clustering",
    "kernels": "benchmarks.bench_kernels",
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="", help="run benches whose name contains this")
    args = ap.parse_args()

    rows: list = []
    failed: list = []
    print("name,us_per_call,derived")
    for name, modpath in MODULES.items():
        if args.only and args.only not in name:
            continue
        try:
            mod = importlib.import_module(modpath)
        except Exception:  # noqa: BLE001 — report the skip, keep going
            err = traceback.format_exc().splitlines()[-1]
            print(f"{name},SKIP(import),{err}")
            sys.stdout.flush()
            failed.append(name)
            continue
        try:
            start = len(rows)
            mod.run(rows)
            for r in rows[start:]:
                print(f"{r[0]},{r[1]:.1f},{r[2]}")
                sys.stdout.flush()
        except Exception:  # noqa: BLE001 — print and continue
            print(f"{name},ERROR,{traceback.format_exc().splitlines()[-1]}")
            failed.append(name)
    if failed:
        sys.exit(f"benchmarks that did not run: {', '.join(failed)}")


if __name__ == "__main__":
    main()
