"""Benchmark harness entry point: one section per paper table/figure plus the
framework-level benches.

  figure1   — semabench (coherence model + real threads)      [paper Fig. 1]
  serving   — TWA scheduler vs global rescan + QoS tenants    [paper §2 adapted]
  kernels   — Pallas kernels: oracle deltas + VMEM budgets
  roofline  — dry-run aggregation (per arch × shape × mesh)   [assignment]

    PYTHONPATH=src python -m benchmarks.run [--only figure1,kernels]
                                            [--json out.json]

`--json` writes per-section metrics (figure1 throughputs, serving
scans/skipped + per-tenant admission shares, kernel oracle deltas) so the
BENCH_*.json perf trajectory can accumulate across PRs.
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="figure1,serving,kernels,roofline")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write per-section metrics JSON to PATH")
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke mode: skip the K=128 megastep sweep and "
                         "shrink the paged-pool workload (flat wall time)")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import setup_compile_cache

    setup_compile_cache()
    if args.quick:
        import os

        os.environ["REPRO_BENCH_QUICK"] = "1"
    if args.json:  # fail fast, not after minutes of benchmarking
        with open(args.json, "a"):
            pass
    only = set(args.only.split(","))
    sections = []
    if "figure1" in only:
        from . import semabench

        sections.append(("figure1 / semabench", semabench.run))
    if "serving" in only:
        from . import serving_bench

        sections.append(("serving scheduler", serving_bench.run))
    if "kernels" in only:
        from . import kernel_bench

        sections.append(("pallas kernels", kernel_bench.run))
    if "roofline" in only:
        from . import roofline_table

        sections.append(("roofline / dry-run", roofline_table.run))

    failures = 0
    report: dict = {"sections": {}, "failures": []}
    for name, fn in sections:
        print(f"\n{'=' * 72}\n== {name}\n{'=' * 72}")
        t0 = time.time()
        metrics: dict = {}
        try:
            print(fn(metrics))
            metrics["wall_s"] = round(time.time() - t0, 3)
            report["sections"][name] = metrics
            print(f"[{name}] ok in {time.time() - t0:.1f}s")
        except Exception as e:  # report and continue — partial results count
            failures += 1
            import traceback

            traceback.print_exc()
            print(f"[{name}] FAILED: {e}")
            report["failures"].append({"section": name, "error": repr(e)})
    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=2, sort_keys=True)
        print(f"\n[metrics] wrote {args.json}")
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
