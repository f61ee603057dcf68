"""Benchmark harness entry point — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--scale small|paper] [--only X]

Prints ``name,us_per_call,derived`` CSV rows (the repo contract). The
roofline table is produced separately by ``python -m benchmarks.roofline``
from the dry-run artifacts.
"""
from __future__ import annotations

import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", default="small", choices=("small", "paper"))
    ap.add_argument("--only", default=None,
                    help="comma-separated benchmark names")
    args = ap.parse_args()

    from repro import runtime

    runtime.use_compile_cache()
    from benchmarks import (
        adaptive_beam,
        build_time,
        cache_skew,
        common,
        disk_io,
        kernel_bench,
        latency,
        lid_accuracy,
        mutation_churn,
        pipeline_throughput,
        recall_qps,
        recall_vs_L,
        scalability,
        serving_load,
    )

    suites = {
        "lid_accuracy": lid_accuracy.run,       # §3.1
        "recall_qps": recall_qps.run,           # Fig 1 / Table 1
        "recall_vs_L": recall_vs_L.run,         # Fig 2b
        "latency": latency.run,                 # Fig 2c
        "scalability": scalability.run,         # Fig 2a / Fig 3
        "build_time": build_time.run,           # §3.3
        "adaptive_beam": adaptive_beam.run,     # beyond-paper (Prop. 4.2)
        "pipeline": pipeline_throughput.run,    # serving-engine pipeline
        "disk_io": disk_io.run,                 # measured vs modelled slow tier
        "cache_skew": cache_skew.run,           # freq-aware hot tier vs static
        "serving_load": serving_load.run,       # front door: QPS at p99 SLO
        "mutation_churn": mutation_churn.run,   # delta tier under write mix
        "kernels": kernel_bench.run,            # hot-op microbench
    }
    if args.only:
        keep = set(args.only.split(","))
        suites = {k: v for k, v in suites.items() if k in keep}

    csv = common.Csv()
    print("name,us_per_call,derived")
    failures = []
    for name, fn in suites.items():
        try:
            fn(csv, scale=args.scale)
        except Exception:
            failures.append(name)
            traceback.print_exc()
            csv.add(f"{name}/FAILED", 0.0, "see traceback above")
    if failures:
        print(f"# {len(failures)} suite(s) failed: {failures}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
