"""Benchmark aggregator: one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--fast]

Prints ``name,us_per_call,derived`` CSV lines.
"""
from __future__ import annotations

import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true", help="smaller problem sizes")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()

    from benchmarks.common import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (distributed_pipeline, fig1_insitu, fig4_timeline,
                            halo_pipeline, kernels_micro, query_micro,
                            roofline_report, table1_morton)

    suites = {
        "table1": lambda: table1_morton.main(n=(1 << 15) if args.fast else (1 << 18)),
        "fig4": lambda: fig4_timeline.ladder(n=512 if args.fast else 2048),
        "fig1": lambda: fig1_insitu.main(fast=args.fast),
        "roofline": lambda: roofline_report.main(fast=args.fast),
        "kernels": kernels_micro.main,
        "halos": lambda: halo_pipeline.main(fast=args.fast),
        "query": lambda: query_micro.main(fast=args.fast),
        "distributed": lambda: distributed_pipeline.main(fast=args.fast),
    }
    print("name,us_per_call,derived")
    failures = []
    for name, fn in suites.items():
        if args.only and name != args.only:
            continue
        try:
            fn()
        except Exception:  # noqa: BLE001
            failures.append(name)
            traceback.print_exc()
    if failures:
        print(f"FAILED suites: {failures}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
