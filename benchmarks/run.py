"""Benchmark aggregator: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (us_per_call = harness wall
time; derived = the figure's headline validation numbers) and writes
per-figure row dumps under results/.

    PYTHONPATH=src python -m benchmarks.run [--full]
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from . import (cluster_routing, fig02_rank_heterogeneity,
               fig06_heavytail_cdf, fig10_latency_load,
               fig13_sched_policies, fig14_cache_policies,
               fig15_prefetch, fig16_sensitivity, fig17_scalability,
               roofline_table)
from .common import save_rows

MODULES = (fig02_rank_heterogeneity, fig06_heavytail_cdf,
           fig10_latency_load, fig13_sched_policies,
           fig14_cache_policies, fig15_prefetch, fig16_sensitivity,
           fig17_scalability, cluster_routing, roofline_table)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="full-length traces (slower, EXPERIMENTS.md "
                         "numbers); default is quick mode")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()

    print("name,us_per_call,derived")
    failed = []
    for mod in MODULES:
        if args.only and args.only not in mod.NAME:
            continue
        t0 = time.time()
        try:
            rows = mod.run(quick=not args.full)
            derived = mod.validate(rows) if hasattr(mod, "validate") else {}
            save_rows(mod.NAME, rows)
        except Exception as e:                      # noqa: BLE001
            # Print the row so the remaining modules still run, then
            # fail the whole run below: an error is never a result.
            derived = {"error": f"{type(e).__name__}: {e}"}
            failed.append(mod.NAME)
        us = (time.time() - t0) * 1e6
        print(f"{mod.NAME},{us:.0f},"
              f"\"{json.dumps(derived, default=str)}\"")
        sys.stdout.flush()
    if failed:
        sys.exit(f"benchmark modules failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
