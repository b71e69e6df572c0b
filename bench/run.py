#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this process is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Cells, configurations, traffic mixes and metrics are listed in
``BENCHMARK.json`` at the root of the checkout; each lives in files of
its own under ``bench/`` (see ``bench/harness/cell.py`` for what one run
does). The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and with ``--trace 1``
``breakdown``), then ``checks``, each compared number beside its limit.
The same numbers end standard error.

A run that finds no TPU, fewer chips than the cell needs, or a missing
file exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", type=Path, default=None,
                    help="also copy the trace file into this directory")
    ap.add_argument("--rate", type=float, default=None,
                    help="offer this rate instead of the mix's (knee "
                         "sweeps only; a cell's rate is its mix file's)")
    ap.add_argument("--log-requests", type=Path, default=None,
                    help="also write every request's times to this file")
    args = ap.parse_args()

    from bench.harness.cell import SetupError, run_cell
    try:
        result = run_cell(ROOT, args.workload, args.seed, args.seconds,
                          bool(args.trace), T_START,
                          keep_trace=args.keep_trace, rate=args.rate,
                          log_requests=args.log_requests)
    except SetupError as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
