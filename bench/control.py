#!/usr/bin/env python3
"""The control of a cell's correctness check, on the chip.

    python3 bench/control.py --workload <cell> --seed <n> [--seed <m> ...] \
        --seconds <s> [--out <file>]

For each seed (each in a process of its own, one after another) it runs
the cell as ``bench/run.py`` does, then compares, over the same sample
of finished requests, two things with the float32 reference: the
engine's served tokens (what every run compares), and the control's: the
same reference computed in bfloat16, the nearest precision below the
configuration's float32, put in the engine's place and read at the same
positions. The run is judged by the control's reading, so a check that
tells a bfloat16 engine from a sound one comes out not correct. Each
seed's numbers go to standard error as they come; the last line is JSON,
one record per seed; ``--out`` keeps every position's gap as well.
"""
import argparse
import json
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def one(workload: str, seed: int, seconds: float) -> dict:
    t0 = time.perf_counter()
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench.harness.cell import run_cell
    r = run_cell(ROOT, workload, seed, seconds, False, t0,
                 control="bfloat16")
    d = r["diagnostics"]
    return {"seed": seed, "correct": r["correct"],
            "program": d["program"], "control": d["control"],
            "checked_tokens": d["checked_tokens"],
            "unanswered": r["checks"]["unanswered"]["value"],
            "metrics": {k: v["value"] for k, v in r["metrics"].items()},
            "gaps": r["gaps"]}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, action="append", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", type=Path, help="every position's gaps, JSON")
    ap.add_argument("--one", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.one:
        print(json.dumps(one(args.workload, args.seed[0], args.seconds)),
              flush=True)
        return 0
    out = []
    for s in args.seed:        # one process per seed: one owner per chip
        p = subprocess.run(
            [sys.executable, __file__, "--one", "--workload",
             args.workload, "--seed", str(s), "--seconds",
             str(args.seconds)], capture_output=True, text=True)
        line = (p.stdout.strip().splitlines() or ["{}"])[-1]
        rec = json.loads(line) if p.returncode == 0 else {
            "seed": s, "error": p.stderr[-2000:]}
        out.append(rec)
        print(json.dumps({k: v for k, v in rec.items() if k != "gaps"}),
              file=sys.stderr, flush=True)
    if args.out is not None:
        args.out.write_text(json.dumps(out))
    print(json.dumps([{k: v for k, v in r.items() if k != "gaps"}
                      for r in out]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
