#!/usr/bin/env python3
"""Run one cell several times, one process a run (this script never touches
JAX), keep every result line under ``chiprun_out/`` and print each metric's
median and spread — the distance between the first and third quartile
(``statistics.quantiles(values, n=4)``) as a share of the median, which is
what the bounds in ``BENCHMARK.json`` are set from.

    python benchmarks/tools/measure.py --workload <cell> --seeds 11,12,13 \
        [--seconds 30] [--trace 0] [--out chiprun_out/<name>.jsonl] [-- <more run.py flags>]
"""

from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def spread(values: list) -> float:
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    median = statistics.median(values)
    return (q3 - q1) / median if median else float("nan")   # a count of 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--seconds", default=None)
    parser.add_argument("--trace", default="0")
    parser.add_argument("--out", default=None)
    parser.add_argument("--stderr-lines", type=int, default=14)
    parser.add_argument("rest", nargs=argparse.REMAINDER)
    args = parser.parse_args(argv)
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    seconds = args.seconds or str(manifest["run_seconds"])
    out = Path(args.out or ROOT / "chiprun_out" / f"{args.workload}.jsonl")
    out.parent.mkdir(parents=True, exist_ok=True)
    extra = [a for a in args.rest if a != "--"]
    rows = []
    for seed in args.seeds.split(","):
        cmd = manifest["command"] + ["--workload", args.workload, "--seed", seed,
                                     "--seconds", seconds, "--trace", args.trace] + extra
        t = time.monotonic()
        proc = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
        wall = time.monotonic() - t
        lines = proc.stdout.strip().splitlines()
        try:
            result = json.loads(lines[-1]) if lines else None
        except ValueError:
            result = None
        tail = [l for l in proc.stderr.splitlines() if not l.startswith(("E0", "W0", "I0"))]
        row = {"workload": args.workload, "seed": int(seed), "rc": proc.returncode,
               "wall_s": round(wall, 1), "flags": extra, "trace": args.trace,
               "seconds": seconds, "result": result,
               "stderr_tail": tail[-args.stderr_lines:]}
        rows.append(row)
        with out.open("a") as f:
            f.write(json.dumps(row) + "\n")
        print(f"--- seed {seed} rc={proc.returncode} wall={wall:.1f}s", flush=True)
        print("\n".join(tail[-args.stderr_lines:]), flush=True)
        if result is not None:
            print(json.dumps({k: v for k, v in result.items() if k != "checks"}),
                  flush=True)
    good = [r["result"] for r in rows if r["result"] is not None]
    print(f"=== {args.workload}: {len(good)} of {len(rows)} runs gave a result, "
          f"correct in {sum(1 for g in good if g['correct'])}")
    for name in (good[0]["metrics"] if good else []):
        values = [g["metrics"][name]["value"] for g in good if name in g["metrics"]]
        line = f"{name}: n={len(values)} median={statistics.median(values):.4f}"
        if len(values) >= 3:
            line += f" spread={100 * spread(values):.2f}%"
        print(line + " values=" + " ".join(f"{v:.3f}" for v in values))
    return 0 if len(good) == len(rows) else 1


if __name__ == "__main__":
    sys.exit(main())
