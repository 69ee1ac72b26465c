#!/usr/bin/env python3
"""Run cells several times, one process after another, and summarise.

    python3 bench/sets.py --workload gpt3-xl.ckpt --seeds 11,12,13 \\
        --seconds 51 --trace 0 --out out/sets.jsonl

Each run is ``bench/run.py`` in a process of its own, exactly as the
benchmark is run; its result line (or its failure) is appended to
``--out`` with the seed and the wall time of the process. At the end every
metric's median and spread are printed per cell: the spread is the
distance between the first and third quartiles
(``statistics.quantiles(values, n=4)``) over the median.
"""
from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def spread(values) -> float:
    median = statistics.median(values) if values else 0.0
    if len(values) < 2 or median == 0:
        return float("nan")
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / median


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", action="append", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", required=True)
    ap.add_argument("--trace", default="0")
    ap.add_argument("--out", required=True)
    ap.add_argument("--tail", type=int, default=3000,
                    help="characters of a failed run's stderr to keep")
    args = ap.parse_args(argv)
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    rows = []
    for workload in args.workload:
        for seed in args.seeds.split(","):
            cmd = [sys.executable, str(ROOT / "bench" / "run.py"),
                   "--workload", workload, "--seed", seed,
                   "--seconds", args.seconds, "--trace", args.trace]
            t = time.perf_counter()
            p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
            row = {"workload": workload, "seed": int(seed),
                   "rc": p.returncode, "wall_s": time.perf_counter() - t}
            lines = p.stdout.strip().splitlines()
            try:
                row["result"] = json.loads(lines[-1])
            except (IndexError, json.JSONDecodeError):
                row["stderr"] = p.stderr[-args.tail:]
            row["stderr_tail"] = "\n".join(p.stderr.splitlines()[-12:])
            rows.append(row)
            with out.open("a") as f:
                f.write(json.dumps(row) + "\n")
            res = row.get("result", {})
            print(json.dumps({k: row[k] for k in ("workload", "seed", "rc",
                                                  "wall_s")}
                             | {"correct": res.get("correct"),
                                "metrics": {m: v["value"] for m, v in
                                            res.get("metrics", {}).items()}}),
                  flush=True)
    for workload in args.workload:
        done = [r["result"] for r in rows
                if r["workload"] == workload and "result" in r]
        names = sorted({m for r in done for m in r["metrics"]})
        for m in names:
            vals = [r["metrics"][m]["value"] for r in done
                    if m in r["metrics"]]
            print(f"{workload} {m}: n {len(vals)} median "
                  f"{statistics.median(vals)} spread {spread(vals)}")


if __name__ == "__main__":
    main()
