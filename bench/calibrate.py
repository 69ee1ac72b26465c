#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/calibrate.py --workload gpt3-xl.nockpt --seeds 1,2,3 \\
        --controls 3 --seconds 0

For each seed, in this one process: the program's run of the cell (its
first steps and, for a cell with a checkpointer, a window of ``--seconds``
and the resume) and the float32 reference give the sound readings. For the
first ``--controls`` seeds also: the control (the reference in float8, the
precision below the configuration's bfloat16) and the planted fault "half
of the batch left out, the mean taken over the rest" (the reference on the
first half of each batch), each read against the float32 reference; and,
with a checkpointer, the shadow rounded to bfloat16 against the live state.
A step that leaves the state unchanged reads 1 on ``change_norm`` and
``grad_norm`` by their definitions and needs no run. The reference is the
configuration's own model (`bench.reference.model_for`).

One JSON object per seed goes to standard output and, with ``--out``, is
appended to that file. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from bench import compare  # noqa: E402
from bench import run as R  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    cell = R.load_cell(args.workload)
    R.use_compile_cache()
    R.chips_present(cell.chips)
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        controls = i < args.controls
        run = R.drive(cell, seed, args.seconds, plane_control=controls)
        d = run["window"]
        ref = R.reference_run(cell, seed)
        row = {"workload": cell.name, "seed": seed,
               "program": R.numbers(cell, d, ref),
               "loss_gap": compare.loss_gap(d.prog, ref),
               "losses": {"program": d.prog.get("loss"), "f32": ref["loss"]}}
        if controls:
            fp8 = R.reference_run(cell, seed, mode="fp8")
            half = R.reference_run(cell, seed, half_batch=True)
            row["control"] = dict(compare.training_numbers(fp8, ref),
                                  loss_gap=compare.loss_gap(fp8, ref),
                                  **d.controls)
            row["half_batch"] = dict(compare.training_numbers(half, ref),
                                     loss_gap=compare.loss_gap(half, ref))
        row["seconds"] = time.perf_counter() - t
        line = json.dumps(row)
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")


if __name__ == "__main__":
    main()
