"""The numbers that decide ``correct``, each a gap of the program from the
plain reference (smaller is closer).

Training, from the first three steps of the run, which go through the
window's own call and feed:

* ``grad_norm``: per leaf, the gap between the norms of the first
  gradient as the optimizer got it (the program's from its first moment
  after one step, mu / (1 - b1)), over the larger of the reference leaf's
  norm and the median leaf's; the worst leaf.
* ``grad_err``: per leaf, the RMS of the elementwise gap of that gradient
  over elements drawn from the seed, over the larger of the reference's RMS
  there and the median leaf's; the worst leaf.
* ``change_norm``: as ``grad_norm``, for the change of the weights after
  three steps. Leaves whose reference gradient is under a thousandth of
  the median leaf's move under Adam by round-off alone and are left out.

The Checkmate plane (`plane_gap` in run.py): the largest
``max |shadow - live| / max |live|`` over the leaves of params, mu and nu.
"""
from __future__ import annotations

import math
import statistics

import numpy as np

ROUNDOFF_SHARE = 1e-3


def _rms(x) -> float:
    x = np.asarray(x, np.float64)
    return float(np.sqrt(np.mean(x * x))) if x.size else 0.0


def _norm_gap(prog: dict, ref: dict, names) -> float:
    med = statistics.median(ref[k][0] for k in sorted(ref))
    worst = 0.0
    for k in names:
        if k not in prog:
            return math.inf
        worst = max(worst, abs(prog[k][0] - ref[k][0]) / max(ref[k][0], med))
    return worst


def _elem_gap(prog: dict, ref: dict, names) -> float:
    med = statistics.median(_rms(ref[k][1]) for k in sorted(ref))
    worst = 0.0
    for k in names:
        if k not in prog:
            return math.inf
        gap = _rms(np.asarray(prog[k][1]) - np.asarray(ref[k][1]))
        worst = max(worst, gap / max(_rms(ref[k][1]), med))
    return worst


def loss_gap(prog: dict, ref: dict) -> float:
    """The largest relative gap of a step's loss. Read for the record and
    not compared: the float8 control moves it no more than bfloat16 does
    (PERF.md, section 6)."""
    pl, rl = prog.get("loss", []), ref["loss"]
    if len(pl) < len(rl):
        return math.inf
    return max(abs(a - b) / abs(b) for a, b in zip(pl, rl))


def training_numbers(prog: dict, ref: dict) -> dict:
    """``prog`` and ``ref``: {"grad": {leaf: (norm, sample)}, "change":
    {leaf: (norm, sample)}} (and "loss", which this leaves alone)."""
    out = {}
    grad, rgrad = prog.get("grad", {}), ref["grad"]
    out["grad_norm"] = _norm_gap(grad, rgrad, sorted(rgrad))
    out["grad_err"] = _elem_gap(grad, rgrad, sorted(rgrad))
    med = statistics.median(v[0] for v in rgrad.values())
    moved = [k for k in sorted(rgrad) if rgrad[k][0] >= ROUNDOFF_SHARE * med]
    out["change_norm"] = _norm_gap(prog.get("change", {}), ref["change"],
                                   moved)
    return {k: (v if math.isfinite(v) else math.inf) for k, v in out.items()}
