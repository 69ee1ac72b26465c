"""step.mfu: the whole step's share of the chip's bf16 peak while the
device works: model FLOPs of the steps completed in the traced window
(forward and backward, no recomputation, from shapes: bench/flops.py) over
the seconds in which an operation ran on the device (every program, the
train step's and any other) times chips times the peak
(bench/peaks.json). Host time between the device's work is left out; the
end-to-end tokens_per_s holds it."""
from bench.flops import flops_per_step


def read(ctx):
    p = ctx.profile
    if p is None or ctx.peaks is None or ctx.steps <= 0 or p.busy_s() <= 0:
        return None
    done = flops_per_step(ctx.model, ctx.batch, ctx.seq) * ctx.steps
    peak = p.busy_s() * ctx.chips * ctx.peaks["bf16_flops_per_s"]
    return 100.0 * done / peak
