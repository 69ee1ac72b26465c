"""input.ms: the loop's input for one step, the host batch
(``data.batch``) and its placement on the chips (``data.put``), median over
the window's steps."""
import statistics

from bench.stepspans import per_step


def read(ctx):
    steps = per_step(ctx, "data.batch", "data.put")
    if not steps:
        return None
    return 1e3 * statistics.median(steps.values())
