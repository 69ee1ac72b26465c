"""step.dispatch_ms: the call of the jitted train step until it returns
(``step.dispatch``, the wait for the device left out), median over the
window's steps."""
import statistics

from bench.stepspans import per_step


def read(ctx):
    steps = per_step(ctx, "step.dispatch")
    if not steps:
        return None
    return 1e3 * statistics.median(steps.values())
