"""Per-step readings of the program's spans in the window, for the
per-layer metrics that take the median over steps, so one freeze of the
process moves them no further than one step's place in the order."""
from __future__ import annotations


def per_step(ctx, *names) -> dict:
    """{step: seconds in the named spans}, over the spans inside the window
    that carry the ``step`` of the iteration they belong to."""
    out = {}
    for name in names:
        for s in ctx.in_window(name):
            step = s.args.get("step")
            if step is not None:
                out[step] = out.get(step, 0.0) + s.dur
    return out
