"""pack.gbps: the in-process channel's pack into the wire layout, the
bytes it wrote over its time (``bucket.pack`` and its ``bytes``), median
over the window's steps; 1 GB is 1e9 bytes."""
import statistics


def read(ctx):
    rates = [s.args["bytes"] / s.dur * 1e-9
             for s in ctx.in_window("bucket.pack")
             if s.args.get("bytes") and s.dur > 0]
    if not rates:
        return None
    return statistics.median(rates)
