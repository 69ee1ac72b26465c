"""pack.reuse_share: the share of the in-process channel's pack that went
into wire buffers the shadow had given back (``bucket.pack``'s ``reused``
over its ``bytes``), in %, median over the window's steps. A program whose
pack reports no ``reused`` reads nothing."""
import statistics


def read(ctx):
    shares = [100.0 * s.args["reused"] / s.args["bytes"]
              for s in ctx.in_window("bucket.pack")
              if s.args.get("bytes") and "reused" in s.args]
    if not shares:
        return None
    return statistics.median(shares)
