"""shadow.apply_ms: one shadow node's optimizer replay of one step
(``shadow.apply``; a ``shadow.apply_batch`` of k steps counts k), mean over
the applies that ran in the window."""


def read(ctx):
    spans = ctx.in_window("shadow.apply")
    batches = ctx.in_window("shadow.apply_batch")
    n = len(spans) + sum(s.args.get("k", 1) for s in batches)
    if n == 0:
        return None
    return 1e3 * (sum(s.dur for s in spans)
                  + sum(s.dur for s in batches)) / n
