"""resume.consolidate_ms: the resume's ``shadow.consolidate`` span: the
shadow drained and its partitions gathered into one checkpoint."""


def read(ctx):
    r = ctx.resume
    if r is None:
        return None
    spans = [s for s in ctx.after("shadow.consolidate", r["t_fail"])
             if s.t1 <= r["t_ready"]]
    if not spans:
        return None
    return 1e3 * sum(s.dur for s in spans)
