"""resume.place_ms: resume_s less the resume's consolidate and the first
step after it (its ``step.compute`` span): placing the checkpoint onto the
chip and the loop's own work around it."""


def read(ctx):
    r = ctx.resume
    if r is None:
        return None
    cons = [s for s in ctx.after("shadow.consolidate", r["t_fail"])
            if s.t1 <= r["t_ready"]]
    steps = [s for s in ctx.after("step.compute", r["t_fail"])
             if s.args.get("step") == r["step"]]
    if not cons or not steps:
        return None
    total = r["t_ready"] - r["t_fail"]
    return 1e3 * (total - sum(s.dur for s in cons) - steps[0].dur)
