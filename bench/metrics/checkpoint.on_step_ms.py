"""checkpoint.on_step_ms: the checkpointer's ``checkpoint.on_step`` span
(pack into the wire layout, send, the shadow's lag gate), mean per step in
the window."""


def read(ctx):
    spans = ctx.in_window("checkpoint.on_step")
    if not spans:
        return None
    return 1e3 * sum(s.dur for s in spans) / len(spans)
