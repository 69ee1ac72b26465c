"""setup.shadow_bootstrap_ms: the shadow's bootstrap during set-up
(``shadow.bootstrap``: params, mu and nu copied off the chip and installed
on every node), in total over the spans that end before the window."""


def read(ctx):
    spans = [s for s in ctx.spans
             if s.name == "shadow.bootstrap" and s.t1 <= ctx.t0]
    if not spans:
        return None
    return 1e3 * sum(s.dur for s in spans)
