"""capture.ms: the capture's device-to-host copy of the reduced gradients
(the loop's ``capture.d2h`` span), mean per step in the window."""


def read(ctx):
    spans = ctx.in_window("capture.d2h")
    if not spans:
        return None
    return 1e3 * sum(s.dur for s in spans) / len(spans)
