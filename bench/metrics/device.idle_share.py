"""device.idle_share: the share of the traced window in which no operation
ran on the device, averaged over the chips."""


def read(ctx):
    p = ctx.profile
    if p is None or not p.chips or p.window_s <= 0:
        return None
    return 100.0 * (1.0 - p.busy_s() / p.window_s)
