"""shadow.lag_wait_ms: time the trainer blocked on the shadow's lag bound
(``ShadowCluster.lag_wait_s_total``, read at the window's start and end),
per step in the window."""


def read(ctx):
    wait = ctx.counters.get("lag_wait_s")
    if wait is None or ctx.steps <= 0:
        return None
    return 1e3 * wait / ctx.steps
