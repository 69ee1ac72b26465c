"""device.idle_unnamed_share: the share of the traced window in which the
device runs no operation and no trainer span is open, averaged over the
chips. Trainer spans are the run's program spans (`repro.obs`) less the
shadow's (``shadow.*``) and the harness's (``bench.*``).

Where the profile carries its host plane (``host``: [name, start_ns,
end_ns] of each host event in the window), the spans are read there, on
the profiler's own clock: the program mirrors each span as an annotation of
its name. Otherwise the run's spans are moved onto the profile's clock at
the window's start."""
from bench.devtrace import merge


def _named(ctx) -> list:
    p = ctx.profile
    names = {s.name for s in ctx.spans
             if not s.name.startswith(("shadow.", "bench."))}
    host = getattr(p, "host", None)
    if host is not None:
        return merge([s, e] for name, s, e in host if name in names)
    to_ns = lambda t: p.t0_ns + (t - ctx.t0) * 1e9  # noqa: E731
    return merge([max(to_ns(s.t0), p.t0_ns), min(to_ns(s.t1), p.t1_ns)]
                 for s in ctx.spans if s.name in names
                 and to_ns(s.t1) > p.t0_ns and to_ns(s.t0) < p.t1_ns)


def _uncovered(gaps, covered) -> float:
    """Nanoseconds of ``gaps`` outside ``covered`` (both merged, sorted)."""
    total, i = 0.0, 0
    for s, e in gaps:
        t = s
        while i < len(covered) and covered[i][1] <= t:
            i += 1
        j = i
        while j < len(covered) and covered[j][0] < e:
            cs, ce = covered[j]
            if cs > t:
                total += cs - t
            t = max(t, ce)
            if t >= e:
                break
            j += 1
        if t < e:
            total += e - t
    return total


def read(ctx):
    p = ctx.profile
    if p is None or not p.chips or p.window_s <= 0:
        return None
    named = _named(ctx)
    idle = [_uncovered(p.gaps(c), named) for c in range(len(p.chips))]
    return 100.0 * sum(idle) / len(idle) * 1e-9 / p.window_s
