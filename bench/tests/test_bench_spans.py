"""The per-layer metrics that read the loop's spans: the input, the
dispatch, the pack's rate, the shadow's bootstrap, and the device's idle
time that no trainer span explains. The last is checked on two traces
recorded on one v5e: ``small.xplane.pb`` (``record_trace.py``, no program
span) and ``span.xplane.pb`` (``record_span_trace.py``, whose 200 ms gap
lies inside a mirrored ``data.batch`` span)."""
from pathlib import Path

import pytest

from bench import devtrace
from bench.context import Context, Span, reader
from bench_tiny import tiny_cell

DATA = Path(__file__).resolve().parent / "data"
NEW = ("device.idle_unnamed_share", "input.ms", "step.dispatch_ms",
       "pack.gbps", "setup.shadow_bootstrap_ms")


def host_events(path, prof) -> list:
    """[name, start_ns, end_ns] of each host-plane event in the window."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    s = max(ev.start_ns, prof.t0_ns)
                    e = min(ev.end_ns, prof.t1_ns)
                    if e > s:
                        out.append([ev.name, s, e])
    return out


def window_ctx(spans=(), profile=None, t0=100.0, t1=110.0, steps=5):
    return Context(model={}, batch=1, seq=1, chips=1, t0=t0, t1=t1,
                   steps=steps, spans=list(spans), profile=profile)


def steps_of(name, durs, t=101.0, **args):
    """One span per step, each starting a second after the last."""
    return [Span(name, "1", t + i, t + i + d, {"step": i + 1, **args})
            for i, d in enumerate(durs)]


# -- per-step medians ------------------------------------------------------------

def test_input_is_batch_plus_put_median_over_steps():
    spans = (steps_of("data.batch", [0.002, 0.002, 0.003, 0.002, 0.5])
             + steps_of("data.put", [0.001, 0.002, 0.001, 0.001, 0.001])
             + [Span("data.batch", "1", 99.0, 99.9, {"step": 0})])  # before
    # per step 3, 4, 4, 3 and 501 ms: the median is 4
    assert reader("input.ms")(window_ctx(spans)) == pytest.approx(4.0)


def test_dispatch_median_leaves_a_freeze_out():
    spans = steps_of("step.dispatch", [0.004, 0.005, 9.0 / 10, 0.004, 0.006])
    spans += steps_of("step.wait", [0.5] * 5)
    assert reader("step.dispatch_ms")(window_ctx(spans)) == pytest.approx(5.0)


def test_pack_rate_is_bytes_over_time_median():
    spans = [Span("bucket.pack", "1", 101.0 + i, 101.0 + i + d,
                  {"step": i + 1, "bytes": 200_000_000, "buckets": 80})
             for i, d in enumerate([0.4, 0.5, 0.4, 0.8])]
    gbps = reader("pack.gbps")(window_ctx(spans))     # 0.5, 0.4, 0.5, 0.25
    assert gbps == pytest.approx((0.5 + 0.4) / 2)


def test_bootstrap_counts_set_up_only():
    spans = [Span("shadow.bootstrap", "3", 50.0, 85.0, {"step": 0}),
             Span("shadow.bootstrap.d2h", "3", 50.0, 60.0, {"bytes": 1}),
             Span("shadow.bootstrap", "3", 105.0, 106.0, {"step": 9})]
    assert reader("setup.shadow_bootstrap_ms")(
        window_ctx(spans)) == pytest.approx(35000.0)


def test_a_program_without_the_spans_reads_nothing():
    """The parent's program: a pack with no byte count, no loop spans."""
    spans = (steps_of("step.compute", [0.5] * 5)
             + steps_of("bucket.pack", [4.0] * 5))
    for name in NEW[1:]:
        assert reader(name)(window_ctx(spans)) is None, name
        assert reader(name)(window_ctx()) is None, name
    assert reader(NEW[0])(window_ctx()) is None          # no profile


# -- the device's idle time that no span names -------------------------------------

@pytest.fixture(scope="module")
def small():
    return devtrace.reduce(str(DATA / "small.xplane.pb"))


@pytest.fixture(scope="module")
def planted():
    path = DATA / "span.xplane.pb"
    prof = devtrace.reduce(str(path))
    return prof, host_events(path, prof)


def trace_ctx(profile, spans=()):
    return window_ctx(spans, profile, t0=100.0, t1=100.0 + profile.window_s,
                      steps=8)


def on_ctx_clock(profile, events, name):
    return [Span(n, "1", 100.0 + (s - profile.t0_ns) * 1e-9,
                 100.0 + (e - profile.t0_ns) * 1e-9, {})
            for n, s, e in events if n == name]


def test_without_program_spans_it_is_the_idle_share(small):
    c = trace_ctx(small)
    idle = reader("device.idle_share")(c)
    assert reader("device.idle_unnamed_share")(c) == pytest.approx(idle)
    small.host = host_events(DATA / "small.xplane.pb", small)
    try:                    # the host plane holds no program span's name
        assert reader("device.idle_unnamed_share")(c) == pytest.approx(idle)
    finally:
        del small.host


def test_the_trace_holds_one_window_and_the_mirrored_span(planted):
    prof, host = planted
    names = [n for n, _, _ in host]
    assert names.count("bench.window") == 1      # the instant is not there
    span, = [e - s for n, s, e in host if n == "data.batch"]
    assert 0.2 <= span * 1e-9 < 0.3


@pytest.mark.parametrize("source", ["host_plane", "program_spans"])
def test_a_span_over_the_gap_names_it(planted, source):
    prof, host = planted
    named = on_ctx_clock(prof, host, "data.batch")
    c = trace_ctx(prof, named)
    if source == "host_plane":
        prof.host = host
    try:
        unnamed = reader("device.idle_unnamed_share")(c)
    finally:
        prof.__dict__.pop("host", None)
    idle = reader("device.idle_share")(c)
    span, = named
    assert 0.2 <= span.dur < 0.3
    # the span lies inside the device's gap: all of it is taken off
    assert idle - unnamed == pytest.approx(100 * span.dur / prof.window_s,
                                           abs=100 * 0.002 / prof.window_s)
    # the shadow's and the harness's spans name nothing
    c.spans = [Span("shadow." + s.name, s.track, s.t0, s.t1, {})
               for s in named]
    assert reader("device.idle_unnamed_share")(c) == pytest.approx(idle)


# -- on a run of the harness -------------------------------------------------------

@pytest.mark.parametrize("workload", ["gpt3-xl.ckpt", "gpt3-xl.nockpt"])
def test_a_cpu_run_reports_each_metric_of_its_cell(workload):
    from bench import run as R
    cell = tiny_cell(workload)
    run = R.drive(cell, 7, 0.5)
    d = run["window"]
    c = Context(model=cell.model, batch=4, seq=64, chips=1, t0=d.t0,
                t1=d.t1, steps=d.steps, spans=run["spans"])
    got = {name: reader(name)(c) for name in NEW[1:]}
    ckpt = workload.endswith(".ckpt")
    assert got["input.ms"] > 0 and got["step.dispatch_ms"] > 0
    assert (got["pack.gbps"] is not None) == ckpt
    assert (got["setup.shadow_bootstrap_ms"] is not None) == ckpt
    if ckpt:
        assert got["pack.gbps"] > 0 and got["setup.shadow_bootstrap_ms"] > 0
