"""The reduction from a profiler trace to metrics, on a small trace
recorded on one v5e by ``record_trace.py`` (eight runs of a jitted
``train_step``, with 200 ms of nothing on the device between the fourth
and the fifth)."""
from pathlib import Path

import pytest

from bench import devtrace
from bench.context import Context, Span, reader
from bench.run import breakdown

TRACE = Path(__file__).resolve().parent / "data" / "small.xplane.pb"


@pytest.fixture(scope="module")
def profile():
    return devtrace.reduce(str(TRACE))


def ctx(profile, spans=()):
    return Context(model={}, batch=1, seq=1, chips=1, t0=100.0,
                   t1=100.0 + profile.window_s, steps=8, spans=list(spans),
                   profile=profile)


def test_one_chip_busy_inside_the_window(profile):
    assert len(profile.chips) == 1
    assert profile.chips[0].name == "/device:TPU:0"
    assert 0.2 < profile.window_s < 5.0
    assert 0.0 < profile.busy_s() < profile.window_s - 0.19
    busy = profile.chips[0].busy
    assert all(s < e for s, e in busy)
    assert all(a[1] < b[0] for a, b in zip(busy, busy[1:]))   # merged
    assert profile.t0_ns <= busy[0][0] and busy[-1][1] <= profile.t1_ns


def test_the_planted_gap_is_the_longest(profile):
    gaps = sorted(profile.gaps(0), key=lambda g: g[0] - g[1])
    assert 0.19 <= (gaps[0][1] - gaps[0][0]) * 1e-9 < 0.4
    idle = sum(e - s for s, e in profile.gaps(0)) * 1e-9
    assert idle == pytest.approx(profile.window_s - profile.busy_s(),
                                 rel=1e-9)


def test_step_program_time(profile):
    runs = [m for m in profile.chips[0].modules if "train_step" in m[0]]
    assert len(runs) == 8
    ms = reader("train_step.device_ms")(ctx(profile))
    assert ms == pytest.approx(sum(e - s for _, s, e in runs) * 1e-6 / 8)
    assert 0 < ms < 200


def test_idle_share(profile):
    share = reader("device.idle_share")(ctx(profile))
    assert share == pytest.approx(
        100 * (1 - profile.busy_s() / profile.window_s))
    assert 5 < share < 100


def test_step_mfu_is_model_flops_over_busy_device_time(profile):
    from bench.flops import flops_per_step
    model = {"num_layers": 1, "d_model": 128, "num_heads": 1,
             "num_kv_heads": 1, "head_dim": 128, "d_ff": 256,
             "vocab_size": 256, "mlp": "swiglu"}
    c = ctx(profile)
    c.model, c.batch, c.seq = model, 4, 256
    c.peaks = {"bf16_flops_per_s": 197e12}
    mfu = reader("step.mfu")(c)
    want = 100 * flops_per_step(model, 4, 256) * 8 / (profile.busy_s()
                                                      * 197e12)
    assert mfu == pytest.approx(want, rel=1e-12)
    c.profile = None
    assert reader("step.mfu")(c) is None


def test_breakdown_names_the_gap_by_the_open_span(profile):
    gaps = sorted(profile.gaps(0), key=lambda g: g[0] - g[1])
    s, e = gaps[0]
    mid = 100.0 + ((s + e) / 2 - profile.t0_ns) * 1e-9
    spans = [Span("step.compute", "1", mid - 1.0, mid + 1.0, {}),
             Span("capture.d2h", "1", mid - 0.5, mid + 0.5, {}),
             Span("shadow.apply", "5", mid - 0.1, mid + 0.1, {})]
    b = breakdown(profile, ctx(profile, spans))
    assert b["idle_gaps"][0][0] == "capture.d2h"     # innermost, trainer's
    assert 1 <= len(b["device_ops"]) <= 10
    assert len(b["idle_gaps"]) <= 10
    total = sum(v for _, v in b["device_ops"])
    assert total <= profile.busy_s() * 1.0001 or len(b["device_ops"]) == 10


def test_no_trace_reads_nothing():
    c = Context(model={}, batch=1, seq=1, chips=1, t0=0.0, t1=1.0, steps=1)
    for name in ("train_step.device_ms", "device.idle_share", "capture.ms",
                 "checkpoint.on_step_ms", "shadow.apply_ms",
                 "shadow.lag_wait_ms", "resume.consolidate_ms",
                 "resume.place_ms", "step.mfu"):
        assert reader(name)(c) is None, name
