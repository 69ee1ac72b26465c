"""pack.reuse_share: the share of the pack's bytes that went into wire
buffers given back by the shadow, on hand-made spans and on a CPU run of
the harness."""
import pytest

from bench.context import Context, Span, reader
from bench_tiny import tiny_cell


def window_ctx(spans=(), t0=100.0, t1=110.0, steps=5):
    return Context(model={}, batch=1, seq=1, chips=1, t0=t0, t1=t1,
                   steps=steps, spans=list(spans))


def packs(reused, t=101.0, nbytes=400, **extra):
    return [Span("bucket.pack", "1", t + i, t + i + 0.5,
                 {"step": i + 1, "bytes": nbytes, "buckets": 4,
                  "reused": r, **extra})
            for i, r in enumerate(reused)]


def test_median_share_over_the_window():
    spans = packs([400, 400, 0, 400, 100])
    spans += [Span("bucket.pack", "1", 99.0, 99.5,          # before: set-up
                   {"step": 0, "bytes": 400, "reused": 0})]
    assert reader("pack.reuse_share")(window_ctx(spans)) == 100.0
    assert reader("pack.reuse_share")(
        window_ctx(packs([0, 100, 200]))) == pytest.approx(25.0)


def test_a_program_without_the_arg_reads_nothing():
    """The parent's program: its pack has bytes and no ``reused``."""
    spans = [Span("bucket.pack", "1", 101.0 + i, 101.5 + i,
                  {"step": i + 1, "bytes": 400, "buckets": 4})
             for i in range(3)]
    assert reader("pack.reuse_share")(window_ctx(spans)) is None
    assert reader("pack.reuse_share")(window_ctx()) is None


@pytest.mark.parametrize("workload", ["gpt3-xl.ckpt", "gpt3-xl.nockpt"])
def test_a_cpu_run_reads_a_warm_pool(workload):
    """After the set-up steps every pack of the window reuses the buffers
    the shadow gave back; a run with no checkpointer packs nothing."""
    from bench import run as R
    cell = tiny_cell(workload)
    run = R.drive(cell, 7, 0.5)
    d = run["window"]
    c = Context(model=cell.model, batch=4, seq=64, chips=1, t0=d.t0,
                t1=d.t1, steps=d.steps, spans=run["spans"])
    got = reader("pack.reuse_share")(c)
    if workload.endswith(".ckpt"):
        assert got == 100.0
    else:
        assert got is None
