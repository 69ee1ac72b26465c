"""The whole run past the look for a chip, at a CPU size, with the timed
path broken underneath: ``correct`` has to come out false for each fault
a cell can have, and true for the sound run. The control, the reference
in float8 in the program's place, has to fail too."""
import numpy as np
import pytest

import repro.core.shadow as shadow_mod
import repro.train.loop as loop_mod
from bench import compare
from bench import run as R
from bench_tiny import tiny_cell

SEED = 5


def correct(cell, seconds=0.5):
    run = R.drive(cell, SEED, seconds)
    ok, checks = R.judge(cell.limits, R.numbers(cell, run["window"],
                                                R.reference_run(cell, SEED)))
    return ok, checks


def break_step(monkeypatch, change):
    build = loop_mod.build_train_step

    def broken(*a, **kw):
        step = build(*a, **kw)
        return lambda state, batch: change(step, state, batch)
    monkeypatch.setattr(loop_mod, "build_train_step", broken)


@pytest.mark.parametrize("workload", ["gpt3-xl.nockpt", "gpt3-xl.ckpt"])
def test_sound_run_is_correct(workload):
    ok, checks = correct(tiny_cell(workload))
    assert ok, checks


def test_resume_is_read_from_the_program_spans():
    """The resume's consolidate and placement are found among the spans,
    which the ``bench.window`` instant puts on the harness's clock."""
    from bench.context import Context, reader
    cell = tiny_cell("gpt3-xl.ckpt")
    run = R.drive(cell, SEED, 0.5)
    d, resume = run["window"], run["resume"]
    ctx = Context(model=cell.model, batch=4, seq=64, chips=1, t0=d.t0,
                  t1=d.t1, steps=d.steps, spans=run["spans"], resume=resume)
    total_ms = 1e3 * (resume["t_ready"] - resume["t_fail"])
    consolidate = reader("resume.consolidate_ms")(ctx)
    place = reader("resume.place_ms")(ctx)
    assert consolidate is not None and place is not None
    assert 0 < consolidate < total_ms and 0 < place < total_ms


def unchanged(step, state, batch):
    new, metrics, grads = step(state, batch)
    return state, metrics, grads


def half_batch(step, state, batch):
    return step(state, {k: v[: v.shape[0] // 2] for k, v in batch.items()})


def token_altered(step, state, batch):
    """One token of the feed altered where it is produced."""
    tokens = batch["tokens"]
    tokens = tokens.at[0, 5].set((tokens[0, 5] + 1) % 1024)
    return step(state, dict(batch, tokens=tokens))


def answer_altered(step, state, batch):
    """One leaf of the weights the step produces altered by 1%."""
    new, metrics, grads = step(state, batch)
    new.params["wq"] = new.params["wq"] * (1 + 1e-2)
    return new, metrics, grads


@pytest.mark.parametrize("fault", [unchanged, half_batch, token_altered,
                                   answer_altered])
@pytest.mark.parametrize("workload", ["gpt3-xl.nockpt", "gpt3-xl.ckpt"])
def test_broken_step_is_not_correct(monkeypatch, workload, fault):
    break_step(monkeypatch, fault)
    ok, checks = correct(tiny_cell(workload))
    assert not ok, checks


def _alter(ckpt):
    w = np.array(ckpt["params"]["wq"])
    w.flat[0] += 1e-3 * np.abs(w).max()
    ckpt["params"]["wq"] = w
    return ckpt


def test_shadow_answer_altered_is_not_correct(monkeypatch):
    """One element of the shadow's consolidated checkpoint altered where it
    is produced."""
    consolidate = shadow_mod.ShadowCluster.consolidate
    monkeypatch.setattr(shadow_mod.ShadowCluster, "consolidate",
                        lambda self, *a, **kw: _alter(
                            consolidate(self, *a, **kw)))
    ok, checks = correct(tiny_cell("gpt3-xl.ckpt"))
    assert not ok
    assert checks["shadow_gap"]["value"] > checks["shadow_gap"]["limit"]


def test_restore_answer_altered_is_not_correct(monkeypatch):
    """The checkpoint the restore hands the loop altered in one element:
    the state placed on the chip is not the checkpoint it came from."""
    import repro.core.checkpoint as ck_mod
    restore = ck_mod.CheckmateCheckpointer.restore
    monkeypatch.setattr(ck_mod.CheckmateCheckpointer, "restore",
                        lambda self: _alter(restore(self)))
    ok, checks = correct(tiny_cell("gpt3-xl.ckpt"))
    assert not ok
    assert checks["restore_gap"]["value"] > checks["restore_gap"]["limit"]


def test_shadow_step_skipped_is_not_correct(monkeypatch):
    apply = shadow_mod.ShadowNode._apply

    def skipping(self, step, lr, flats, grad_scale):
        if step != 2:
            return apply(self, step, lr, flats, grad_scale)
    monkeypatch.setattr(shadow_mod.ShadowNode, "_apply", skipping)
    ok, checks = correct(tiny_cell("gpt3-xl.ckpt"))
    assert not ok
    assert checks["shadow_gap"]["value"] is None or \
        checks["shadow_gap"]["value"] > checks["shadow_gap"]["limit"]


def test_float8_control_is_not_correct():
    cell = tiny_cell("gpt3-xl.nockpt")
    ref = R.reference_run(cell, SEED)
    control = R.reference_run(cell, SEED, mode="fp8")
    ok, checks = R.judge(cell.limits,
                         compare.training_numbers(control, ref))
    assert not ok, checks
