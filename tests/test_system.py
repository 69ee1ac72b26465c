"""End-to-end system behaviour: the full Checkmate pipeline (train ->
capture -> bucket -> shadow -> consolidate -> recover) plus data pipeline
determinism and the async timeliness invariant."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import repro.configs as C
from repro.core.buckets import layout_for_tree
from repro.core.checkpoint import CheckmateCheckpointer
from repro.core.recovery import FailurePlan
from repro.core.shadow import ShadowCluster
from repro.data.synthetic import SyntheticStream
from repro.dist.sharding import ShardingRules, make_smoke_mesh
from repro.optim import OptimizerConfig
from repro.train.loop import train
from repro.train.step import make_train_state


@pytest.fixture(scope="module")
def env():
    mesh = make_smoke_mesh()
    cfg = C.get("llama3.2-3b").reduced()
    return cfg, ShardingRules(mesh), OptimizerConfig(lr=1e-3)


def test_end_to_end_checkmate_async(env):
    """Async shadow plane keeps per-iteration checkpoints bit-identical and
    keeps up with training (the §6.3 timeliness condition)."""
    cfg, rules, opt = env
    s0 = make_train_state(jax.random.PRNGKey(0), cfg, rules)
    shadow = ShadowCluster(layout_for_tree(s0.params), opt, n_nodes=2,
                           async_mode=True)
    shadow.bootstrap(s0.params, s0.mu, s0.nu, 0)
    state, stats = train(cfg, rules, steps=8, batch=4, seq=32, opt=opt,
                         state=s0, checkpointer=CheckmateCheckpointer(shadow))
    ckpt = shadow.consolidate(timeout=60)
    assert ckpt["step"] == 8
    for k in state.params:
        assert np.array_equal(np.asarray(state.params[k]),
                              ckpt["params"][k]), k
    s = shadow.stats()
    assert s.lag == 0
    assert s.mean_apply_s < max(stats.mean_iter, 1e-3) * 10
    shadow.shutdown()


@pytest.mark.parametrize("consumer", [True, False])
def test_loop_frees_each_steps_device_gradients(env, consumer):
    """Once a step's gradients are captured (or, with no consumer, at once)
    the loop holds none on the device: at each step hook every parameter
    shape is live exactly as often as params, mu and nu hold it, so two
    steps' gradients never share device memory."""
    _, rules, opt = env
    # widths no other test uses, so only this run's arrays match them
    cfg = C.get("llama3.2-3b").reduced(d_model=56, d_ff=120, vocab_size=97)
    s0 = make_train_state(jax.random.PRNGKey(0), cfg, rules)
    shapes = [v.shape for v in s0.params.values() if v.ndim >= 2]
    ck = None
    if consumer:
        shadow = ShadowCluster(layout_for_tree(s0.params), opt)
        shadow.bootstrap(s0.params, s0.mu, s0.nu, 0)
        ck = CheckmateCheckpointer(shadow)
    seen = []

    def hook(step, state, stats):
        live = [a.shape for a in jax.live_arrays()
                if a.dtype == jnp.float32 and a.shape in shapes]
        seen.append(all(live.count(s) == 3 * shapes.count(s)
                        for s in shapes))

    train(cfg, rules, steps=3, batch=4, seq=16, opt=opt, state=s0,
          checkpointer=ck, step_hook=hook)
    assert seen == [True] * 3


def test_loss_decreases(env):
    cfg, rules, opt = env
    _, stats = train(cfg, rules, steps=12, batch=8, seq=32, opt=opt, seed=5)
    assert np.mean(stats.losses[-3:]) < np.mean(stats.losses[:3])


def test_data_determinism_and_seek():
    cfg = C.get("tinyllama-1.1b").reduced()
    a = SyntheticStream(cfg, 4, 32, seed=9)
    b = SyntheticStream(cfg, 4, 32, seed=9).seek(3)
    batches_a = [a.batch_at(i) for i in range(5)]
    np.testing.assert_array_equal(batches_a[3]["tokens"],
                                  next(b)["tokens"])
    # different steps differ
    assert not np.array_equal(batches_a[0]["tokens"],
                              batches_a[1]["tokens"])


def test_failure_without_checkpointer_raises(env):
    cfg, rules, opt = env
    with pytest.raises(RuntimeError):
        train(cfg, rules, steps=6, batch=4, seq=32, opt=opt,
              failure_plan=FailurePlan((3,)))


def test_straggler_flagging(env):
    """The loop's EMA straggler detector flags nothing on a uniform run."""
    cfg, rules, opt = env
    _, stats = train(cfg, rules, steps=8, batch=4, seq=32, opt=opt,
                     straggler_factor=50.0)
    assert stats.straggler_flags == []


def test_grads_cover_all_params(env):
    """The capture payload (grads out of train_step) covers every leaf —
    Checkmate's correctness precondition."""
    cfg, rules, opt = env
    from repro.models import registry
    from repro.train.step import build_train_step
    state = make_train_state(jax.random.PRNGKey(0), cfg, rules)
    step = jax.jit(build_train_step(cfg, rules.mesh, rules, opt,
                                    lambda s: 1e-3))
    batch = {"tokens": jnp.ones((4, 32), jnp.int32),
             "labels": jnp.ones((4, 32), jnp.int32)}
    _, _, grads = step(state, batch)
    assert set(grads) == set(registry.param_specs(cfg))
