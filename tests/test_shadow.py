"""Shadow cluster: bit-exact replication, partitioning, async timeliness
(paper §4.2, §6.5)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import repro.configs as C
from repro.core.buckets import layout_for_tree
from repro.core.channel import InProcessChannel, StepEvent
from repro.core.shadow import ShadowCluster, plan_shadow_nodes
from repro.dist.sharding import ShardingRules, make_smoke_mesh
from repro.optim import OptimizerConfig, apply_updates, init_state
from repro.train.step import make_train_state


@pytest.fixture(scope="module")
def setup():
    mesh = make_smoke_mesh()
    cfg = C.get("tinyllama-1.1b").reduced()
    rules = ShardingRules(mesh)
    state = make_train_state(jax.random.PRNGKey(0), cfg, rules)
    return cfg, rules, state


def _random_grads(params, seed):
    rng = np.random.default_rng(seed)
    return {k: rng.standard_normal(v.shape).astype(np.float32) * 0.01
            for k, v in params.items()}


@pytest.mark.parametrize("n_nodes", [1, 3])
@pytest.mark.parametrize("opt_name", ["adamw", "adam", "sgd"])
def test_bit_exact_replication(setup, n_nodes, opt_name):
    """Shadow replay == training update, bitwise, for every optimizer the
    paper names as functional (SGD/Adam/AdamW, §4.2.4)."""
    cfg, rules, state0 = setup
    opt = OptimizerConfig(name=opt_name, lr=1e-3)
    layout = layout_for_tree(state0.params, cap_bytes=32 * 1024)
    shadow = ShadowCluster(layout, opt, n_nodes=n_nodes)
    shadow.bootstrap(state0.params, state0.mu, state0.nu, 0)

    state = state0
    apply_fn = jax.jit(lambda s, g: apply_updates(s, g, opt, 1e-3))
    for step in range(1, 4):
        grads = _random_grads(state0.params, step)
        state = apply_fn(state, {k: jnp.asarray(v) for k, v in grads.items()})
        shadow.on_gradients(step, 1e-3, grads)

    ckpt = shadow.consolidate()
    assert ckpt["step"] == 3
    for k in state.params:
        assert np.array_equal(np.asarray(state.params[k]), ckpt["params"][k]), k
        assert np.array_equal(np.asarray(state.mu[k]), ckpt["mu"][k]), k
        assert np.array_equal(np.asarray(state.nu[k]), ckpt["nu"][k]), k


def test_partition_is_disjoint_and_total(setup):
    cfg, rules, state0 = setup
    layout = layout_for_tree(state0.params, cap_bytes=32 * 1024)
    shadow = ShadowCluster(layout, OptimizerConfig(), n_nodes=4)
    all_leaves = [l for n in shadow.nodes for l in n._leaves]
    assert sorted(all_leaves) == sorted(state0.params)   # total, disjoint


def test_async_mode_and_stats(setup):
    cfg, rules, state0 = setup
    layout = layout_for_tree(state0.params)
    shadow = ShadowCluster(layout, OptimizerConfig(), n_nodes=2,
                           async_mode=True)
    shadow.bootstrap(state0.params, state0.mu, state0.nu, 0)
    for step in range(1, 6):
        shadow.on_gradients(step, 1e-3, _random_grads(state0.params, step))
    ckpt = shadow.consolidate(timeout=30)
    assert ckpt["step"] == 5
    s = shadow.stats()
    assert s.lag == 0
    assert s.mean_apply_s > 0
    shadow.shutdown()


def test_grad_scale_matches_clipped_training(setup):
    """Global-norm clipping: shadow applies the scale computed on the
    training side (metadata), staying bit-identical."""
    cfg, rules, state0 = setup
    opt = OptimizerConfig(lr=1e-3, grad_clip=0.5)
    layout = layout_for_tree(state0.params)
    shadow = ShadowCluster(layout, OptimizerConfig(lr=1e-3), n_nodes=2)
    shadow.bootstrap(state0.params, state0.mu, state0.nu, 0)

    grads = _random_grads(state0.params, 0)
    gn = float(np.sqrt(sum((g ** 2).sum() for g in grads.values())))
    scale = min(1.0, 0.5 / (gn + 1e-9))
    state = jax.jit(lambda s, g: apply_updates(s, g, opt, 1e-3))(
        state0, {k: jnp.asarray(v) for k, v in grads.items()})
    shadow.on_gradients(1, 1e-3, grads, grad_scale=scale)
    ckpt = shadow.consolidate()
    for k in state.params:
        np.testing.assert_allclose(np.asarray(state.params[k]),
                                   ckpt["params"][k], rtol=1e-6, atol=1e-7)


def test_plan_shadow_nodes(setup):
    """§4.2.4 profiling: returns a node count that fits the iteration."""
    cfg, rules, state0 = setup
    layout = layout_for_tree(state0.params)
    tree = {k: np.asarray(v) for k, v in state0.params.items()}
    n, t = plan_shadow_nodes(layout, OptimizerConfig(), iter_time_s=10.0,
                             trial_tree=tree)
    assert n == 1                      # 10s budget >> tiny model apply time
    n2, _ = plan_shadow_nodes(layout, OptimizerConfig(),
                              iter_time_s=max(t / 4, 1e-6), trial_tree=tree)
    assert n2 >= n


@pytest.mark.parametrize("flat", [True, False])
@pytest.mark.parametrize("cap_bytes", [16, 32 * 1024])
def test_shadow_state_lives_on_the_clusters_device(setup, flat, cap_bytes):
    """Every node buffer and every consolidated leaf sits on the device the
    cluster chose (the host CPU), and a consolidated leaf is a copy: the
    next apply, which donates the node's buffers, leaves it intact. At
    ``cap_bytes=16`` every leaf is a bucket of its own, so a leaf spans
    its whole flat buffer."""
    cfg, rules, state0 = setup
    layout = layout_for_tree(state0.params, cap_bytes=cap_bytes)
    shadow = ShadowCluster(layout, OptimizerConfig(name="adamw", lr=1e-3),
                           n_nodes=2, flat=flat)
    assert shadow.device == jax.devices("cpu")[0]
    shadow.bootstrap(state0.params, state0.mu, state0.nu, 0)
    chan = InProcessChannel()
    chan.open(layout)

    def deliver(step):
        chan.send(StepEvent(step=step, lr=1e-3,
                            grads=_random_grads(state0.params, step)))
        for d in chan.poll():
            shadow.on_delivery(d)

    deliver(1)
    for node in shadow.nodes:
        buffers = node.buffers()
        assert buffers
        assert all(a.devices() == {shadow.device} for a in buffers)
    ckpt = shadow.consolidate()
    before = {k: np.array(v) for k, v in ckpt["params"].items()}
    deliver(2)
    for part in ("params", "mu", "nu"):
        assert set(ckpt[part]) == set(state0.params)
        for leaf in ckpt[part].values():
            assert isinstance(leaf, jax.Array)
            assert leaf.devices() == {shadow.device}
    for k, v in ckpt["params"].items():
        assert np.array_equal(np.asarray(v), before[k]), k
