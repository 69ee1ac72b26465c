"""The plain float32 reference (bench/reference.py) against the system's
dense model on a tiny configuration, both in float32 on the CPU."""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bench import gen, reference
from bench.run import model_config
from bench_tiny import tiny_cell


@pytest.fixture(scope="module")
def job():
    cell = tiny_cell("gpt3-xl.nockpt")
    from repro.dist.sharding import ShardingRules, make_smoke_mesh
    cfg = dataclasses.replace(model_config(cell.model),
                              compute_dtype="float32")
    return cell, cfg, ShardingRules(make_smoke_mesh())


def test_weights_follow_the_stated_convention(job):
    """The reference makes the program's initial weights from the seed,
    bit for bit, without calling it."""
    from repro.models import registry
    cell, cfg, rules = job
    seed = 2**31 + 3
    prog = registry.init_params(reference.seed_key(seed), cfg, rules)
    ref = reference.init_params(cell.model, seed)
    assert sorted(prog) == sorted(ref)
    for k in ref:
        np.testing.assert_array_equal(np.asarray(prog[k]), np.asarray(ref[k]))


def test_loss_and_gradients_match_the_program_in_float32(job):
    from repro.models import registry
    cell, cfg, rules = job
    seed = 7
    params = reference.init_params(cell.model, seed)
    tokens, labels = gen.batch_at(cell.traffic, cfg.vocab_size, seed, 0)
    batch = {"tokens": jnp.asarray(tokens), "labels": jnp.asarray(labels)}
    loss_p, g_p = jax.value_and_grad(
        lambda p: registry.loss_fn(p, cfg, rules, batch))(params)
    loss_r, g_r = reference.batch_grad(params, tokens, labels, cell.model)
    assert abs(float(loss_p) - loss_r) <= 1e-5 * abs(loss_r)
    for k in g_r:
        a, b = np.asarray(g_p[k]), np.asarray(g_r[k])
        assert np.linalg.norm(a - b) <= 1e-4 * np.linalg.norm(b), k


def test_program_stream_is_the_generators():
    from repro.data.synthetic import SyntheticStream
    cell = tiny_cell("gpt3-xl.nockpt")
    cfg = model_config(cell.model)
    seed = 2**31 + 9
    stream = SyntheticStream(cfg, cell.traffic["batch"], cell.traffic["seq"],
                             seed=seed)
    for step in (0, 1, 5):
        tokens, labels = gen.batch_at(cell.traffic, cfg.vocab_size, seed,
                                      step)
        b = stream.batch_at(step)
        np.testing.assert_array_equal(b["tokens"], tokens)
        np.testing.assert_array_equal(b["labels"], labels)
    rows = {tuple(r) for r in tokens}
    assert len(rows) == tokens.shape[0]          # every row differs


def test_adamw_step_matches_the_program_optimizer(job):
    from repro.optim import OptimizerConfig, apply_updates, init_state
    cell, cfg, _ = job
    opt = cell.traffic["optimizer"]
    params = reference.init_params(cell.model, 1)
    grads = jax.tree.map(lambda p: jnp.sin(p * 3.0) * 1e-3, params)
    state = apply_updates(init_state(params), grads, OptimizerConfig(**opt),
                          opt["lr"])
    items = tuple(sorted((k, float(opt[k]))
                         for k in ("b1", "b2", "eps", "weight_decay")))
    zeros = jax.tree.map(jnp.zeros_like, params)
    p, m, v = reference._adamw(
        jax.tree.map(jnp.copy, params), grads, zeros,
        jax.tree.map(jnp.zeros_like, params), jnp.float32(1),
        jnp.float32(opt["lr"]), items)
    for k in params:
        np.testing.assert_allclose(np.asarray(state.params[k]),
                                   np.asarray(p[k]), rtol=1e-6, atol=1e-9)
