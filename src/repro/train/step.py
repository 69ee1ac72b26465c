"""Train / prefill / decode step builders.

``build_train_step`` produces the jit-able step with:
  * microbatched gradient accumulation (lax.scan),
  * ZeRO-1 gradient reduce-scatter + sharded optimizer update + param
    all-gather (GSPMD, via sharding constraints),
  * the reduce-scattered gradient tree returned as an output — Checkmate's
    exactly-once capture point (each device owns a disjoint grad slice).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from repro import obs as _obs
from repro.configs.base import ModelConfig, ShapeConfig
from repro.dist.sharding import ShardingRules
from repro.models import registry
from repro.optim import OptimizerConfig, TrainState, apply_updates, init_state
from repro.optim.functional import global_norm
from repro.optim.sharded import zero1_shardings


def state_shardings(cfg: ModelConfig, rules: ShardingRules):
    """(params, mu, nu, step) shardings; mu/nu are ZeRO-1 sharded."""
    aspecs = registry.abstract_params(cfg, rules)
    pshard = jax.tree.map(lambda a: a.sharding, aspecs)
    zshard = (zero1_shardings(aspecs, rules.mesh) if cfg.zero1 else pshard)
    return TrainState(params=pshard, mu=zshard, nu=zshard,
                      step=NamedSharding(rules.mesh, jax.sharding.PartitionSpec()))


def build_train_step(cfg: ModelConfig, mesh, rules: ShardingRules,
                     opt: OptimizerConfig, lr_fn: Callable,
                     return_grads: bool = True):
    """Returns train_step(state, batch) -> (state, metrics[, grads])."""
    aspecs = registry.abstract_params(cfg, rules)
    pshard = jax.tree.map(lambda a: a.sharding, aspecs)
    zshard = (zero1_shardings(aspecs, mesh) if cfg.zero1 else pshard)

    cd = jnp.dtype(cfg.compute_dtype)

    def loss_fn(params, microbatch):
        # PERF (EXPERIMENTS.md §Perf iter 1): cast the whole tree to the
        # compute dtype BEFORE the layer scan, keeping the param shardings —
        # FSDP all-gathers and weight reads then move bf16, not f32.
        params_c = jax.tree.map(
            lambda p, s: jax.lax.with_sharding_constraint(p.astype(cd), s),
            params, pshard)
        return registry.loss_fn(params_c, cfg, rules, microbatch)

    def train_step(state: TrainState, batch):
        mb = cfg.microbatches

        if mb <= 1:
            loss, grads = jax.value_and_grad(loss_fn)(state.params, batch)
        else:
            def reshape(x):
                b = x.shape[0]
                return x.reshape((mb, b // mb) + x.shape[1:])
            mbatch = jax.tree.map(reshape, batch)

            def micro(carry, one):
                gacc, lacc = carry
                l, g = jax.value_and_grad(loss_fn)(state.params, one)
                # PERF (§Perf iter 2): reduce-scatter each microbatch's
                # grads to the ZeRO-1 layout inside the scan; the carry is
                # dp-sharded, so GSPMD emits RS (half an all-reduce's bytes).
                g = jax.tree.map(
                    lambda t, s: jax.lax.with_sharding_constraint(t, s),
                    g, zshard)
                return (jax.tree.map(jnp.add, gacc, g), lacc + l), None

            zeros = jax.tree.map(
                lambda a, s: jax.lax.with_sharding_constraint(
                    jnp.zeros(a.shape, jnp.float32), s),
                state.params, zshard)
            (grads, loss), _ = jax.lax.scan(micro, (zeros, 0.0), mbatch)
            grads = jax.tree.map(lambda g: g / mb, grads)
            loss = loss / mb

        # --- Checkmate capture point: reduce-scattered final gradients ------
        grads = jax.tree.map(
            lambda g, s: jax.lax.with_sharding_constraint(g, s), grads, zshard)

        lr = lr_fn(state.step)
        new_state = apply_updates(state, grads, opt, lr)
        # ZeRO-1: moments stay dp-sharded; params all-gather back.
        new_state = TrainState(
            params=jax.tree.map(
                lambda p, s: jax.lax.with_sharding_constraint(p, s),
                new_state.params, pshard),
            mu=jax.tree.map(
                lambda m, s: jax.lax.with_sharding_constraint(m, s),
                new_state.mu, zshard),
            nu=jax.tree.map(
                lambda v, s: jax.lax.with_sharding_constraint(v, s),
                new_state.nu, zshard),
            step=new_state.step)

        metrics = {"loss": loss, "grad_norm": global_norm(grads), "lr": lr}
        if return_grads:
            return new_state, metrics, grads
        return new_state, metrics

    ob = _obs.get()
    if ob.enabled:
        # the capture payload: f32 reduced gradients, one per param leaf
        nbytes = sum(4 * math.prod(a.shape)
                     for a in jax.tree.leaves(aspecs))
        ob.metrics.gauge("capture_bytes",
                         "Per-step reduced-gradient capture size").set(
            nbytes, arch=cfg.name)
    return train_step


def make_train_state(rng, cfg: ModelConfig, rules: ShardingRules) -> TrainState:
    params = registry.init_params(rng, cfg, rules)
    state = init_state(params)
    sh = state_shardings(cfg, rules)
    mu = jax.tree.map(jax.device_put, state.mu, sh.mu)
    nu = jax.tree.map(jax.device_put, state.nu, sh.nu)
    return TrainState(params=params, mu=mu, nu=nu, step=state.step)


def abstract_train_state(cfg: ModelConfig, rules: ShardingRules) -> TrainState:
    aspecs = registry.abstract_params(cfg, rules)
    sh = state_shardings(cfg, rules)
    mu = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, jnp.float32, sharding=s),
        aspecs, sh.mu)
    nu = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, jnp.float32, sharding=s),
        aspecs, sh.nu)
    return TrainState(params=aspecs, mu=mu, nu=nu,
                      step=jax.ShapeDtypeStruct((), jnp.int32))


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------

def build_prefill_step(cfg: ModelConfig, shape: ShapeConfig,
                       rules: ShardingRules):
    def prefill_step(params, inputs):
        extra = {k: v for k, v in inputs.items() if k != "tokens"}
        if cfg.family in ("audio", "vlm"):
            cache, logits = registry.prefill(
                params, cfg, rules, inputs["tokens"], shape.seq_len, **extra)
        else:
            cache, logits = registry.prefill(
                params, cfg, rules, inputs["tokens"], shape.seq_len)
        return cache, logits
    return prefill_step


def build_decode_step(cfg: ModelConfig, rules: ShardingRules,
                      greedy: bool = True):
    def serve_step(params, cache, token):
        logits, cache = registry.decode_step(params, cfg, rules, cache, token)
        next_token = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return next_token[:, None], cache
    return serve_step
