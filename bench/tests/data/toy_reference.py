"""A model module for the tests of the reference's seam
(`bench.reference.model_for`): the dense decoder with a router of
``EXPERTS`` outputs read off the final hidden state, and a Switch
load-balance term added to the loss: ``EXPERTS * sum(me * ce)``, ``me`` the
router's mean probability of each expert and ``ce`` the share of tokens
whose first choice it is, both over every token of the batch. The term is
no mean of per-row losses, so this model takes the batch whole."""
from functools import partial

import jax
import jax.numpy as jnp

from bench import flops, reference

EXPERTS = 4
AUX = 0.5


def leaf_specs(cfg):
    return dict(reference.dense_leaf_specs(cfg),
                router=((cfg["d_model"], EXPERTS), "fan_in"))


def loss(params, tokens, labels, cfg, mode):
    def hidden(row):
        x = params["embed"][row]
        for i in range(cfg["num_layers"]):
            x = reference.layer(x, {k: params[k][i]
                                    for k in reference.LAYER_LEAVES},
                                cfg, mode)
        return reference.rmsnorm(x, params["final_norm"], cfg["norm_eps"])

    x = jax.vmap(hidden)(tokens)
    logits = reference.product("bsd,dv->bsv", x, params["unembed"], mode)
    nll = (jax.nn.logsumexp(logits, axis=-1)
           - jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0])
    probs = jax.nn.softmax(
        reference.product("bsd,de->bse", x, params["router"], mode), axis=-1)
    me = jnp.mean(probs, axis=(0, 1))
    ce = jnp.mean(jax.nn.one_hot(jnp.argmax(probs, axis=-1), EXPERTS),
                  axis=(0, 1))
    return jnp.mean(nll) + AUX * EXPERTS * jnp.sum(me * ce)


@partial(jax.jit, static_argnames=("cfg_items", "mode"))
def _value_and_grad(params, tokens, labels, cfg_items, mode):
    return jax.value_and_grad(loss)(params, tokens, labels, dict(cfg_items),
                                    mode)


def batch_grad(params, tokens, labels, cfg, mode="f32", rows=None):
    rows = list(range(tokens.shape[0]) if rows is None else rows)
    items = tuple((k, cfg[k]) for k in reference.MODEL_KEYS)
    value, grads = _value_and_grad(params, jnp.asarray(tokens[rows]),
                                   jnp.asarray(labels[rows]), items, mode)
    return float(value), grads


def flops_per_token(cfg, seq):
    return (flops.dense_flops_per_token(cfg, seq)
            + 6 * cfg["d_model"] * EXPERTS)
