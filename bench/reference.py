"""Plain float32 reference of the benchmark's dense decoder and its AdamW step.

Written from the configuration file alone (``bench/configs/*.json``): the
block those files state (RMSNorm, rotate-half RoPE, causal softmax
attention, SwiGLU, an untied head), the weight convention they state, and
AdamW with decoupled weight decay. It imports nothing of the system under
test and takes nothing it made: weights come from the seed by the stated
convention, tokens from `bench/gen.py`.

Every matrix product runs at ``Precision.HIGHEST``. A batch is taken one
row at a time, each row's layers rematerialised, so that three steps of a
model that fills the chip fit beside their own optimizer state.

``mode="fp8"`` is the control: the same reference with every matrix
product's operands rounded to scaled float8 (e4m3 forward, e5m2 for the
gradients flowing back), the precision below the bfloat16 the
configuration computes in.
"""
from __future__ import annotations

import math
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


# -- weights -------------------------------------------------------------------

def leaf_specs(cfg: dict) -> dict:
    """name -> (shape, init) for every weight the configuration has."""
    L, d, f, V = (cfg["num_layers"], cfg["d_model"], cfg["d_ff"],
                  cfg["vocab_size"])
    hq = cfg["num_heads"] * cfg["head_dim"]
    hkv = cfg["num_kv_heads"] * cfg["head_dim"]
    specs = {
        "attn_norm": ((L, d), "ones"),
        "wq": ((L, d, hq), "fan_in"),
        "wk": ((L, d, hkv), "fan_in"),
        "wv": ((L, d, hkv), "fan_in"),
        "wo": ((L, hq, d), "fan_in"),
        "mlp_norm": ((L, d), "ones"),
        "w_gate": ((L, d, f), "fan_in"),
        "w_up": ((L, d, f), "fan_in"),
        "w_down": ((L, f, d), "fan_in"),
        "embed": ((V, d), "normal"),
        "final_norm": ((d,), "ones"),
        "unembed": ((d, V), "fan_in"),
    }
    if cfg["mlp"] != "swiglu" or cfg["tie_embeddings"]:
        raise ValueError("the reference states only the SwiGLU block with "
                         "an untied head")
    return specs


def seed_key(seed: int):
    """The threefry key of a seed of up to 64 bits: [high word, low word]."""
    return jnp.asarray([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                       dtype=jnp.uint32)


def leaf_keys(cfg: dict, seed: int) -> dict:
    """One key per weight: the seed's key split over the sorted names."""
    names = sorted(leaf_specs(cfg))
    keys = jax.random.split(seed_key(seed), len(names))
    return dict(zip(names, keys))


def init_leaf(key, shape, init) -> jax.Array:
    """normal: N(0, 0.02^2); fan_in: N(0, 1/fan_in), fan_in the
    second-to-last dimension; ones: 1. All float32."""
    if init == "ones":
        return jnp.ones(shape, jnp.float32)
    if init == "normal":
        return jax.random.normal(key, shape, jnp.float32) * 0.02
    fan = shape[-2] if len(shape) >= 2 else shape[-1]
    return jax.random.normal(key, shape, jnp.float32) * (1.0 / math.sqrt(fan))


def init_params(cfg: dict, seed: int) -> dict:
    keys = leaf_keys(cfg, seed)
    return {name: init_leaf(keys[name], shape, init)
            for name, (shape, init) in leaf_specs(cfg).items()}


# -- float8 rounding for the control -------------------------------------------

def _round8(x, dtype):
    x = x.astype(jnp.float32)
    top = float(jnp.finfo(dtype).max)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@jax.custom_vjp
def _fp8_operand(x):
    return _round8(x, jnp.float8_e4m3fn)


_fp8_operand.defvjp(lambda x: (_round8(x, jnp.float8_e4m3fn), None),
                    lambda _, g: (g,))


@jax.custom_vjp
def _fp8_cotangent(y):
    return y


_fp8_cotangent.defvjp(lambda y: (y, None),
                      lambda _, g: (_round8(g, jnp.float8_e5m2),))


def _product(spec, a, b, mode):
    if mode == "fp8":
        return _fp8_cotangent(jnp.einsum(spec, _fp8_operand(a),
                                         _fp8_operand(b), precision=HIGHEST))
    return jnp.einsum(spec, a, b, precision=HIGHEST)


# -- the block -------------------------------------------------------------------

def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, theta):
    """x: (seq, heads, head_dim); rotate-half over positions 0..seq-1."""
    s, _, hd = x.shape
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def layer(x, lp, cfg, mode):
    s = x.shape[0]
    h, kv, hd = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    eps = cfg["norm_eps"]
    mm = partial(_product, mode=mode)
    xn = rmsnorm(x, lp["attn_norm"], eps)
    q = rope(mm("sd,de->se", xn, lp["wq"]).reshape(s, h, hd),
             cfg["rope_theta"])
    k = rope(mm("sd,de->se", xn, lp["wk"]).reshape(s, kv, hd),
             cfg["rope_theta"])
    v = mm("sd,de->se", xn, lp["wv"]).reshape(s, kv, hd)
    k = jnp.repeat(k, h // kv, axis=1)
    v = jnp.repeat(v, h // kv, axis=1)
    scores = mm("qhd,khd->hqk", q, k) / math.sqrt(hd)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    o = mm("hqk,khd->qhd", probs, v).reshape(s, h * hd)
    x = x + mm("se,ed->sd", o, lp["wo"])
    xn = rmsnorm(x, lp["mlp_norm"], eps)
    hidden = (jax.nn.silu(mm("sd,df->sf", xn, lp["w_gate"]))
              * mm("sd,df->sf", xn, lp["w_up"]))
    return x + mm("sf,fd->sd", hidden, lp["w_down"])


MODEL_KEYS = ("num_layers", "d_model", "num_heads", "num_kv_heads",
              "head_dim", "d_ff", "vocab_size", "norm_eps", "rope_theta",
              "mlp", "tie_embeddings")

LAYER_LEAVES = ("attn_norm", "wq", "wk", "wv", "wo", "mlp_norm",
                "w_gate", "w_up", "w_down")


def row_loss(params, tokens, labels, cfg, mode):
    """Mean next-token cross entropy of one row (seq,) of tokens."""
    x = params["embed"][tokens]
    body = jax.checkpoint(partial(layer, cfg=cfg, mode=mode))
    for i in range(cfg["num_layers"]):
        x = body(x, {k: params[k][i] for k in LAYER_LEAVES})
    x = rmsnorm(x, params["final_norm"], cfg["norm_eps"])
    logits = _product("sd,dv->sv", x, params["unembed"], mode)
    nll = (jax.nn.logsumexp(logits, axis=-1)
           - jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0])
    return jnp.mean(nll)


@partial(jax.jit, static_argnames=("cfg_items", "mode"), donate_argnums=(0,))
def _accumulate(acc, params, tokens, labels, cfg_items, mode):
    cfg = dict(cfg_items)
    loss, g = jax.value_and_grad(row_loss)(params, tokens, labels, cfg, mode)
    return (acc[0] + loss, jax.tree.map(jnp.add, acc[1], g))


@partial(jax.jit, static_argnames=("opt_items",), donate_argnums=(0, 2, 3))
def _adamw(params, grads, mu, nu, step, lr, opt_items):
    """AdamW with bias correction and decoupled weight decay, every leaf."""
    o = dict(opt_items)
    b1, b2 = o["b1"], o["b2"]
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
    c1, c2 = 1 - b1 ** step, 1 - b2 ** step
    params = jax.tree.map(
        lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2) + o["eps"])
                                  + o["weight_decay"] * p),
        params, mu, nu)
    return params, mu, nu


def batch_grad(params, tokens, labels, cfg, mode="f32", rows=None):
    """Mean loss and mean gradient over ``rows`` of the batch (all rows by
    default), one row per call."""
    rows = range(tokens.shape[0]) if rows is None else rows
    items = tuple((k, cfg[k]) for k in MODEL_KEYS)
    acc = (jnp.zeros((), jnp.float32),
           jax.tree.map(jnp.zeros_like, params))
    for r in rows:
        acc = _accumulate(acc, params, jnp.asarray(tokens[r]),
                          jnp.asarray(labels[r]), items, mode)
    n = float(len(rows))
    return float(acc[0]) / n, jax.tree.map(lambda g: g / n, acc[1])


def _leaf_reading(x, idx):
    x = x.astype(jnp.float32)
    return jnp.sqrt(jnp.sum(x * x)), jnp.take(x.reshape(-1), idx)


_leaf_reading_jit = jax.jit(_leaf_reading)


def leaf_readings(tree: dict, idx: dict, scale: float = 1.0) -> dict:
    """Per leaf: the norm and the sampled elements of ``scale * leaf``."""
    out = {}
    for name in sorted(idx):
        n, s = _leaf_reading_jit(tree[name], jnp.asarray(idx[name]))
        out[name] = (float(n) * scale, np.asarray(s, np.float64) * scale)
    return out


@jax.jit
def _change(p, p0, idx):
    return _leaf_reading(p - p0, idx)


def change_readings(params: dict, cfg: dict, seed: int, idx: dict) -> dict:
    """Per leaf: norm and sampled elements of ``params - initial weights``,
    the initial weights made again from the seed one leaf at a time."""
    keys = leaf_keys(cfg, seed)
    out = {}
    for name, (shape, init) in sorted(leaf_specs(cfg).items()):
        p0 = init_leaf(keys[name], shape, init)
        n, s = _change(params[name], p0, jnp.asarray(idx[name]))
        out[name] = (float(n), np.asarray(s, np.float64))
        del p0
    return out


def run(cfg: dict, opt: dict, seed: int, batches, idx: dict, steps: int = 3,
        mode: str = "f32", rows=None) -> dict:
    """``steps`` AdamW steps from the seed's weights on ``batches(i)`` (the
    tokens and labels of step i, 0-based). Returns the losses, the first
    step's gradient (``grad``) and the change of the weights after the
    last step (``change``), each leaf as (norm, sampled elements)."""
    params = init_params(cfg, seed)
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    items = tuple(sorted((k, float(opt[k]))
                         for k in ("b1", "b2", "eps", "weight_decay")))
    out = {"loss": []}
    for i in range(steps):
        tokens, labels = batches(i)
        loss, grads = batch_grad(params, tokens, labels, cfg, mode, rows)
        out["loss"].append(loss)
        if i == 0:
            out["grad"] = leaf_readings(grads, idx)
        params, mu, nu = _adamw(params, grads, mu, nu,
                                jnp.float32(i + 1), jnp.float32(opt["lr"]),
                                items)
        del grads
    del mu, nu
    out["change"] = change_readings(params, cfg, seed, idx)
    return out


def sample_index(cfg: dict, seed: int, per_leaf: int = 4096) -> dict:
    """Flat element indices compared per leaf, drawn from the seed."""
    rng = np.random.default_rng([seed, 0x5EED])
    out = {}
    for name, (shape, _) in sorted(leaf_specs(cfg).items()):
        size = int(np.prod(shape))
        k = min(size, per_leaf)
        out[name] = np.sort(rng.choice(size, size=k, replace=False)
                            ).astype(np.int32)
    return out
