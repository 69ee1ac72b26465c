"""Jit'd public wrappers for the Pallas kernels.

Each wrapper picks its implementation from the platform the call is LOWERED
for (``jax.lax.platform_dependent``), never from the process's default
backend: a TPU host also runs CPU computations (the shadow plane is pinned
to the host CPU, `repro.core.shadow`), and each must get its own path. On
TPU the kernels compile to Mosaic; elsewhere they run in Pallas interpret
mode — the kernel body executes in Python per grid cell, which is what the
correctness sweeps exercise.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import bucket_pack as _bp
from repro.kernels import flash_attention as _fa
from repro.kernels import fused_adamw as _fw
from repro.kernels import ref as _ref

LANES = 128


def _kernel(fn, *args, **kw):
    """``fn(*args, interpret=...)``: Mosaic when lowered for TPU, the
    interpreter on any other platform."""
    return jax.lax.platform_dependent(
        *args, tpu=partial(fn, interpret=False, **kw),
        default=partial(fn, interpret=True, **kw))


def _pad_to(x, mult):
    pad = (-x.size) % mult
    if pad:
        x = jnp.concatenate([jnp.ravel(x), jnp.zeros((pad,), x.dtype)])
    return jnp.ravel(x), pad


@partial(jax.jit, static_argnames=("b1", "b2", "eps", "wd", "block_rows"))
def fused_adamw(p, g, m, v, step, lr, b1=0.9, b2=0.95, eps=1e-8, wd=0.1,
                block_rows=256):
    """Fused AdamW on arbitrary-shaped leaves (flattened + padded)."""
    shape = p.shape
    n = p.size
    mult = LANES * block_rows
    pf, _ = _pad_to(p, mult)
    gf, _ = _pad_to(g, mult)
    mf, _ = _pad_to(m, mult)
    vf, _ = _pad_to(v, mult)
    po, mo, vo = _kernel(_fw.fused_adamw_flat, pf, gf, mf, vf, step, lr,
                         b1=b1, b2=b2, eps=eps, wd=wd, block_rows=block_rows)
    return (po[:n].reshape(shape), mo[:n].reshape(shape),
            vo[:n].reshape(shape))


@partial(jax.jit, static_argnames=("b1", "b2", "eps", "wd", "block_rows"))
def fused_adamw_flat(p, g, m, v, step, lr, scale=1.0, b1=0.9, b2=0.95,
                     eps=1e-8, wd=0.1, block_rows=1024):
    """Fused AdamW over one contiguous flat bucket buffer — the shadow hot
    loop (`repro.core.shadow`), one pass per state element.

    Lowered for TPU this is the Mosaic kernel (`fused_adamw.fused_adamw_flat`,
    512 KiB/operand VMEM tiles). Lowered for the CPU — where the shadow
    plane runs — it is the pure-jnp pass (`ref.adamw_ref`), which XLA fuses
    into a single elementwise pass over the buffer; the interpret-mode
    kernel stays the correctness oracle in tests/test_kernels.py. ``scale``
    (the global-norm clip factor computed on the training side) is folded
    into the same pass.
    """
    gs = g.astype(jnp.float32) * scale
    hyp = dict(b1=b1, b2=b2, eps=eps, wd=wd)

    def mosaic(p, gs, m, v, step, lr):
        n = p.size
        mult = LANES * block_rows
        pf, _ = _pad_to(p, mult)
        gf, _ = _pad_to(gs, mult)
        mf, _ = _pad_to(m, mult)
        vf, _ = _pad_to(v, mult)
        po, mo, vo = _fw.fused_adamw_flat(pf, gf, mf, vf, step, lr, **hyp,
                                          block_rows=block_rows,
                                          interpret=False)
        return po[:n], mo[:n], vo[:n]

    return jax.lax.platform_dependent(
        p, gs, m, v, step, lr, tpu=mosaic,
        default=partial(_ref.adamw_ref, **hyp))


@partial(jax.jit, static_argnames=("causal", "block_q", "block_k"))
def flash_attention(q, k, v, causal=True, block_q=512, block_k=512):
    """(b, s, h, d) attention; kv heads must already be expanded to h."""
    return _kernel(_fa.flash_attention, q, k, v, causal=causal,
                   block_q=block_q, block_k=block_k)


@jax.jit
def packed_copy(flat):
    n = flat.size
    mult = LANES
    f, pad = _pad_to(flat, mult)
    rows = f.size // LANES
    # choose the largest block that divides rows
    block = rows
    for cand in (4096, 2048, 1024, 512, 256, 128, 64, 32, 16, 8, 4, 2, 1):
        if rows % cand == 0:
            block = cand
            break
    out = _kernel(_bp.packed_copy, f, block_rows=block)
    return out[:n]
