"""Fused AdamW Pallas kernel — the shadow/optimizer hot loop on TPU.

AdamW is deeply memory-bound: ~15 flops against 28 B/param moved
(read p,m,v,g = 16 B; write p,m,v = 12 B at f32). Unfused jnp materializes
the m/v intermediates and roughly doubles HBM traffic; this kernel performs
the whole read-modify-write in ONE pass through VMEM tiles.

The parameter tree is flattened to a 1-D buffer (bucket layout — see
repro.core.buckets), viewed as (rows, 128) lanes, and the grid walks row
blocks of 1024 x 128 (512 KiB/operand tiles in f32: p,m,v,g in + p,m,v out,
double-buffered = 7 MiB of VMEM, inside v5e's 16 MiB default scoped limit).
The scalar hyperparameters, bias corrections included, are computed outside
the kernel and read from SMEM: Mosaic has no scalar ``pow``.

This mirrors the paper's shadow-node optimization story (§5: AVX-512
streaming memcpy, 8x) translated to the TPU memory hierarchy: the win is
touching HBM exactly once per state element.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
DEFAULT_BLOCK_ROWS = 1024


def _adamw_kernel(hyp_ref, p_ref, g_ref, m_ref, v_ref,
                  po_ref, mo_ref, vo_ref):
    """One (block_rows, 128) tile: fully element-wise in VMEM."""
    lr = hyp_ref[0]
    b1 = hyp_ref[1]
    b2 = hyp_ref[2]
    eps = hyp_ref[3]
    wd = hyp_ref[4]
    bc1 = hyp_ref[5]
    bc2 = hyp_ref[6]

    p = p_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    m = m_ref[...]
    v = v_ref[...]

    m_new = b1 * m + (1.0 - b1) * g
    v_new = b2 * v + (1.0 - b2) * g * g
    upd = (m_new / bc1) / (jnp.sqrt(v_new / bc2) + eps) + wd * p
    po_ref[...] = (p - lr * upd).astype(po_ref.dtype)
    mo_ref[...] = m_new
    vo_ref[...] = v_new


def fused_adamw_flat(p, g, m, v, step, lr, b1=0.9, b2=0.95, eps=1e-8,
                     wd=0.1, block_rows: int = DEFAULT_BLOCK_ROWS,
                     interpret: bool = True):
    """p,g,m,v: flat f32 arrays whose size is a multiple of 128*block_rows
    after padding (handled by ops.fused_adamw)."""
    n = p.size
    rows = n // LANES
    block_rows = min(block_rows, rows)
    grid = (rows // block_rows,)

    shape2d = (rows, LANES)
    p2, g2 = p.reshape(shape2d), g.reshape(shape2d)
    m2, v2 = m.reshape(shape2d), v.reshape(shape2d)
    step = jnp.asarray(step, jnp.float32)
    hyp = jnp.stack([jnp.asarray(x, jnp.float32) for x in
                     (lr, b1, b2, eps, wd, 1.0 - b1 ** step,
                      1.0 - b2 ** step)])

    tile = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)

    po, mo, vo = pl.pallas_call(
        _adamw_kernel,
        grid=grid,
        in_specs=[smem, tile, tile, tile, tile],
        out_specs=[tile, tile, tile],
        out_shape=[
            jax.ShapeDtypeStruct(shape2d, p.dtype),
            jax.ShapeDtypeStruct(shape2d, jnp.float32),
            jax.ShapeDtypeStruct(shape2d, jnp.float32),
        ],
        interpret=interpret,
    )(hyp, p2, g2, m2, v2)
    return po.reshape(n), mo.reshape(n), vo.reshape(n)
