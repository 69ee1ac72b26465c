"""HLO cost extractor: exact on known programs (incl. while trip counts)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.launch.hlo_analysis import HloModule, analyze_hlo_text


def _compile(f, *specs):
    return jax.jit(f).lower(*specs).compile()


def test_single_matmul_flops_exact():
    x = jax.ShapeDtypeStruct((256, 512), jnp.float32)
    w = jax.ShapeDtypeStruct((512, 128), jnp.float32)
    c = _compile(lambda a, b: a @ b, x, w)
    cost = analyze_hlo_text(c.as_text())
    assert cost.flops == 2 * 256 * 512 * 128


def test_scan_multiplies_trip_count():
    def f(x, w):
        def body(x, _):
            return jnp.tanh(x @ w), None
        x, _ = jax.lax.scan(body, x, None, length=8)
        return x
    x = jax.ShapeDtypeStruct((512, 512), jnp.float32)
    w = jax.ShapeDtypeStruct((512, 512), jnp.float32)
    cost = analyze_hlo_text(_compile(f, x, w).as_text())
    matmul = 2 * 512 ** 3
    assert abs(cost.flops - 8 * (matmul + 512 * 512)) / (8 * matmul) < 0.01
    # XLA's own analysis counts the body once — ours must be ~8x larger
    xla = _compile(f, x, w).cost_analysis()
    assert cost.flops > 7 * xla["flops"]


def test_nested_scan():
    def f(x, w):
        def outer(x, _):
            def inner(y, _):
                return jnp.tanh(y @ w), None
            y, _ = jax.lax.scan(inner, x, None, length=3)
            return y, None
        x, _ = jax.lax.scan(outer, x, None, length=4)
        return x
    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    w = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    cost = analyze_hlo_text(_compile(f, x, w).as_text())
    matmul = 2 * 128 ** 3
    assert abs(cost.flops - 12 * (matmul + 128 * 128)) / (12 * matmul) < 0.02


def test_batched_dot_flops():
    x = jax.ShapeDtypeStruct((4, 64, 32), jnp.float32)
    w = jax.ShapeDtypeStruct((4, 32, 16), jnp.float32)
    c = _compile(lambda a, b: jnp.einsum("bij,bjk->bik", a, b), x, w)
    cost = analyze_hlo_text(c.as_text())
    assert cost.flops == 2 * 4 * 64 * 32 * 16


def test_bytes_nonzero_and_bounded():
    x = jax.ShapeDtypeStruct((1024, 1024), jnp.float32)
    c = _compile(lambda a: (a * 2 + 1).sum(), x)
    cost = analyze_hlo_text(c.as_text())
    nbytes = 1024 * 1024 * 4
    assert nbytes <= cost.bytes <= 6 * nbytes


def test_tuple_types_with_index_comments_parse():
    """Regression: (a, b, ..., /*index=5*/ c, ...) tuple types must parse."""
    txt = """
HloModule test, is_scheduled=true

%body (p: (s32[], f32[8,8], f32[8,8], f32[8,8], f32[8,8], /*index=5*/f32[8,8])) -> (s32[], f32[8,8], f32[8,8], f32[8,8], f32[8,8], /*index=5*/f32[8,8]) {
  %p = (s32[], f32[8,8]{1,0}, f32[8,8]{1,0}, f32[8,8]{1,0}, f32[8,8]{1,0}, /*index=5*/f32[8,8]{1,0}) parameter(0)
  %g0 = s32[] get-tuple-element(%p), index=0
  %g1 = f32[8,8]{1,0} get-tuple-element(%p), index=1
  %g2 = f32[8,8]{1,0} get-tuple-element(%p), index=2
  %d = f32[8,8]{1,0} dot(%g1, %g2), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  ROOT %t = (s32[], f32[8,8]{1,0}, f32[8,8]{1,0}, f32[8,8]{1,0}, f32[8,8]{1,0}, /*index=5*/f32[8,8]{1,0}) tuple(%g0, %d, %g2, %g2, %g2, %g2)
}

%cond (p2: (s32[], f32[8,8], f32[8,8], f32[8,8], f32[8,8], /*index=5*/f32[8,8])) -> pred[] {
  %p2 = (s32[], f32[8,8]{1,0}, f32[8,8]{1,0}, f32[8,8]{1,0}, f32[8,8]{1,0}, /*index=5*/f32[8,8]{1,0}) parameter(0)
  %c = s32[] constant(5)
  %i = s32[] get-tuple-element(%p2), index=0
  ROOT %lt = pred[] compare(%i, %c), direction=LT
}

ENTRY %main (a: (s32[], f32[8,8], f32[8,8], f32[8,8], f32[8,8], /*index=5*/f32[8,8])) -> (s32[], f32[8,8], f32[8,8], f32[8,8], f32[8,8], /*index=5*/f32[8,8]) {
  %a = (s32[], f32[8,8]{1,0}, f32[8,8]{1,0}, f32[8,8]{1,0}, f32[8,8]{1,0}, /*index=5*/f32[8,8]{1,0}) parameter(0)
  ROOT %w = (s32[], f32[8,8]{1,0}, f32[8,8]{1,0}, f32[8,8]{1,0}, f32[8,8]{1,0}, /*index=5*/f32[8,8]{1,0}) while(%a), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"5"},"known_init_step":{"init":"0","step":"1"}}
}
"""
    cost = analyze_hlo_text(txt)
    assert cost.flops == 5 * 2 * 8 * 8 * 8


def test_collective_parse():
    txt = """
HloModule t, is_scheduled=true

ENTRY %main (x: f32[64,128]) -> f32[64,128] {
  %x = f32[64,128]{1,0} parameter(0)
  ROOT %ar = f32[64,128]{1,0} all-reduce(%x), channel_id=1, replica_groups=[1,4]<=[4], to_apply=%add
}

%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(%a, %b)
}
"""
    cost = analyze_hlo_text(txt)
    assert cost.collective_bytes == 64 * 128 * 4
    assert cost.per_collective == {"all-reduce": 64 * 128 * 4}
