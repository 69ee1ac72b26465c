"""Pallas kernels vs ref.py oracles — shape/dtype sweeps (interpret mode)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref

RNG = np.random.default_rng(42)


@pytest.mark.parametrize("shape", [(128,), (1000,), (257, 129), (4, 33, 7),
                                   (128 * 256,), (3, 128, 128)])
@pytest.mark.parametrize("pdtype", [jnp.float32, jnp.bfloat16])
def test_fused_adamw_matches_ref(shape, pdtype):
    p = jnp.asarray(RNG.standard_normal(shape), pdtype)
    g = jnp.asarray(RNG.standard_normal(shape), jnp.float32)
    m = jnp.asarray(RNG.standard_normal(shape), jnp.float32)
    v = jnp.asarray(np.abs(RNG.standard_normal(shape)), jnp.float32)
    po, mo, vo = ops.fused_adamw(p, g, m, v, 5.0, 3e-4)
    pr, mr, vr = ref.adamw_ref(p, g, m, v, 5.0, 3e-4)
    np.testing.assert_allclose(np.asarray(po, np.float32),
                               np.asarray(pr, np.float32),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(mo, mr, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(vo, vr, rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("hyp", [dict(b1=0.9, b2=0.999, eps=1e-8, wd=0.0),
                                 dict(b1=0.8, b2=0.95, eps=1e-6, wd=0.2)])
def test_fused_adamw_hyperparams(hyp):
    shape = (515,)
    p = jnp.asarray(RNG.standard_normal(shape), jnp.float32)
    g = jnp.asarray(RNG.standard_normal(shape), jnp.float32)
    m = jnp.zeros(shape, jnp.float32)
    v = jnp.zeros(shape, jnp.float32)
    po, mo, vo = ops.fused_adamw(p, g, m, v, 1.0, 1e-3, **hyp)
    pr, mr, vr = ref.adamw_ref(p, g, m, v, 1.0, 1e-3, **hyp)
    np.testing.assert_allclose(po, pr, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("b,s,h,d", [(2, 128, 2, 16), (1, 256, 4, 32),
                                     (2, 64, 2, 8), (1, 64, 1, 64)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_ref(b, s, h, d, causal):
    q = jnp.asarray(RNG.standard_normal((b, s, h, d)), jnp.float32) * 0.3
    k = jnp.asarray(RNG.standard_normal((b, s, h, d)), jnp.float32) * 0.3
    v = jnp.asarray(RNG.standard_normal((b, s, h, d)), jnp.float32)
    o = ops.flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)
    orf = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(o, orf, rtol=2e-5, atol=2e-5)


def test_flash_attention_bf16():
    b, s, h, d = 1, 128, 2, 32
    q = jnp.asarray(RNG.standard_normal((b, s, h, d)), jnp.bfloat16) * 0.3
    k = jnp.asarray(RNG.standard_normal((b, s, h, d)), jnp.bfloat16) * 0.3
    v = jnp.asarray(RNG.standard_normal((b, s, h, d)), jnp.bfloat16)
    o = ops.flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    orf = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(orf, np.float32),
                               rtol=0.05, atol=0.05)


def test_flash_attention_uneven_blocks():
    """q and kv block sizes differ."""
    b, s, h, d = 1, 128, 1, 16
    q = jnp.asarray(RNG.standard_normal((b, s, h, d)), jnp.float32) * 0.5
    k = jnp.asarray(RNG.standard_normal((b, s, h, d)), jnp.float32) * 0.5
    v = jnp.asarray(RNG.standard_normal((b, s, h, d)), jnp.float32)
    o = ops.flash_attention(q, k, v, causal=True, block_q=64, block_k=32)
    orf = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(o, orf, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("n", [128, 1000, 12345, 128 * 300])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
def test_packed_copy(n, dtype):
    if dtype == jnp.int32:
        x = jnp.asarray(RNG.integers(-100, 100, n), dtype)
    else:
        x = jnp.asarray(RNG.standard_normal(n), dtype)
    np.testing.assert_array_equal(np.asarray(ops.packed_copy(x)),
                                  np.asarray(x))


def test_bucket_pack_matches_ref():
    leaves = [jnp.asarray(RNG.standard_normal(s), jnp.float32)
              for s in [(3, 4), (7,), (2, 2, 2)]]
    total = sum(x.size for x in leaves)
    flat_ref = ref.bucket_pack_ref(leaves, total)
    from repro.kernels.bucket_pack import pack_leaves
    padded_total = total + ((-total) % 128)
    flat = pack_leaves(leaves, padded_total)
    np.testing.assert_array_equal(np.asarray(flat[:total]),
                                  np.asarray(flat_ref))
    back = ref.bucket_unpack_ref(flat[:total], [x.shape for x in leaves])
    for a, b in zip(back, leaves):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fused_adamw_flat_follows_the_lowering_platform(monkeypatch):
    """The shadow's fused AdamW is the jnp pass when lowered for the CPU and
    the Mosaic kernel when lowered for a TPU, in the same process, without
    asking which backend is the default."""
    def no_default_backend():
        raise AssertionError("kernel choice read the default backend")
    monkeypatch.setattr(jax, "default_backend", no_default_backend)
    flat = jax.ShapeDtypeStruct((1000,), jnp.float32)
    scalar = jax.ShapeDtypeStruct((), jnp.float32)
    traced = ops.fused_adamw_flat.trace(flat, flat, flat, flat, scalar, scalar)
    cpu = traced.lower(lowering_platforms=("cpu",)).as_text()
    tpu = traced.lower(lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" not in cpu
    assert "tpu_custom_call" in tpu
