"""Flash-attention Pallas kernel vs pure-jnp oracle (shape/dtype sweep)."""

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.flash_attention import ops, ref

SHAPES = [
    # (B, T, S, Hq, Hkv, D, causal, window)
    (2, 64, 64, 4, 2, 32, True, 0),
    (1, 128, 128, 8, 8, 64, True, 0),
    (2, 96, 96, 4, 1, 16, True, 0),  # padding (96 % 32 != 0 with bq=64)
    (2, 64, 64, 8, 2, 32, True, 24),  # sliding window
    (1, 48, 48, 4, 4, 64, False, 0),  # bidirectional
]


def _mk(key, B, T, S, Hq, Hkv, D, dtype):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, T, Hq, D)).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, Hkv, D)).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, Hkv, D)).astype(dtype)
    return q, k, v


@pytest.mark.parametrize("case", SHAPES)
def test_flash_matches_ref_f32(case):
    B, T, S, Hq, Hkv, D, causal, window = case
    q, k, v = _mk(jax.random.key(sum(case[:6])), B, T, S, Hq, Hkv, D, jnp.float32)
    out = ops.flash_attention(q, k, v, causal=causal, window=window, bq=32, bk=32)
    exp = ref.attention_ref(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
        causal=causal, window=window,
    ).transpose(0, 2, 1, 3)
    assert float(jnp.max(jnp.abs(out - exp))) < 2e-5


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_flash_dtypes(dtype):
    q, k, v = _mk(jax.random.key(9), 2, 64, 64, 4, 2, 32, dtype)
    out = ops.flash_attention(q, k, v, bq=32, bk=32)
    exp = ref.attention_ref(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
    ).transpose(0, 2, 1, 3)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    assert out.dtype == dtype
    assert float(jnp.max(jnp.abs(out.astype(jnp.float32) - exp.astype(jnp.float32)))) < tol


@pytest.mark.parametrize("f32_products", [True, False], ids=["f32_products", "bf16_products"])
def test_flash_bf16_at_training_width(f32_products):
    """bf16 operands at a GQA training width (head 128, 4 query heads a KV
    head, 256 positions) against the f32 oracle on the same values: the
    default upcasts the products' inputs to f32, so only the bf16 output's
    rounding is left (outputs here stay under 4, where a bf16 ulp is
    2^-6); with the operands' dtype the softmax weights are also rounded
    to bf16 (2^-9 relative), which stays inside that ulp."""
    q, k, v = _mk(jax.random.key(11), 1, 256, 256, 8, 2, 128, jnp.bfloat16)
    out = ops.flash_attention(q, k, v, f32_products=f32_products, bq=64, bk=64)
    up = lambda a: a.astype(jnp.float32).transpose(0, 2, 1, 3)
    exp = ref.attention_ref(up(q), up(k), up(v)).transpose(0, 2, 1, 3)
    assert out.dtype == jnp.bfloat16
    assert float(jnp.max(jnp.abs(exp))) < 4
    assert float(jnp.max(jnp.abs(out.astype(jnp.float32) - exp))) < 2 ** -6


def test_block_shape_independence():
    """Result must not depend on the BlockSpec tile size."""
    q, k, v = _mk(jax.random.key(3), 1, 128, 128, 4, 4, 32, jnp.float32)
    o1 = ops.flash_attention(q, k, v, bq=32, bk=32)
    o2 = ops.flash_attention(q, k, v, bq=64, bk=128)
    assert float(jnp.max(jnp.abs(o1 - o2))) < 2e-5


def test_matches_model_attention_path():
    """Kernel should agree with the model's _sdpa reference semantics."""
    from repro.models.attention import _sdpa, causal_mask

    q, k, v = _mk(jax.random.key(4), 2, 64, 64, 4, 2, 32, jnp.float32)
    out_kernel = ops.flash_attention(q, k, v, bq=32, bk=32)
    mask = causal_mask(64, 64)
    out_model = _sdpa(q, k, v, mask, scale=32 ** -0.5)
    assert float(jnp.max(jnp.abs(out_kernel - out_model))) < 2e-5
