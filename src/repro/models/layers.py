"""Core neural-net layers (pure-functional, dict-pytree parameters).

No flax/haiku dependency: each layer is an ``init(key, ...) -> params`` plus
an ``apply(params, x, ...) -> y`` pair.  Parameters are nested dicts whose
leaf *names* drive the sharding rules in ``repro.sharding.rules``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def truncated_normal(key, shape, dtype, stddev):
    return stddev * jax.random.truncated_normal(key, -2.0, 2.0, shape).astype(dtype)


# ----------------------------------------------------------------------------
# Dense
# ----------------------------------------------------------------------------

def dense_init(key, d_in: int, d_out: int, *, bias: bool = False, dtype=jnp.float32):
    stddev = (1.0 / d_in) ** 0.5
    p = {"kernel": truncated_normal(key, (d_in, d_out), dtype, stddev)}
    if bias:
        p["bias"] = jnp.zeros((d_out,), dtype)
    return p


def dense(p, x, *, compute_dtype=None):
    """Matmul in the activation dtype (params are cast down, not the
    activations up) — the standard bf16-compute / fp32-master convention."""
    k = p["kernel"]
    if compute_dtype is not None:
        x = x.astype(compute_dtype)
    with jax.named_scope("weight_cast"):
        k = k.astype(x.dtype)
        b = p["bias"].astype(x.dtype) if "bias" in p else None
    y = x @ k
    if b is not None:
        y = y + b
    return y


# ----------------------------------------------------------------------------
# Norms
# ----------------------------------------------------------------------------

def rmsnorm_init(d: int, dtype=jnp.float32):
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(p, x, *, eps: float = 1e-6):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(dt)


def layernorm_init(d: int, dtype=jnp.float32):
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layernorm(p, x, *, eps: float = 1e-5):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean((x32 - mu) ** 2, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)).astype(dt)


# ----------------------------------------------------------------------------
# Embedding
# ----------------------------------------------------------------------------

def embedding_init(key, vocab: int, d: int, dtype=jnp.float32):
    return {"embedding": truncated_normal(key, (vocab, d), dtype, 0.02)}


def embed(p, ids, *, compute_dtype=None):
    e = p["embedding"]
    out = jnp.take(e, ids, axis=0)
    if compute_dtype is not None:
        out = out.astype(compute_dtype)
    return out


def unembed(p, x):
    """Logits = x @ Eᵀ (tied) — fp32 accumulation for the softmax path."""
    return jnp.einsum(
        "...d,vd->...v", x, p["embedding"], preferred_element_type=jnp.float32
    )


# ----------------------------------------------------------------------------
# SwiGLU MLP (llama-family FFN)
# ----------------------------------------------------------------------------

def swiglu_init(key, d_model: int, d_ff: int, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(k1, d_model, d_ff, dtype=dtype),
        "w_up": dense_init(k2, d_model, d_ff, dtype=dtype),
        "w_down": dense_init(k3, d_ff, d_model, dtype=dtype),
    }


def swiglu(p, x, *, compute_dtype=None):
    g = dense(p["w_gate"], x, compute_dtype=compute_dtype)
    u = dense(p["w_up"], x, compute_dtype=compute_dtype)
    return dense(p["w_down"], jax.nn.silu(g) * u, compute_dtype=compute_dtype)


# ----------------------------------------------------------------------------
# GELU MLP (whisper FFN)
# ----------------------------------------------------------------------------

def gelu_mlp_init(key, d_model: int, d_ff: int, dtype=jnp.float32):
    k1, k2 = jax.random.split(key)
    return {
        "w_in": dense_init(k1, d_model, d_ff, bias=True, dtype=dtype),
        "w_out": dense_init(k2, d_ff, d_model, bias=True, dtype=dtype),
    }


def gelu_mlp(p, x, *, compute_dtype=None):
    h = dense(p["w_in"], x, compute_dtype=compute_dtype)
    return dense(p["w_out"], jax.nn.gelu(h), compute_dtype=compute_dtype)


# ----------------------------------------------------------------------------
# Rotary position embeddings (standard + multimodal M-RoPE)
# ----------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float) -> jnp.ndarray:
    """(head_dim/2,) inverse frequencies."""
    return 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )


def yarn_frequencies(head_dim: int, theta: float, factor: float, beta_fast: float,
                     beta_slow: float, original_max_pos: int) -> jnp.ndarray:
    """(head_dim/2,) YaRN inverse frequencies (arXiv:2309.00071, as
    DeepSeek-V3 uses them): the plain frequencies where a dimension turns
    more than ``beta_fast`` times over the pre-training context, those
    divided by ``factor`` where it turns fewer than ``beta_slow`` times,
    and a linear ramp between the two dimensions."""
    def dim_at(rotations):
        return head_dim * math.log(original_max_pos / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(dim_at(beta_fast)), 0)
    high = min(math.ceil(dim_at(beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low) / (high - low), 0, 1)
    extra = rope_frequencies(head_dim, theta)
    return extra / factor * ramp + extra * (1.0 - ramp)


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature: 0.1 · mscale · ln(factor) + 1 (1 at
    factor ≤ 1).  DeepSeek-V3 multiplies the softmax scale by its square."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
               inv_freq: jnp.ndarray | None = None) -> jnp.ndarray:
    """Rotate ``x`` (..., T, H, D) by per-token ``positions`` (..., T);
    ``inv_freq`` replaces the plain frequencies (YaRN)."""
    d = x.shape[-1]
    inv = rope_frequencies(d, theta) if inv_freq is None else inv_freq  # (D/2,)
    ang = positions[..., :, None, None].astype(jnp.float32) * inv  # (..., T, 1, D/2)
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(
    x: jnp.ndarray, positions3: jnp.ndarray, theta: float, sections: tuple
) -> jnp.ndarray:
    """Multimodal RoPE (Qwen2-VL [arXiv:2409.12191]).

    ``positions3``: (3, ..., T) — temporal / height / width position ids.
    ``sections``: frequency-band split of head_dim/2, e.g. (16, 24, 24).
    Each band takes its rotation angle from the corresponding position id.
    """
    d = x.shape[-1]
    assert sum(sections) == d // 2, "mrope sections must cover head_dim/2"
    inv = rope_frequencies(d, theta)  # (D/2,)
    # select which of the 3 position streams drives each frequency band
    band = jnp.repeat(
        jnp.arange(3), jnp.asarray(sections), total_repeat_length=d // 2
    )  # (D/2,) in {0,1,2}
    pos = positions3[band, ..., :]  # (D/2, ..., T) — gather per band
    pos = jnp.moveaxis(pos, 0, -1)  # (..., T, D/2)
    ang = pos[..., :, None, :].astype(jnp.float32) * inv  # (..., T, 1, D/2)
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ----------------------------------------------------------------------------
# Losses
# ----------------------------------------------------------------------------

def cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray, *, mask=None):
    """Mean token cross entropy; logits (..., V) fp32, labels (...) int."""
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
