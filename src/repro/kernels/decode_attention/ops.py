"""Jit'd wrapper for the decode-attention kernel."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention.kernel import NEG_INF, decode_attention_fwd


@partial(jax.jit, static_argnames=("bk", "interpret"))
def decode_attention(
    q: jnp.ndarray,  # (B, Hq, D)
    k: jnp.ndarray,  # (B, S, Hkv, D)
    v: jnp.ndarray,
    valid_len: jnp.ndarray,  # (B,) or scalar
    *,
    bk: int = 512,
    interpret: bool | None = None,
) -> jnp.ndarray:
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, Hq, D = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    bk_eff = min(bk, S)

    pad = (-S) % bk_eff
    if pad:
        widths = [(0, 0)] * 4
        widths[1] = (0, pad)
        k = jnp.pad(k, widths)
        v = jnp.pad(v, widths)
    Sp = S + pad

    vl = jnp.broadcast_to(jnp.asarray(valid_len).reshape(-1), (B,))
    bias = jnp.where(jnp.arange(Sp)[None, :] < vl[:, None], 0.0, NEG_INF).astype(
        jnp.float32
    )[:, None, :]

    qg = q.reshape(B, Hkv, G, D)
    out = decode_attention_fwd(
        qg, jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2), bias,
        bk=bk_eff, interpret=interpret,
    )
    return out.reshape(B, Hq, D)


@jax.jit
def decode_attention_xla(
    q: jnp.ndarray,  # (B, Hq, D)
    k: jnp.ndarray,  # (B, S, Hkv, D)
    v: jnp.ndarray,
    valid_len: jnp.ndarray,  # (B,) or scalar
) -> jnp.ndarray:
    """Jitted XLA reference for the decode kernel — the explicit
    ``use_kernel`` fallback on non-TPU backends.

    Mirrors the kernel's single-pass math exactly (additive 0/-1e30 bias,
    max → exp → masked-p @ v → divide-by-l, all f32), rather than
    ``softmax(logits) @ v``: on a single KV block (``bk ≥ S``) the two
    paths are bit-identical, so flipping ``use_kernel`` never changes a
    served token.
    """
    B, Hq, D = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    vl = jnp.broadcast_to(jnp.asarray(valid_len).reshape(-1), (B,))
    bias = jnp.where(
        jnp.arange(S)[None, :] < vl[:, None], 0.0, NEG_INF
    ).astype(jnp.float32)  # (B, S)

    qg = q.reshape(B, Hkv, G, D).astype(jnp.float32)
    s = jnp.einsum(
        "bhgd,bshd->bhgs", qg, k.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    ) * (D ** -0.5)
    s = s + bias[:, None, None, :]
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    p = jnp.where(bias[:, None, None, :] > NEG_INF / 2, p, 0.0)
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum(
        "bhgs,bshd->bhgd", p, v.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    denom = jnp.where(l > 0.0, l, 1.0)
    out = acc / denom[..., None]
    return out.reshape(B, Hq, D).astype(q.dtype)
