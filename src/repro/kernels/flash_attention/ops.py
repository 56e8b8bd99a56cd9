"""Jit'd public wrapper for the flash-attention kernel.

Accepts the model's (B, T, H, D) layout, handles padding to block multiples
and GQA head grouping, and dispatches to the Pallas kernel (interpret mode
off-TPU).  ``flash_attention`` mirrors ``repro.models.attention._sdpa``
semantics for the cache-free train/prefill path.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention_fwd


def _pad_to(x, axis, mult):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@partial(jax.jit, static_argnames=("causal", "window", "q_offset", "scale", "f32_products",
                                   "bq", "bk", "interpret"))
def flash_attention(
    q: jnp.ndarray,  # (B, T, Hq, D) — model layout
    k: jnp.ndarray,  # (B, S, Hkv, D)
    v: jnp.ndarray,  # (B, S, Hkv, Dv)
    *,
    causal: bool = True,
    window: int = 0,
    q_offset: int = 0,
    scale: float | None = None,
    f32_products: bool = True,
    bq: int = 128,
    bk: int = 128,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """(B, T, Hq, Dv); ``scale`` defaults to D^-0.5.  ``f32_products``
    upcasts the products' inputs to f32; off, the MXU takes the operands'
    dtype (the softmax weights rounded to V's), accumulating in f32."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, T, Hq, D = q.shape
    S = k.shape[1]
    bq_eff = min(bq, max(8, T))
    bk_eff = min(bk, max(8, S))
    qt = _pad_to(q.transpose(0, 2, 1, 3), 2, bq_eff)
    kt = _pad_to(k.transpose(0, 2, 1, 3), 2, bk_eff)
    vt = _pad_to(v.transpose(0, 2, 1, 3), 2, bk_eff)
    out = flash_attention_fwd(
        qt,
        kt,
        vt,
        seq_q=T,
        seq_k=S,
        causal=causal,
        window=window,
        q_offset=q_offset,
        scale=D ** -0.5 if scale is None else scale,
        f32_products=f32_products,
        bq=bq_eff,
        bk=bk_eff,
        interpret=interpret,
    )
    return out[:, :, :T].transpose(0, 2, 1, 3)
