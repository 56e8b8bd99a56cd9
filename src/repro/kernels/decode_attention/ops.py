"""Jit'd wrappers for the decode-attention kernels: the dense one over a
per-slot cache, the paged one over a page arena and its block table, and
the paged MLA one over a latent arena."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention.kernel import NEG_INF, decode_attention_fwd
from repro.kernels.decode_attention.paged_kernel import paged_decode_attention_fwd


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
    Sp = S + pad
    with jax.named_scope("kv_gather"):
        # pad to whole KV blocks and lay K/V out head-major for the kernel
        if pad:
            widths = [(0, 0)] * 4
            widths[1] = (0, pad)
            k = jnp.pad(k, widths)
            v = jnp.pad(v, widths)
        k, v = jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2)

    vl = jnp.broadcast_to(jnp.asarray(valid_len).reshape(-1), (B,))
    bias = jnp.where(jnp.arange(Sp)[None, :] < vl[:, None], 0.0, NEG_INF).astype(
        jnp.float32
    )[:, None, :]

    qg = q.reshape(B, Hkv, G, D)
    out = decode_attention_fwd(
        qg, k, v, bias, bk=bk_eff, interpret=interpret,
    )
    return out.reshape(B, Hq, D)


@partial(jax.jit, static_argnames=("pages_per_chunk", "interpret"))
def paged_decode_attention(
    q: jnp.ndarray,  # (n_slots, Hq, D)
    k_pages: jnp.ndarray,  # (n_pages, Hkv, P, D) — the page arena
    v_pages: jnp.ndarray,
    block: jnp.ndarray,  # (n_slots, pages_per_slot) physical page ids
    valid_len: jnp.ndarray,  # (n_slots,) valid positions, each ≥ 1
    *,
    pages_per_chunk: int = 32,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """One-token attention for every slot, read through the block table:
    only each slot's ``ceil(valid_len / P)`` live pages are copied from
    the arena, ``pages_per_chunk`` pages a time.  Same result as
    ``decode_attention_xla`` over ``paged_view`` of the arena, up to the
    order of the f32 softmax sums."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n_slots, Hq, D = q.shape
    Hkv = k_pages.shape[1]
    qg = q.reshape(n_slots, Hkv, Hq // Hkv, D)
    out = paged_decode_attention_fwd(
        qg, k_pages, v_pages, block, valid_len,
        pages_per_chunk=pages_per_chunk, interpret=interpret,
    )
    return out.reshape(n_slots, Hq, D)


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


@partial(jax.jit, static_argnames=("value_width", "scale", "pages_per_chunk", "interpret"))
def paged_mla_decode(
    q: jnp.ndarray,  # (n_slots, H, W): [q_nope·W_uk ‖ q_pe] per head
    kv_pages: jnp.ndarray,  # (n_pages, P, W) — the latent arena
    block: jnp.ndarray,  # (n_slots, pages_per_slot) physical page ids
    valid_len: jnp.ndarray,  # (n_slots,) valid positions, each ≥ 1
    *,
    value_width: int,
    scale: float,
    pages_per_chunk: int = 8,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Absorbed MLA decode for every slot, read through the block table:
    scores against whole latent rows, values their first ``value_width``
    columns; (n_slots, H, value_width) f32.  Same result as
    ``paged_mla_decode_xla`` over ``paged_mla_view`` of the arena, up to
    the rounding of the weights to the arena's dtype and the order of
    the f32 sums."""
    from repro.kernels.decode_attention.paged_mla_kernel import paged_mla_decode_fwd

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return paged_mla_decode_fwd(
        q.astype(kv_pages.dtype), kv_pages, block, valid_len, value_width=value_width,
        scale=scale, pages_per_chunk=pages_per_chunk, interpret=interpret,
    )


@partial(jax.jit, static_argnames=("value_width", "scale"))
def paged_mla_decode_xla(
    q: jnp.ndarray,  # (B, H, W)
    kv: jnp.ndarray,  # (B, S, W) — a dense per-slot view of the arena
    valid_len: jnp.ndarray,  # (B,)
    *,
    value_width: int,
    scale: float,
) -> jnp.ndarray:
    """The kernel's XLA reference and the CPU path: the same absorbed
    attention in f32 over a dense view; (B, H, value_width) f32."""
    S = kv.shape[1]
    kv = kv.astype(jnp.float32)
    s = jnp.einsum("bhw,bsw->bhs", q.astype(jnp.float32), kv,
                   preferred_element_type=jnp.float32) * scale
    mask = (jnp.arange(S)[None, :] < valid_len.reshape(-1, 1))[:, None, :]
    s = jnp.where(mask, s, NEG_INF)
    p = jnp.where(mask, jnp.exp(s - jnp.max(s, axis=-1, keepdims=True)), 0.0)
    acc = jnp.einsum("bhs,bsr->bhr", p, kv[..., :value_width],
                     preferred_element_type=jnp.float32)
    return acc / jnp.sum(p, axis=-1, keepdims=True)
