"""Grouped-query attention with RoPE / M-RoPE, sliding windows, KV cache.

Reference (XLA) path; the Pallas flash kernel in
``repro.kernels.flash_attention`` is a drop-in for the train/prefill core
(``use_kernel=True`` on TPU), and the single-token decode path can route
through ``repro.kernels.decode_attention`` (``decode_attn="pallas"``) or
its jitted XLA reference (``decode_attn="xla"`` — the explicit fallback
``decode_kernel_plan`` reports).  Over a dense cache "pallas" is the
dense kernel, bit-equal to the reference; over a paged arena it is the
paged kernel, which reads each slot's live pages through the block table.
``decode_attn="off"`` keeps the historical ``_sdpa`` decode math
untouched.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.cache import KVCache, PagedKVCache, paged_append, paged_view
from repro.models.config import ModelConfig
from repro.models.layers import apply_mrope, apply_rope, dense, dense_init
from repro.sharding.rules import current_mesh_context, maybe_shard


def attn_init(key, cfg: ModelConfig, dtype=jnp.float32):
    kq, kk, kv, ko = jax.random.split(key, 4)
    d, h, hkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    return {
        "wq": dense_init(kq, d, h * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wk": dense_init(kk, d, hkv * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wv": dense_init(kv, d, hkv * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wo": dense_init(ko, h * hd, d, dtype=dtype),
    }


def _sdpa(q, k, v, mask, *, scale):
    """Softmax attention core; fp32 logits/softmax regardless of input dtype.

    q: (B, T, H, D); k/v: (B, S, Hkv, D) with H = G*Hkv (GQA).
    mask: (B, T, S) or (T, S) boolean — True = attend.
    """
    B, T, H, D = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    qg = q.reshape(B, T, Hkv, G, D)
    logits = jnp.einsum(
        "bthgd,bshd->bhgts", qg, k, preferred_element_type=jnp.float32
    )  # (B, Hkv, G, T, S)
    logits = logits * scale
    m = mask if mask.ndim == 3 else mask[None]
    logits = jnp.where(m[:, None, None, :, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum(
        "bhgts,bshd->bthgd", probs.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(B, T, H, D).astype(q.dtype)


def _sdpa_q_chunked(q, k, v, *, scale, q_chunk: int, window: int = 0):
    """Causal attention scanned over query chunks — the XLA-path analogue of
    flash attention's memory behavior: only (B, H, q_chunk, S) logits are
    live at once.  q: (B, T, H, D); T must be a multiple of q_chunk."""
    B, T, H, D = q.shape
    nch = T // q_chunk
    qs = q.reshape(B, nch, q_chunk, H, D).swapaxes(0, 1)  # (nch, B, qc, H, D)

    def chunk(i, q_blk):
        mask = causal_mask(q_chunk, T, offset=i * q_chunk, window=window)
        return _sdpa(q_blk, k, v, mask, scale=scale)

    outs = jax.lax.map(lambda iq: chunk(iq[0], iq[1]), (jnp.arange(nch), qs))
    return outs.swapaxes(0, 1).reshape(B, T, H, D)


def resolve_decode_attn(use_kernel, *, sliding_window: int = 0) -> str:
    """Map the public ``use_kernel`` knob (True / False / "auto") to the
    static decode-attention implementation tag: "pallas" (the Pallas
    kernel — forced, or auto on TPU) or "xla" (the jitted reference,
    bit-equal to the kernel).  Sliding-window attention has no kernel
    path and raises rather than silently changing semantics."""
    if sliding_window > 0:
        raise ValueError(
            "decode_attention has no sliding-window support — serve "
            "sliding-window models with decode_attn='off'"
        )
    if use_kernel == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    return "pallas" if use_kernel else "xla"


#: what each decode path runs, over a dense cache and over a paged arena
_DECODE_KERNEL = {
    ("pallas", False): "decode_attention",
    ("pallas", True): "paged_decode_attention",
    ("xla", False): "decode_attention_xla",
    ("xla", True): "paged_view + decode_attention_xla",
}
#: the same over MLA's paged latent arena
_MLA_DECODE_KERNEL = {"pallas": "paged_mla_decode",
                      "xla": "paged_mla_view + paged_mla_decode_xla"}


def decode_kernel_plan(cfg: ModelConfig, *, use_kernel="auto",
                       paged: bool = False) -> dict:
    """Which implementation the single-token decode path will take, and
    why — the ``kernel_plan``-style report serving surfaces so a run
    claiming kernel speed can't silently be on the fallback.  ``kernel``
    names what runs: over a paged arena (``paged=True``) the Pallas path
    is the block-table kernel, the XLA path a page gather first."""
    if cfg.sliding_window > 0:
        return {
            "path": "off",
            "reason": f"sliding_window={cfg.sliding_window} (no kernel path)",
        }
    backend = jax.default_backend()
    path = resolve_decode_attn(use_kernel)
    if path == "pallas":
        reason = (
            "forced by use_kernel=True" if use_kernel is True
            else f"backend={backend}"
        )
        if backend != "tpu":
            reason += " (interpret mode)"
    else:
        reason = f"backend={backend} — jitted XLA reference " + (
            "(equal to the paged kernel up to f32 summation order)" if paged
            else "(bit-equal to the kernel)"
        )
    kernel = (_MLA_DECODE_KERNEL[path] if paged and cfg.mixer == "mla"
              else _DECODE_KERNEL[path, paged])
    return {"path": path, "kernel": kernel,
            "reason": reason, "backend": backend}


def _decode_attend(q1, k_all, v_all, valid_len, *, impl: str):
    """One-token attention over a dense cache view via the decode kernel
    ("pallas") or its bit-equal jitted reference ("xla").
    q1: (B, Hq, D); k/v: (B, S, Hkv, D); valid_len: (B,) or scalar."""
    from repro.kernels.decode_attention import ops as da_ops

    if impl == "pallas":
        return da_ops.decode_attention(q1, k_all, v_all, valid_len)
    if impl == "xla":
        return da_ops.decode_attention_xla(q1, k_all, v_all, valid_len)
    raise ValueError(f"unknown decode_attn impl {impl!r}")


def causal_mask(T: int, S: int, *, offset: int = 0, window: int = 0) -> jnp.ndarray:
    """(T, S) mask; query i attends key j iff j <= i+offset (and within the
    sliding window when ``window > 0``)."""
    qpos = jnp.arange(T)[:, None] + offset
    kpos = jnp.arange(S)[None, :]
    m = kpos <= qpos
    if window > 0:
        m &= kpos > qpos - window
    return m


def attn_apply(
    p,
    cfg: ModelConfig,
    x: jnp.ndarray,
    *,
    positions: jnp.ndarray,
    cache: KVCache | PagedKVCache | None = None,
    mrope_positions: jnp.ndarray | None = None,
    use_kernel: bool = False,
    pages: tuple | None = None,
    decode_attn: str = "off",
):
    """GQA attention.  Train/prefill when ``cache is None``; otherwise decode:
    append x's (single or few) tokens at ``cache.index`` and attend over the
    full cache.

    A ``PagedKVCache`` cache decodes through the block table instead:
    ``pages=(block, length)`` (slot → page ids, per-slot fill counts) are
    jit arguments and the new token is scattered into the arena.  With
    ``decode_attn="pallas"`` the paged kernel then reads each slot's live
    pages through the block table; otherwise the XLA reference attends
    over the gathered per-slot view (``paged_view``).
    ``decode_attn`` ("off" | "xla" | "pallas") statically picks the
    single-token decode implementation; "off" keeps the ``_sdpa`` path
    (on a paged cache, the XLA reference).
    """
    B, T, d = x.shape
    H, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    cd = x.dtype

    q = dense(p["wq"], x).reshape(B, T, H, D)
    k = dense(p["wk"], x).reshape(B, T, Hkv, D)
    v = dense(p["wv"], x).reshape(B, T, Hkv, D)

    if cfg.mrope_sections and mrope_positions is not None:
        q = apply_mrope(q, mrope_positions, cfg.rope_theta, cfg.mrope_sections)
        k = apply_mrope(k, mrope_positions, cfg.rope_theta, cfg.mrope_sections)
    else:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

    scale = D ** -0.5

    if cache is None:
        if use_kernel and T >= 128:
            from repro.kernels.flash_attention import ops as fa_ops

            out = fa_ops.flash_attention(
                q, k, v, causal=True, window=cfg.sliding_window
            )
        else:
            qc = 0 if cfg.unroll_time_scans else cfg.attn_q_chunk
            if qc and T > qc and T % qc == 0:
                out = _sdpa_q_chunked(
                    q, k, v, scale=scale, q_chunk=qc, window=cfg.sliding_window
                )
            else:
                mask = causal_mask(T, T, window=cfg.sliding_window)
                out = _sdpa(q, k, v, mask, scale=scale)
        new_cache = None
    elif isinstance(cache, PagedKVCache):
        if T != 1:
            raise ValueError("paged decode appends exactly one token")
        if cfg.sliding_window > 0:
            raise ValueError("paged decode needs full causal attention")
        from repro.kernels.decode_attention import ops as da_ops

        block, length = pages
        new_cache = paged_append(cache, block, length, k[:, 0], v[:, 0])
        if decode_attn == "pallas":
            out = da_ops.paged_decode_attention(
                q[:, 0], new_cache.k, new_cache.v, block, length + 1
            )
        else:
            k_all, v_all = paged_view(new_cache, block)
            out = da_ops.decode_attention_xla(
                q[:, 0], k_all.astype(cd), v_all.astype(cd), length + 1
            )
        out = out[:, None]  # (B, 1, Hq, D)
    else:
        S = cache.k.shape[1]
        idx = cache.index
        k_all = jax.lax.dynamic_update_slice(
            cache.k, k.astype(cache.k.dtype), (0, idx, 0, 0)
        )
        v_all = jax.lax.dynamic_update_slice(
            cache.v, v.astype(cache.v.dtype), (0, idx, 0, 0)
        )
        ctx = current_mesh_context()
        if ctx is not None and "kvseq" in ctx.logical:
            # keep the cache sequence-sharded through the attention compute
            # (flash-decode locality: partial softmax per shard + tiny
            # combine instead of all-gathering K/V)
            k_all = maybe_shard(k_all, "batch", "kvseq", None, None)
            v_all = maybe_shard(v_all, "batch", "kvseq", None, None)
        if decode_attn != "off" and T == 1 and cfg.sliding_window == 0:
            valid = jnp.broadcast_to(idx + 1, (B,))
            out = _decode_attend(
                q[:, 0], k_all.astype(cd), v_all.astype(cd), valid,
                impl=decode_attn,
            )[:, None]
        else:
            # valid keys: j <= idx + i (supports T >= 1 appended tokens)
            qpos = idx + jnp.arange(T)[:, None]
            kpos = jnp.arange(S)[None, :]
            mask = kpos <= qpos
            if cfg.sliding_window > 0:
                mask &= kpos > qpos - cfg.sliding_window
            out = _sdpa(q, k_all.astype(cd), v_all.astype(cd), mask, scale=scale)
        new_cache = KVCache(k=k_all, v=v_all, index=idx + T)

    y = dense(p["wo"], out.reshape(B, T, H * D))
    return y, new_cache
