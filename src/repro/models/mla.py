"""Multi-head Latent Attention (MLA) — DeepSeek-V2/V3, MiniCPM3.

The KV cache stores only the normed compressed latent ``c_kv``
(kv_lora_rank) plus one shared roped key ``k_pe`` per position — the MLA
memory saving.  Three paths:

* no cache, or a dense cache (training, prefill, the dense decode): the
  paper's form — up-project the latent to per-head K/V and attend, in
  query chunks so that only ``(B, H, q_chunk, S)`` logits are live;
* ``absorb=True`` over a dense cache: absorb ``W_uk`` into the query and
  ``W_uv`` into the output so attention runs in the latent space (the
  published inference optimization);
* a ``PagedMLACache`` arena (continuous-batching serving): the absorbed
  form, one token per slot, over each slot's latent pages through the
  block table — the paged MLA Pallas kernel with ``decode_attn="pallas"``,
  else its XLA reference over the gathered view.

With ``cfg.yarn_factor`` set the rope dimensions use YaRN frequencies and
the softmax scale carries YaRN's mscale² (DeepSeek-V3).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.cache import (
    MLACache,
    PagedMLACache,
    pad_lanes,
    paged_mla_append,
    paged_mla_view,
)
from repro.models.config import ModelConfig
from repro.models.layers import (
    apply_rope,
    dense,
    dense_init,
    rmsnorm,
    rmsnorm_init,
    yarn_frequencies,
    yarn_mscale,
)

#: logits live at once in the chunked prefill when ``attn_q_chunk`` is unset
_LOGITS_PER_CHUNK = 1 << 26
#: query and key block of the flash-attention prefill on TPU
_FLASH_BLOCK = 512


def mla_init(key, cfg: ModelConfig, dtype=jnp.float32):
    m = cfg.mla
    H = cfg.num_heads
    ks = jax.random.split(key, 7)
    return {
        "w_dq": dense_init(ks[0], cfg.d_model, m.q_lora_rank, dtype=dtype),
        "q_norm": rmsnorm_init(m.q_lora_rank, dtype),
        "w_uq": dense_init(
            ks[1], m.q_lora_rank, H * (m.qk_nope_head_dim + m.qk_rope_head_dim), dtype=dtype
        ),
        "w_dkv": dense_init(ks[2], cfg.d_model, m.kv_lora_rank, dtype=dtype),
        "kv_norm": rmsnorm_init(m.kv_lora_rank, dtype),
        "w_kr": dense_init(ks[3], cfg.d_model, m.qk_rope_head_dim, dtype=dtype),
        "w_uk": dense_init(ks[4], m.kv_lora_rank, H * m.qk_nope_head_dim, dtype=dtype),
        "w_uv": dense_init(ks[5], m.kv_lora_rank, H * m.v_head_dim, dtype=dtype),
        "w_o": dense_init(ks[6], H * m.v_head_dim, cfg.d_model, dtype=dtype),
    }


def rope_inv_freq(cfg: ModelConfig):
    """The rope dimensions' inverse frequencies: YaRN's with
    ``cfg.yarn_factor``, else None (plain RoPE)."""
    if not cfg.yarn_factor:
        return None
    return yarn_frequencies(cfg.mla.qk_rope_head_dim, cfg.rope_theta, cfg.yarn_factor,
                            cfg.yarn_beta_fast, cfg.yarn_beta_slow, cfg.yarn_original_max_pos)


def softmax_scale(cfg: ModelConfig) -> float:
    """(nope + rope)^-0.5, times YaRN's mscale² with ``cfg.yarn_factor``."""
    m = cfg.mla
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    if cfg.yarn_factor:
        scale *= yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim) ** 2
    return scale


def _queries(p, cfg, x, positions):
    m, H = cfg.mla, cfg.num_heads
    B, T, _ = x.shape
    cq = rmsnorm(p["q_norm"], dense(p["w_dq"], x), eps=cfg.rms_eps)
    q = dense(p["w_uq"], cq).reshape(B, T, H, m.qk_nope_head_dim + m.qk_rope_head_dim)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta, rope_inv_freq(cfg))
    return q_nope, q_rope


def _latent(p, cfg, x, positions):
    """What a position caches: the normed latent (B, T, r) and the roped
    shared key (B, T, dr)."""
    c_kv = rmsnorm(p["kv_norm"], dense(p["w_dkv"], x), eps=cfg.rms_eps)
    k_pe = apply_rope(dense(p["w_kr"], x)[:, :, None, :], positions, cfg.rope_theta,
                      rope_inv_freq(cfg))[:, :, 0, :]
    return c_kv, k_pe


def _q_chunk(cfg: ModelConfig, T: int, S: int) -> int:
    """Queries attended at once: ``attn_q_chunk`` if set, else the largest
    power of two that keeps the chunk's logits under ``_LOGITS_PER_CHUNK``;
    the whole of ``T`` where the chunk does not divide it."""
    fit = max(1, _LOGITS_PER_CHUNK // (cfg.num_heads * S))
    c = cfg.attn_q_chunk or 1 << (fit.bit_length() - 1)
    return c if c < T and T % c == 0 else T


def _attend(p, cfg, q_nope, q_rope, c_kv, k_pe, qpos, *, use_kernel=False):
    """The paper's form over a dense latent: per-head K/V up-projected from
    ``c_kv`` (B, S, r); query t sees keys at positions ≤ ``qpos[t]``.
    Scanned over query chunks, or, with ``use_kernel`` (a prompt that
    fills the whole cache, ``qpos`` = 0..T-1), the flash-attention kernel
    over [q_nope ‖ q_rope] · [k_nope ‖ k_pe], so that no (H, T, S) logits
    reach HBM.  Returns (B, T, H, dv)."""
    m, H = cfg.mla, cfg.num_heads
    B, T = q_nope.shape[:2]
    S = c_kv.shape[1]
    dt = q_nope.dtype
    k_nope = dense(p["w_uk"], c_kv.astype(dt)).reshape(B, S, H, m.qk_nope_head_dim)
    v = dense(p["w_uv"], c_kv.astype(dt)).reshape(B, S, H, m.v_head_dim)
    k_pe = k_pe.astype(dt)
    scale = softmax_scale(cfg)
    if use_kernel:
        from repro.kernels.flash_attention import ops as fa_ops

        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_pe[:, :, None, :], (B, S, H, m.qk_rope_head_dim))],
            axis=-1)
        return fa_ops.flash_attention(q, k, v, causal=True, scale=scale, f32_products=False,
                                      bq=_FLASH_BLOCK, bk=_FLASH_BLOCK)
    kpos = jnp.arange(S)

    def chunk(xs):
        qn, qr, qp = xs  # (B, c, H, dn), (B, c, H, dr), (c,)
        logits = (
            jnp.einsum("bthd,bshd->bhts", qn, k_nope, preferred_element_type=jnp.float32)
            + jnp.einsum("bthd,bsd->bhts", qr, k_pe, preferred_element_type=jnp.float32)
        ) * scale
        logits = jnp.where((kpos[None, :] <= qp[:, None])[None, None], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)
        return jnp.einsum("bhts,bshd->bthd", probs.astype(v.dtype), v,
                          preferred_element_type=jnp.float32)

    c = _q_chunk(cfg, T, S)
    if c == T:
        return chunk((q_nope, q_rope, qpos))
    n = T // c
    split = lambda a: a.reshape(B, n, c, *a.shape[2:]).swapaxes(0, 1)
    out = jax.lax.map(chunk, (split(q_nope), split(q_rope), qpos.reshape(n, c)))
    return out.swapaxes(0, 1).reshape(B, T, H, m.v_head_dim)


def _absorbed_query(p, cfg, q_nope, q_rope):
    """[q_nope · W_uk ‖ q_rope] in f32: each head's query in the latent
    space, (..., H, r + dr)."""
    m, H = cfg.mla, cfg.num_heads
    with jax.named_scope("mla_absorb"):
        w_uk = p["w_uk"]["kernel"].reshape(m.kv_lora_rank, H, m.qk_nope_head_dim)
        q_lat = jnp.einsum("...hd,rhd->...hr", q_nope.astype(jnp.float32),
                           w_uk.astype(jnp.float32))
        return jnp.concatenate([q_lat, q_rope.astype(jnp.float32)], axis=-1)


def _absorbed_out(p, cfg, ctx_lat):
    """Latent context (..., H, r) through W_uv: (..., H, dv) f32."""
    m, H = cfg.mla, cfg.num_heads
    with jax.named_scope("mla_absorb"):
        w_uv = p["w_uv"]["kernel"].reshape(m.kv_lora_rank, H, m.v_head_dim)
        return jnp.einsum("...hr,rhd->...hd", ctx_lat, w_uv.astype(jnp.float32))


def _paged_decode(p, cfg, q_nope, q_rope, row, cache: PagedMLACache, pages, decode_attn):
    """One token per slot: append its latent row, then the absorbed
    attention over the slot's pages (the query padded with zeros to the
    arena's lanes).  Returns ((B, 1, H, dv), new arena)."""
    from repro.kernels.decode_attention import ops as da_ops

    block, length = pages
    r = cfg.mla.kv_lora_rank
    cache = paged_mla_append(cache, block, length, row[:, 0])
    q = pad_lanes(_absorbed_query(p, cfg, q_nope[:, 0], q_rope[:, 0]), cache.kv.shape[-1])
    valid = length + 1
    if decode_attn == "pallas":
        ctx = da_ops.paged_mla_decode(q, cache.kv, block, valid, value_width=r,
                                      scale=softmax_scale(cfg))
    else:
        ctx = da_ops.paged_mla_decode_xla(q, paged_mla_view(cache, block), valid,
                                          value_width=r, scale=softmax_scale(cfg))
    return _absorbed_out(p, cfg, ctx)[:, None], cache


def mla_apply(
    p,
    cfg: ModelConfig,
    x: jnp.ndarray,
    *,
    positions: jnp.ndarray,
    cache: MLACache | PagedMLACache | None = None,
    absorb: bool = False,
    pages: tuple | None = None,
    decode_attn: str = "off",
    **_,
):
    m, H = cfg.mla, cfg.num_heads
    B, T, _ = x.shape

    q_nope, q_rope = _queries(p, cfg, x, positions)
    c_kv_new, k_pe_new = _latent(p, cfg, x, positions)

    if isinstance(cache, PagedMLACache):
        row = jnp.concatenate([c_kv_new, k_pe_new], axis=-1)
        out, new_cache = _paged_decode(p, cfg, q_nope, q_rope, row, cache, pages, decode_attn)
    else:
        if cache is None:
            c_kv, k_pe, qpos = c_kv_new, k_pe_new, jnp.arange(T)
            new_cache = None
        else:
            idx = cache.index
            c_kv = jax.lax.dynamic_update_slice(
                cache.c_kv, c_kv_new.astype(cache.c_kv.dtype), (0, idx, 0))
            k_pe = jax.lax.dynamic_update_slice(
                cache.k_rope, k_pe_new.astype(cache.k_rope.dtype), (0, idx, 0))
            qpos = idx + jnp.arange(T)
            new_cache = MLACache(c_kv=c_kv, k_rope=k_pe, index=idx + T)
        if absorb:
            S = c_kv.shape[1]
            kv = jnp.concatenate([c_kv, k_pe], axis=-1).astype(jnp.float32)  # (B, S, r + dr)
            q = _absorbed_query(p, cfg, q_nope, q_rope)  # (B, T, H, r + dr)
            logits = jnp.einsum("bthw,bsw->bhts", q, kv) * softmax_scale(cfg)
            mask = jnp.arange(S)[None, :] <= qpos[:, None]
            probs = jax.nn.softmax(jnp.where(mask[None, None], logits, -1e30), axis=-1)
            ctx = jnp.einsum("bhts,bsr->bthr", probs, kv[..., : m.kv_lora_rank])
            out = _absorbed_out(p, cfg, ctx)
        else:
            # a prompt that fills the cache (the serving prefill) takes
            # the flash kernel on TPU
            out = _attend(p, cfg, q_nope, q_rope, c_kv, k_pe, qpos,
                          use_kernel=cache is not None and T == c_kv.shape[1]
                          and jax.default_backend() == "tpu")

    y = dense(p["w_o"], out.astype(x.dtype).reshape(B, T, H * m.v_head_dim))
    return y, new_cache
