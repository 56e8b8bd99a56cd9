"""Unified decoder-only LM covering dense / MoE / MLA / SSM / hybrid / VLM.

Layer stacks are compiled as ``jax.lax.scan`` over **segments**: the
per-layer spec list (mixer type × FFN type) is factored into either

* a repeating *period* (Jamba: 8-layer pattern × 9 super-blocks;
  xLSTM: 4-block pattern × 3), scanned over the repeats with the pattern
  unrolled inside the body, or
* maximal homogeneous *runs* (DeepSeek-V3: 3 dense layers + 58 MoE layers →
  two scans),

which keeps the HLO compact enough to compile 61-layer/671B-parameter
graphs for 512 host devices in minutes (see launch/dryrun.py).

Parameters are nested dicts; per-segment leaves carry a leading stack dim.
Decode carries per-segment stacked caches through the same scans.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from repro.models import attention, cache as cache_lib, mamba, mla, moe, xlstm
from repro.models.config import ModelConfig
from repro.models.layers import (
    cross_entropy,
    dense,
    dense_init,
    embed,
    embedding_init,
    rmsnorm,
    rmsnorm_init,
    swiglu,
    swiglu_init,
    unembed,
)
from repro.sharding.rules import maybe_shard


# ----------------------------------------------------------------------------
# Layer specs and segmentation
# ----------------------------------------------------------------------------

#: mixers whose caches accept T ≥ 1 appended tokens in ONE decode_step
#: call (keys causal-masked against idx + arange(T)); the recurrent
#: mixers (mamba / mlstm / slstm) carry single-step state and must be
#: fed token by token.  Serving uses this to pick batched vs loop prefill.
MULTI_TOKEN_MIXERS = ("attn", "mla")


@dataclass(frozen=True)
class LayerSpec:
    mixer: str  # attn | mla | mamba | mlstm | slstm
    ffn: str  # dense | moe | none


@dataclass(frozen=True)
class Segment:
    unit: tuple  # tuple[LayerSpec] — unrolled inside the scan body
    repeats: int  # scan length


def layer_specs(cfg: ModelConfig) -> list[LayerSpec]:
    specs = []
    for i in range(cfg.num_layers):
        # mixer
        if cfg.hybrid_pattern:
            mixer = cfg.hybrid_pattern[i % len(cfg.hybrid_pattern)]
        elif cfg.xlstm is not None:
            mixer = "slstm" if i in cfg.xlstm.slstm_at else "mlstm"
        else:
            mixer = cfg.mixer
        # ffn
        if cfg.xlstm is not None:
            ffn = "none"  # xLSTM blocks embed their own FFN
        elif cfg.moe is None:
            ffn = "dense"
        else:
            mode = cfg.moe.layer_mode
            if mode == "all":
                ffn = "moe"
            elif mode == "every_other":
                ffn = "moe" if i % 2 == 1 else "dense"
            elif mode == "after_first_k":
                ffn = "dense" if i < cfg.moe.first_k_dense else "moe"
            else:
                raise ValueError(mode)
        specs.append(LayerSpec(mixer=mixer, ffn=ffn))
    return specs


def segments(cfg: ModelConfig) -> list[Segment]:
    segs = _segments_base(cfg)
    if cfg.segment_repeats:
        if len(cfg.segment_repeats) != len(segs):
            raise ValueError(
                f"segment_repeats {cfg.segment_repeats} vs {len(segs)} segments"
            )
        segs = [
            Segment(unit=s.unit, repeats=r)
            for s, r in zip(segs, cfg.segment_repeats)
        ]
    return segs


def _segments_base(cfg: ModelConfig) -> list[Segment]:
    specs = layer_specs(cfg)
    L = len(specs)
    # smallest period p | L with specs[i] == specs[i % p]
    for p in range(1, L):
        if L % p == 0 and all(specs[i] == specs[i % p] for i in range(L)):
            return [Segment(unit=tuple(specs[:p]), repeats=L // p)]
    # fall back to maximal homogeneous runs
    segs = []
    i = 0
    while i < L:
        j = i
        while j < L and specs[j] == specs[i]:
            j += 1
        segs.append(Segment(unit=(specs[i],), repeats=j - i))
        i = j
    return segs


# ----------------------------------------------------------------------------
# Single layer
# ----------------------------------------------------------------------------

_MIXER_INIT = {
    "attn": attention.attn_init,
    "mla": mla.mla_init,
    "mamba": mamba.mamba_init,
    "mlstm": xlstm.mlstm_init,
    "slstm": xlstm.slstm_init,
}
_MIXER_APPLY = {
    "attn": attention.attn_apply,
    "mla": mla.mla_apply,
    "mamba": mamba.mamba_apply,
    "mlstm": xlstm.mlstm_apply,
    "slstm": xlstm.slstm_apply,
}


def init_layer(key, cfg: ModelConfig, spec: LayerSpec, dtype):
    k1, k2 = jax.random.split(key)
    p = {
        "mixer_norm": rmsnorm_init(cfg.d_model, dtype),
        "mixer": _MIXER_INIT[spec.mixer](k1, cfg, dtype=dtype),
    }
    if spec.ffn == "dense":
        p["ffn_norm"] = rmsnorm_init(cfg.d_model, dtype)
        p["ffn"] = swiglu_init(k2, cfg.d_model, cfg.d_ff, dtype=dtype)
    elif spec.ffn == "moe":
        p["ffn_norm"] = rmsnorm_init(cfg.d_model, dtype)
        p["ffn"] = moe.moe_init(k2, cfg, dtype=dtype)
    return p


def apply_layer(
    p,
    cfg: ModelConfig,
    spec: LayerSpec,
    h,
    *,
    cache=None,
    positions=None,
    mrope_positions=None,
    mla_absorb=False,
    pages=None,
    decode_attn="off",
):
    aux = jnp.zeros((), jnp.float32)
    hn = rmsnorm(p["mixer_norm"], h, eps=cfg.rms_eps)
    kw = {}
    if spec.mixer in ("attn", "mla"):
        kw["positions"] = positions
    if spec.mixer in ("attn", "mla"):
        kw["pages"] = pages
        kw["decode_attn"] = decode_attn
    if spec.mixer == "attn":
        kw["mrope_positions"] = mrope_positions
    if spec.mixer == "mla":
        kw["absorb"] = mla_absorb
    mix, new_cache = _MIXER_APPLY[spec.mixer](p["mixer"], cfg, hn, cache=cache, **kw)
    h = h + mix
    h = maybe_shard(h, "batch", "seq", None)
    routes = None
    if spec.ffn == "dense":
        h = h + swiglu(p["ffn"], rmsnorm(p["ffn_norm"], h, eps=cfg.rms_eps))
    elif spec.ffn == "moe" and cfg.holds_expert_share:
        y, routes = moe.moe_apply_held(p["ffn"], cfg, rmsnorm(p["ffn_norm"], h, eps=cfg.rms_eps))
        h = h + y
    elif spec.ffn == "moe":
        y, aux_moe = moe.moe_apply(p["ffn"], cfg, rmsnorm(p["ffn_norm"], h, eps=cfg.rms_eps))
        h = h + y
        aux = aux + aux_moe
    h = maybe_shard(h, "batch", "seq", None)
    return h, new_cache, aux, routes


def init_layer_cache(cfg: ModelConfig, spec: LayerSpec, batch: int, seq: int, dtype):
    if spec.mixer == "attn":
        return cache_lib.kv_cache_init(batch, seq, cfg.num_kv_heads, cfg.head_dim, dtype)
    if spec.mixer == "mla":
        return cache_lib.mla_cache_init(
            batch, seq, cfg.mla.kv_lora_rank, cfg.mla.qk_rope_head_dim, dtype
        )
    if spec.mixer == "mamba":
        d_inner, _, d_state, d_conv = mamba._dims(cfg)
        return cache_lib.mamba_cache_init(batch, d_conv, d_inner, d_state, dtype)
    if spec.mixer == "mlstm":
        di = int(cfg.xlstm.proj_factor_mlstm * cfg.d_model)
        di = (di // cfg.num_heads) * cfg.num_heads
        dh = di // cfg.num_heads
        return cache_lib.mlstm_cache_init(batch, cfg.num_heads, dh, dh)
    if spec.mixer == "slstm":
        return cache_lib.slstm_cache_init(batch, cfg.d_model)
    raise ValueError(spec.mixer)


# ----------------------------------------------------------------------------
# Full model
# ----------------------------------------------------------------------------

def _stack(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def init_params(key, cfg: ModelConfig):
    dtype = jnp.dtype(cfg.param_dtype)
    keys = jax.random.split(key, 4 + cfg.num_layers)
    params = {"embed": embedding_init(keys[0], cfg.padded_vocab, cfg.d_model, dtype)}

    segs = segments(cfg)
    ki = 1
    for si, seg in enumerate(segs):
        reps = []
        for r in range(seg.repeats):
            unit_p = {}
            for li, spec in enumerate(seg.unit):
                unit_p[f"l{li}"] = init_layer(
                    jax.random.fold_in(keys[1 + si], r * 131 + li), cfg, spec, dtype
                )
            reps.append(unit_p)
        params[f"seg{si}"] = _stack(reps)
        ki += 1

    params["final_norm"] = rmsnorm_init(cfg.d_model, dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(keys[2], cfg.d_model, cfg.padded_vocab, dtype=dtype)

    if cfg.num_mtp_layers > 0:
        spec = LayerSpec(mixer=cfg.mixer, ffn="dense" if cfg.moe is None else "moe")
        params["mtp"] = {
            "proj": dense_init(keys[3], 2 * cfg.d_model, cfg.d_model, dtype=dtype),
            "norm_h": rmsnorm_init(cfg.d_model, dtype),
            "norm_e": rmsnorm_init(cfg.d_model, dtype),
            "layer": init_layer(jax.random.fold_in(keys[3], 1), cfg, spec, dtype),
            "final_norm": rmsnorm_init(cfg.d_model, dtype),
        }
    return params


def init_cache(cfg: ModelConfig, batch: int, seq: int, dtype, *, index: int = 0):
    """Stacked per-segment caches (index pre-set for decode-at-position)."""
    caches = {}
    for si, seg in enumerate(segs_of(cfg)):
        reps = []
        for _ in range(seg.repeats):
            unit_c = {
                f"l{li}": init_layer_cache(cfg, spec, batch, seq, dtype)
                for li, spec in enumerate(seg.unit)
            }
            reps.append(unit_c)
        stacked = _stack(reps)
        if index:
            # the only int32 leaves in caches are the fill indices
            stacked = jax.tree.map(
                lambda l: jnp.full_like(l, index) if l.dtype == jnp.int32 else l,
                stacked,
            )
        caches[f"seg{si}"] = stacked
    return caches


def segs_of(cfg: ModelConfig) -> list[Segment]:
    return segments(cfg)


def _remat_wrap(fn, cfg: ModelConfig):
    if cfg.remat_policy == "none":
        return fn
    if cfg.remat_policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    if cfg.remat_policy == "full":
        return jax.checkpoint(fn)
    raise ValueError(cfg.remat_policy)


def forward(
    params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,
    *,
    positions: jnp.ndarray | None = None,
    mrope_positions: jnp.ndarray | None = None,
    vision_embeds: jnp.ndarray | None = None,
    cache=None,
    mla_absorb: bool = False,
    return_hidden: bool = False,
    skip_logits: bool = False,
    pages: tuple | None = None,
    decode_attn: str = "off",
    return_routes: bool = False,
):
    """Returns (logits, aux_loss, new_cache[, hidden][, routes]).

    ``routes`` ((B, T) int32, with ``return_routes`` on a stack whose
    expert layers hold a share of the experts): per token, the routed
    (token, expert) pairs that landed on held experts, summed over the
    expert layers."""
    cd = jnp.dtype(cfg.compute_dtype)
    B, T = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(T), (B, T))

    h = embed(params["embed"], tokens, compute_dtype=cd)
    if vision_embeds is not None:
        # VLM stub frontend: the first Tv positions are precomputed patch
        # embeddings (projector output) — replace the placeholder tokens.
        Tv = vision_embeds.shape[1]
        h = jnp.concatenate([vision_embeds.astype(cd), h[:, Tv:]], axis=1)
    h = maybe_shard(h, "batch", "seq", None)

    aux = jnp.zeros((), jnp.float32)
    new_caches = {} if cache is not None else None
    count = cfg.holds_expert_share
    routes = jnp.zeros((B, T), jnp.int32)

    for si, seg in enumerate(segments(cfg)):
        seg_params = params[f"seg{si}"]

        def body(carry, xs, seg=seg):
            h, aux, *held = carry
            if cache is not None:
                p_step, c_step = xs
            else:
                p_step, c_step = xs, None
            new_c = {}
            for li, spec in enumerate(seg.unit):
                c_in = c_step[f"l{li}"] if c_step is not None else None
                h, c_out, a, r = apply_layer(
                    p_step[f"l{li}"],
                    cfg,
                    spec,
                    h,
                    cache=c_in,
                    positions=positions,
                    mrope_positions=mrope_positions,
                    mla_absorb=mla_absorb,
                    pages=pages,
                    decode_attn=decode_attn,
                )
                aux = aux + a
                if r is not None:
                    held = [held[0] + r]
                if c_out is not None:
                    new_c[f"l{li}"] = c_out
            return (h, aux, *held), (new_c if cache is not None else None)

        body = _remat_wrap(body, cfg) if cache is None else body

        carry = (h, aux, routes) if count else (h, aux)
        if not cfg.scan_layers:
            # probe path: unroll so XLA cost_analysis counts every repeat
            new_slices = []
            for r in range(seg.repeats):
                p_r = jax.tree.map(lambda x: x[r], seg_params)
                if cache is not None:
                    c_r = jax.tree.map(lambda x: x[r], cache[f"seg{si}"])
                    carry, c_out = body(carry, (p_r, c_r))
                    new_slices.append(c_out)
                else:
                    carry, _ = body(carry, p_r)
            if cache is not None:
                new_caches[f"seg{si}"] = jax.tree.map(
                    lambda *xs: jnp.stack(xs), *new_slices
                )
        elif cache is not None:
            carry, seg_new_cache = jax.lax.scan(
                body, carry, (seg_params, cache[f"seg{si}"])
            )
            new_caches[f"seg{si}"] = seg_new_cache
        else:
            carry, _ = jax.lax.scan(body, carry, seg_params)
        h, aux, *held = carry
        routes = held[0] if count else routes

    h = rmsnorm(params["final_norm"], h, eps=cfg.rms_eps)
    if skip_logits:
        logits = None
    else:
        logits = _head_logits(params, cfg, h)
        logits = maybe_shard(logits, "batch", "seq", "model")

    out = (logits, aux, new_caches)
    if return_hidden:
        out = out + (h,)
    if return_routes:
        out = out + (routes,)
    return out


# ----------------------------------------------------------------------------
# Multi-token prediction (DeepSeek-V3 MTP, depth 1)
# ----------------------------------------------------------------------------

def mtp_hidden(params, cfg: ModelConfig, hidden, tokens, positions):
    """Depth-1 MTP trunk: h'_t = Layer(W [norm(h_t); norm(E(tok_{t+1}))]);
    the caller applies the shared head (chunked) to predict token t+2."""
    p = params["mtp"]
    cd = jnp.dtype(cfg.compute_dtype)
    e_next = embed(params["embed"], tokens, compute_dtype=cd)  # caller pre-shifts
    x = jnp.concatenate(
        [
            rmsnorm(p["norm_h"], hidden, eps=cfg.rms_eps),
            rmsnorm(p["norm_e"], e_next, eps=cfg.rms_eps),
        ],
        axis=-1,
    )
    x = dense(p["proj"], x)
    spec = LayerSpec(mixer=cfg.mixer, ffn="dense" if cfg.moe is None else "moe")
    x, _, aux, _ = apply_layer(p["layer"], cfg, spec, x, positions=positions)
    x = rmsnorm(p["final_norm"], x, eps=cfg.rms_eps)
    return x, aux


# ----------------------------------------------------------------------------
# Losses / steps
# ----------------------------------------------------------------------------

def _head_logits(params, cfg: ModelConfig, h):
    if cfg.tie_embeddings:
        logits = unembed(params["embed"], h)
    else:
        logits = dense(params["lm_head"], h).astype(jnp.float32)
    if cfg.padded_vocab != cfg.vocab_size:  # mask pad columns (never predicted)
        pad_mask = jnp.arange(cfg.padded_vocab) < cfg.vocab_size
        logits = jnp.where(pad_mask, logits, -1e30)
    return logits


def chunked_ce(params, cfg: ModelConfig, hidden, labels, *, mask=None, chunk=512):
    """Cross entropy computed from (pre-norm-applied) hidden states in
    sequence chunks, so only (B, chunk, V) logits are ever live — the full
    (B, T, V) fp32 logits tensor (the dominant fixed memory cost at large
    vocab) is never materialized.  jax.checkpoint recomputes per-chunk
    logits in the backward pass."""
    B, T, _ = hidden.shape
    if mask is None:
        mask = jnp.ones((B, T), jnp.float32)
    c = min(chunk, T)
    if T % c:
        c = T  # fall back to single chunk for odd lengths (smoke tests)
    nch = T // c

    @jax.checkpoint
    def piece(h_c, l_c, m_c):
        logits = _head_logits(params, cfg, h_c).astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, l_c[..., None], axis=-1)[..., 0]
        return jnp.sum((lse - gold) * m_c), jnp.sum(m_c)

    def body(acc, xs):
        h_c, l_c, m_c = xs
        s, n = piece(h_c, l_c, m_c)
        return (acc[0] + s, acc[1] + n), None

    hs = hidden.reshape(B, nch, c, -1).swapaxes(0, 1)
    ls = labels.reshape(B, nch, c).swapaxes(0, 1)
    ms = mask.reshape(B, nch, c).swapaxes(0, 1)
    (tot, cnt), _ = jax.lax.scan(body, (jnp.zeros(()), jnp.zeros(())), (hs, ls, ms))
    return tot / jnp.maximum(cnt, 1.0)


def loss_fn(params, cfg: ModelConfig, batch):
    """batch: tokens (B,T), labels (B,T); optional mrope_positions,
    vision_embeds, loss_mask."""
    _, aux, _, hidden = forward(
        params,
        cfg,
        batch["tokens"],
        mrope_positions=batch.get("mrope_positions"),
        vision_embeds=batch.get("vision_embeds"),
        return_hidden=True,
        skip_logits=True,
    )
    loss = chunked_ce(
        params, cfg, hidden, batch["labels"], mask=batch.get("loss_mask")
    )
    total = loss + aux
    metrics = {"ce": loss, "aux": aux}

    if cfg.num_mtp_layers > 0:
        B, T = batch["tokens"].shape
        positions = jnp.broadcast_to(jnp.arange(T), (B, T))
        # tokens shifted by one feed the MTP stream; labels shifted by two
        tok_next = jnp.roll(batch["tokens"], -1, axis=1)
        lbl_next = jnp.roll(batch["labels"], -1, axis=1)
        h_mtp, aux_mtp = mtp_hidden(params, cfg, hidden, tok_next, positions)
        mask = jnp.ones((B, T), jnp.float32).at[:, -2:].set(0.0)
        mtp_loss = chunked_ce(params, cfg, h_mtp, lbl_next, mask=mask)
        total = total + cfg.mtp_loss_coef * mtp_loss + aux_mtp
        metrics["mtp"] = mtp_loss

    return total, metrics


def decode_step(params, cfg: ModelConfig, tokens, cache, *, positions=None,
                mla_absorb: bool = False, decode_attn: str = "off"):
    """One serve step: tokens (B, 1) + cache → (logits (B,1,V), new_cache)."""
    if positions is None:
        # position = current cache fill index (same for all layers); pure
        # SSM/xLSTM caches carry no index (state is position-free)
        idx_leaves = [l for l in jax.tree.leaves(cache) if l.dtype == jnp.int32]
        if idx_leaves:
            positions = jnp.broadcast_to(idx_leaves[0].reshape(-1)[0], tokens.shape)
        else:
            positions = jnp.zeros(tokens.shape, jnp.int32)
    logits, aux, new_cache = forward(
        params, cfg, tokens, positions=positions, cache=cache,
        mla_absorb=mla_absorb, decode_attn=decode_attn,
    )
    return logits, new_cache


# ----------------------------------------------------------------------------
# Paged decode plane (continuous-batching serving)
# ----------------------------------------------------------------------------

def _is_paged(x) -> bool:
    return isinstance(x, (cache_lib.PagedKVCache, cache_lib.PagedMLACache))


def init_paged_cache(cfg: ModelConfig, n_pages: int, page_size: int, dtype):
    """Stacked per-segment page arenas (same tree shape as ``init_cache``
    so ``forward`` scans them identically): a ``PagedKVCache`` for each
    attention layer, a ``PagedMLACache`` (one latent row per position)
    for each MLA layer.  Recurrent mixers have no paged decode path."""
    for spec in layer_specs(cfg):
        if spec.mixer not in ("attn", "mla"):
            raise ValueError(
                f"paged decode supports attention and MLA stacks, got mixer "
                f"{spec.mixer!r}"
            )

    def arena(spec):
        if spec.mixer == "mla":
            m = cfg.mla
            return cache_lib.paged_mla_cache_init(
                n_pages, page_size, cache_lib.latent_width(m.kv_lora_rank, m.qk_rope_head_dim),
                dtype)
        return cache_lib.paged_kv_cache_init(
            n_pages, page_size, cfg.num_kv_heads, cfg.head_dim, dtype)

    caches = {}
    for si, seg in enumerate(segs_of(cfg)):
        reps = []
        for _ in range(seg.repeats):
            reps.append({f"l{li}": arena(spec) for li, spec in enumerate(seg.unit)})
        caches[f"seg{si}"] = _stack(reps)
    return caches


def paged_decode_step(params, cfg: ModelConfig, tokens, cache, block, length,
                      *, decode_attn: str = "xla"):
    """One continuous-batching step: advance every slot one token.

    tokens: (n_slots, 1); block: (n_slots, pages_per_slot) physical page
    ids; length: (n_slots,) tokens already cached per slot.  Returns
    (logits (n_slots, 1, V), new_cache[, routes (n_slots,)]): on a stack
    whose expert layers hold a share of the experts
    (``cfg.holds_expert_share``), each slot's routed pairs that landed on
    held experts (``forward``).  Inactive slots (block row all NULL_PAGE,
    length 0) compute garbage harmlessly — rows are independent and
    their writes land in the null page.
    """
    positions = jnp.broadcast_to(length[:, None], tokens.shape)
    out = forward(
        params, cfg, tokens, positions=positions, cache=cache,
        pages=(block, length), decode_attn=decode_attn,
        return_routes=cfg.holds_expert_share,
    )
    if cfg.holds_expert_share:
        return out[0], out[2], out[3][:, 0]
    return out[0], out[2]


def paged_insert_prompt(paged, dense, block_row, n_valid):
    """Scatter a B=1 prefilled dense cache into one slot's pages (join).

    ``paged``: tree from ``init_paged_cache``; ``dense``: tree from
    ``init_cache(batch=1)`` after prefill, same segment structure.
    ``block_row``: (pages_per_slot,) page ids for the joining slot;
    ``n_valid``: prompt length (rows ≥ n_valid go to the null page, so
    bucket padding in the prefilled cache never becomes visible).
    """

    def insert_one(pg, dn):
        # the segment's stacked arenas in one scatter: dn.k is
        # (repeats, 1, S, H_kv, D), pg.k (repeats, n_pages, H_kv, P, D);
        # an MLA layer's latent rows are [c_kv ‖ k_rope], (repeats, S, W)
        if isinstance(pg, cache_lib.PagedMLACache):
            rows = jnp.concatenate([dn.c_kv[:, 0], dn.k_rope[:, 0]], axis=-1)
            return cache_lib.paged_mla_write(pg, block_row, rows, n_valid)
        return cache_lib.paged_write(
            pg, block_row, dn.k[:, 0], dn.v[:, 0], n_valid
        )

    return jax.tree.map(insert_one, paged, dense, is_leaf=_is_paged)
