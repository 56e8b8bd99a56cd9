"""Pallas TPU flash attention (GQA, causal, sliding-window).

TPU-native design (DESIGN.md §3): the grid's innermost dimension walks KV
blocks *sequentially* (TPU grids execute in order), carrying the online-
softmax state (m, l, acc) in VMEM scratch across iterations; q/k/v tiles
are streamed HBM→VMEM by BlockSpec index maps; tile shapes are multiples
of the 128-lane MXU width.  Grid: (B, Hq, T/bq, S/bk); GQA maps query head
h to KV head h // G in the k/v index maps.  Out-of-window blocks are
skipped with ``pl.when`` (block-level causal/window skipping — the FLOP
saving that makes causal flash ~2x over dense), and under a causal mask
the k/v index maps stop at the query block's last visible KV block, so
the skipped blocks are not fetched either.  Only blocks that cut the
diagonal, the window's edge or the padding build the element mask.  Products
accumulate in f32; their inputs are upcast to f32 unless
``f32_products=False``, which feeds the MXU the operands' dtype and rounds
the softmax weights to V's dtype for the value product.  V may be narrower
than Q and K (MLA's 192-wide keys, 128-wide values).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(
    q_ref,  # (1, 1, bq, D)
    k_ref,  # (1, 1, bk, D)
    v_ref,  # (1, 1, bk, Dv)
    o_ref,  # (1, 1, bq, Dv)
    acc_ref,  # VMEM (bq, Dv) f32
    m_ref,  # VMEM (bq, 128) f32 (lane-padded)
    l_ref,  # VMEM (bq, 128) f32
    *,
    bq: int,
    bk: int,
    seq_q: int,
    seq_k: int,
    causal: bool,
    window: int,
    q_offset: int,
    scale: float,
    f32_products: bool,
):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # block-level skip: entire block out of the causal / window range
    block_q_max = iq * bq + bq - 1 + q_offset
    block_q_min = iq * bq + q_offset
    block_k_min = ik * bk
    block_k_max = ik * bk + bk - 1
    relevant = jnp.asarray(True)
    # blocks that cut the causal diagonal, the window's edge or the
    # padding take the element mask; the others see every position
    edge = jnp.logical_or(block_k_max >= seq_k, block_q_max >= seq_q + q_offset)
    if causal:
        relevant &= block_k_min <= block_q_max
        edge |= block_k_max > block_q_min
    if window > 0:
        relevant &= block_k_max > block_q_min - window
        edge |= block_k_min <= block_q_max - window

    def compute(masked: bool):
        q = q_ref[0, 0]  # (bq, D)
        k = k_ref[0, 0]  # (bk, D)
        v = v_ref[0, 0]  # (bk, Dv)
        if f32_products:
            q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (bq, bk)
        if masked:
            qpos = block_q_min + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = block_k_min + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            mask = kpos < seq_k  # padding
            mask &= qpos < seq_q + q_offset
            if causal:
                mask &= kpos <= qpos
            if window > 0:
                mask &= kpos > qpos - window
            s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, 0]  # (bq,)
        l_prev = l_ref[:, 0]
        m_cur = jnp.max(s, axis=1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        if masked:  # a row with nothing visible yet has m_new = NEG_INF
            p = jnp.where(mask, p, 0.0)
        l_new = alpha * l_prev + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    pl.when(relevant & edge)(lambda: compute(True))
    pl.when(relevant & jnp.logical_not(edge))(lambda: compute(False))

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_ref[:, 0]
        denom = jnp.where(l > 0.0, l, 1.0)
        o_ref[0, 0, :, :] = (acc_ref[...] / denom[:, None]).astype(o_ref.dtype)


def flash_attention_fwd(
    q: jnp.ndarray,  # (B, Hq, Tp, D) — pre-padded to block multiples
    k: jnp.ndarray,  # (B, Hkv, Sp, D)
    v: jnp.ndarray,  # (B, Hkv, Sp, Dv)
    *,
    seq_q: int,
    seq_k: int,
    causal: bool,
    window: int,
    q_offset: int,
    scale: float,
    f32_products: bool = True,
    bq: int = 128,
    bk: int = 128,
    interpret: bool = True,
):
    B, Hq, Tp, D = q.shape
    Hkv, Sp = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    G = Hq // Hkv
    grid = (B, Hq, Tp // bq, Sp // bk)

    def kv_block(b, h, iq, ik):
        if causal:  # past the query block's last visible block: no new fetch
            ik = jnp.minimum(ik, (iq * bq + bq - 1 + q_offset) // bk)
        return b, h // G, ik, 0

    kernel = functools.partial(
        _flash_kernel,
        bq=bq,
        bk=bk,
        seq_q=seq_q,
        seq_k=seq_k,
        causal=causal,
        window=window,
        q_offset=q_offset,
        scale=scale,
        f32_products=f32_products,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, D), kv_block),
            pl.BlockSpec((1, 1, bk, Dv), kv_block),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, Dv), lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Tp, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, Dv), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
