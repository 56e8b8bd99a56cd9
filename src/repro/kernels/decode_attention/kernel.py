"""Pallas TPU decode attention: one query token vs a long KV cache.

Flash-decode style: the grid streams the KV cache in ``bk``-row blocks
(innermost, sequential), merging partial softmax statistics (m, l, acc) in
VMEM scratch; the G=Hq/Hkv query heads sharing a KV head are processed
together as the (G, D) left operand of the MXU matmuls.  The valid cache
length arrives as an additive (B, 1, S) bias row (0 / -inf) so the block mask
needs no scalar prefetch — portable to interpret mode.

K/V are head-major ``(B, Hkv, S, D)`` and the bias is ``(B, 1, S)``, so
every block's last two dimensions are ``(bk, D)`` or ``(1, bk)``: the
TPU compiler takes a block whose second-to-last dimension is a multiple
of 8 (or the whole dimension) and whose last is a multiple of 128 (or
the whole dimension).

This is the hot op of the ``decode_32k``/``long_500k`` shapes: per token it
moves the whole cache once (memory-bound; arithmetic intensity ≈ 2·G
flops/byte), so the roofline memory term of EXPERIMENTS.md is set directly
by this kernel's bytes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(
    q_ref,  # (1, 1, G, D)
    k_ref,  # (1, 1, bk, D)
    v_ref,  # (1, 1, bk, D)
    bias_ref,  # (1, 1, bk)
    o_ref,  # (1, 1, G, D)
    acc_ref,  # VMEM (G, D) f32
    m_ref,  # VMEM (G, 128) f32
    l_ref,  # VMEM (G, 128) f32
):
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)  # (G, D)
    k = k_ref[0, 0].astype(jnp.float32)  # (bk, D)
    v = v_ref[0, 0].astype(jnp.float32)
    bias = bias_ref[0].astype(jnp.float32)  # (1, bk)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * (q.shape[-1] ** -0.5)  # (G, bk)
    s = s + bias

    m_prev = m_ref[:, 0:1]  # (G, 1)
    l_prev = l_ref[:, 0:1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    p = jnp.where(bias > NEG_INF / 2, p, 0.0)
    l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_ref[:, 0:1]
        denom = jnp.where(l > 0.0, l, 1.0)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def decode_attention_fwd(
    q: jnp.ndarray,  # (B, Hkv, G, D)
    k: jnp.ndarray,  # (B, Hkv, Sp, D) — head-major
    v: jnp.ndarray,
    bias: jnp.ndarray,  # (B, 1, Sp) 0 / -inf additive mask
    *,
    bk: int = 512,
    interpret: bool = True,
):
    B, Hkv, G, D = q.shape
    Sp = k.shape[2]
    grid = (B, Hkv, Sp // bk)
    return pl.pallas_call(
        _decode_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, h, ik: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, ik: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, ik: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, bk), lambda b, h, ik: (b, 0, ik)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, h, ik: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, D), jnp.float32),
            pltpu.VMEM((G, 128), jnp.float32),
            pltpu.VMEM((G, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v, bias)
