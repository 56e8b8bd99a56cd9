"""Pallas TPU decode attention for MLA over a paged latent arena.

The absorbed form of multi-head latent attention (DeepSeek-V2/V3) is
multi-query attention in the latent space: every query head attends to
one shared key row per position, ``[c_kv ‖ k_pe]`` (kv_lora_rank +
rope_dim wide, 576 for DeepSeek-V3), and its values are the first
``kv_lora_rank`` columns of that same row.  The arena
``(n_pages, P, W)`` holds one such row per position, so one copy of a
page feeds both the scores and the values.

Grid: one step per slot.  The slot's query is ``(H, W)``, so the MXU sees
all H heads as the rows of one matmul.  The slot's live pages,
``ceil(valid / P)`` of them, are copied through the block table
(scalar prefetch) by the paged GQA kernel's ``SlotPages``, in chunks of
``pages_per_chunk`` pages, double-buffered; pages past the slot's length
are not copied.  The softmax is the online merge (m, l, acc in f32);
the scores and the value product take the arena's dtype in and
accumulate in f32, and the weights are rounded to the arena's dtype for
the value product, as flash decoders do.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decode_attention.kernel import NEG_INF
from repro.kernels.decode_attention.paged_kernel import SlotPages


def _paged_mla_kernel(
    block_ref,  # SMEM (n_slots · pages_per_slot,) physical page ids
    valid_ref,  # SMEM (n_slots,) valid positions per slot
    q_ref,  # (1, H, W)
    kv_arena,  # (n_pages, P, W), left where XLA placed it
    o_ref,  # (1, H, R)
    kv_buf,  # VMEM (2, C·P, W)
    sems,  # DMA semaphores (1, 2)
    m_ref,  # VMEM (H, 128) f32
    l_ref,  # VMEM (H, 128) f32
    acc_ref,  # VMEM (H, R) f32
    *,
    pages_per_slot: int,
    pages_per_chunk: int,
    scale: float,
):
    slot = pl.program_id(0)
    P = kv_arena.shape[1]
    R = acc_ref.shape[-1]
    CP = pages_per_chunk * P
    valid = valid_ref[slot]
    pages = SlotPages(block_ref, slot, valid, [(kv_arena, kv_buf, True)], sems,
                      pages_per_slot=pages_per_slot, pages_per_chunk=pages_per_chunk,
                      page_size=P)

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    q = q_ref[0]  # (H, W)

    def compute(chunk, b):
        s = jax.lax.dot_general(
            q, kv_buf[b], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (H, C·P)
        pos = chunk * CP + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = pos < valid
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[:, 0:1]
        l_prev = l_ref[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        l_ref[...] = jnp.broadcast_to(alpha * l_prev + jnp.sum(p, axis=1, keepdims=True),
                                      l_ref.shape)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(kv_buf.dtype), kv_buf[b, :, :R], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    pages.loop(compute)
    l = l_ref[:, 0:1]
    o_ref[0] = (acc_ref[...] / jnp.where(l > 0.0, l, 1.0)).astype(o_ref.dtype)


def paged_mla_decode_fwd(
    q: jnp.ndarray,  # (n_slots, H, W) in the arena's dtype
    kv_pages: jnp.ndarray,  # (n_pages, P, W)
    block: jnp.ndarray,  # (n_slots, pages_per_slot) int32
    valid_len: jnp.ndarray,  # (n_slots,) int32, each ≥ 1
    *,
    value_width: int,
    scale: float,
    pages_per_chunk: int,
    interpret: bool,
):
    n_slots, H, W = q.shape
    P = kv_pages.shape[1]
    pps = block.shape[1]
    C = min(pages_per_chunk, pps)
    R = value_width
    kernel = lambda *refs: _paged_mla_kernel(
        *refs, pages_per_slot=pps, pages_per_chunk=C, scale=scale
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_slots,),
        in_specs=[
            pl.BlockSpec((1, H, W), lambda s, blk, vl: (s, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, H, R), lambda s, blk, vl: (s, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, C * P, W), kv_pages.dtype),
            pltpu.SemaphoreType.DMA((1, 2)),
            pltpu.VMEM((H, 128), jnp.float32),
            pltpu.VMEM((H, 128), jnp.float32),
            pltpu.VMEM((H, R), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_slots, H, R), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=pltpu.InterpretParams() if interpret else False,
        name="paged_mla_decode",
    )(block.reshape(-1).astype(jnp.int32), valid_len.astype(jnp.int32), q, kv_pages)
