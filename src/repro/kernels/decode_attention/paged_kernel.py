"""Pallas TPU decode attention over a paged KV arena, through the block table.

One query token per slot attends to that slot's cached positions, which
live in fixed-size pages of a shared arena ``(n_pages, Hkv, P, D)`` and
are addressed through the slot's row of the block table.  The block table
(flattened) and the per-slot valid lengths arrive as scalar prefetch, so
the kernel knows before it starts which pages each slot holds; the arena
is left where XLA placed it (``memory_space=ANY``) and the kernel copies
only a slot's live pages, ``ceil(valid / P)`` of them, into VMEM.  Pages
past a slot's length are neither copied nor attended.

Grid: one step per slot, all ``Hkv`` heads of that slot together.  Inside
a step a loop runs over the slot's live pages in chunks of
``pages_per_chunk``, double-buffered: the copies of chunk c + 1 run while
chunk c is computed.  One page of every KV head, ``(Hkv, P, D)``, is one
contiguous copy; its rows land at ``[:, i·P : (i+1)·P]`` of a head-major
``(Hkv, chunk·P, D)`` buffer, so each head's keys are one ``(chunk·P, D)``
matrix for the MXU.

The softmax is the same online merge as ``kernel._decode_kernel`` (m, l,
acc in f32).  The mask is an iota compare against the valid length; it
removes positions past the length on the chunk that holds it.  A chunk's
pages past the slot's last live page are not copied: their value rows are
zeroed instead, so the masked weights (exact zeros) multiply finite
values.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decode_attention.kernel import NEG_INF


class SlotPages:
    """Double-buffered copies of one slot's live pages, ``pages_per_chunk``
    a chunk, from page arenas left in HBM into VMEM buffers; shared by the
    GQA and the MLA paged kernels.

    ``streams`` pairs each arena with its buffer and a flag: page ``i`` of
    a chunk lands at rows ``[i·P, (i+1)·P)`` of the buffer's position axis
    (the second to last), and a flagged buffer has the rows of the chunk's
    pages past the slot's last live page zeroed instead of copied, so that
    masked weights (exact zeros) multiply finite values.  Stream ``j``
    waits on semaphore row ``j`` of ``sems`` ((n_streams, 2)).
    """

    def __init__(self, block_ref, slot, valid, streams, sems, *,
                 pages_per_slot: int, pages_per_chunk: int, page_size: int):
        self.block_ref = block_ref
        self.streams = streams
        self.sems = sems
        self.C = pages_per_chunk
        self.P = page_size
        self.n_live = (valid + page_size - 1) // page_size
        self.n_chunks = (self.n_live + self.C - 1) // self.C
        self.row0 = slot * pages_per_slot

    def _rows(self, i):
        return pl.ds(pl.multiple_of(i * self.P, self.P), self.P)

    @staticmethod
    def _at(buf, b, rows):
        return buf.at[b, :, rows, :] if len(buf.shape) == 4 else buf.at[b, rows, :]

    def _each_copy(self, chunk, b, i, act):
        """``act`` on the copy of page ``i`` of ``chunk`` into buffer ``b``,
        stream by stream."""
        page = self.block_ref[self.row0 + chunk * self.C + i]
        rows = self._rows(i)
        for j, (arena, buf, _) in enumerate(self.streams):
            act(pltpu.make_async_copy(arena.at[page], self._at(buf, b, rows), self.sems.at[j, b]))

    def live_in(self, chunk):
        return jnp.minimum(self.C, self.n_live - chunk * self.C)

    def start(self, chunk, b):
        """Issue the copies of ``chunk`` into buffer ``b``."""
        def copy(i, carry):
            self._each_copy(chunk, b, i, lambda c: c.start())
            return carry

        def zero(i, carry):
            rows = self._rows(i)
            for _, buf, flag in self.streams:
                if flag:
                    dst = self._at(buf, b, rows)
                    dst[...] = jnp.zeros(dst.shape, buf.dtype)
            return carry

        jax.lax.fori_loop(0, self.live_in(chunk), copy, 0)
        jax.lax.fori_loop(self.live_in(chunk), self.C, zero, 0)

    def wait(self, chunk, b):
        def one(i, carry):
            self._each_copy(chunk, b, i, lambda c: c.wait())
            return carry

        jax.lax.fori_loop(0, self.live_in(chunk), one, 0)

    def loop(self, compute):
        """Run ``compute(chunk, b)`` over the slot's chunks, the copies of
        chunk c + 1 in flight while chunk c is computed."""
        self.start(0, 0)

        def body(chunk, carry):
            b = chunk % 2

            @pl.when(chunk + 1 < self.n_chunks)
            def _prefetch():
                self.start(chunk + 1, 1 - b)

            self.wait(chunk, b)
            compute(chunk, b)
            return carry

        jax.lax.fori_loop(0, self.n_chunks, body, 0)


def _paged_decode_kernel(
    block_ref,  # SMEM (n_slots · pages_per_slot,) physical page ids
    valid_ref,  # SMEM (n_slots,) valid positions per slot
    q_ref,  # (1, Hkv, G, D)
    k_arena,  # (n_pages, Hkv, P, D), left where XLA placed it
    v_arena,
    o_ref,  # (1, Hkv, G, D)
    k_buf,  # VMEM (2, Hkv, C·P, D)
    v_buf,
    sems,  # DMA semaphores (2, 2): [k / v, buffer]
    m_ref,  # VMEM (Hkv, G, 128) f32
    l_ref,  # VMEM (Hkv, G, 128) f32
    acc_ref,  # VMEM (Hkv, G, D) f32
    *,
    pages_per_slot: int,
    pages_per_chunk: int,
):
    slot = pl.program_id(0)
    Hkv, P = k_arena.shape[1], k_arena.shape[2]
    C = pages_per_chunk
    valid = valid_ref[slot]
    pages = SlotPages(block_ref, slot, valid, [(k_arena, k_buf, False), (v_arena, v_buf, True)],
                      sems, pages_per_slot=pages_per_slot, pages_per_chunk=C, page_size=P)

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def compute(chunk, buf):
        for h in range(Hkv):
            q = q_ref[0, h].astype(jnp.float32)  # (G, D)
            k = k_buf[buf, h].astype(jnp.float32)  # (C·P, D)
            v = v_buf[buf, h].astype(jnp.float32)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * (q.shape[-1] ** -0.5)  # (G, C·P)
            pos = chunk * (C * P) + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1
            )
            mask = pos < valid
            s = jnp.where(mask, s, NEG_INF)

            m_prev = m_ref[h][:, 0:1]  # (G, 1)
            l_prev = l_ref[h][:, 0:1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_ref[h] = jnp.broadcast_to(m_new, m_ref.shape[1:])
            l_ref[h] = jnp.broadcast_to(l_new, l_ref.shape[1:])

    pages.loop(compute)

    for h in range(Hkv):
        l = l_ref[h][:, 0:1]
        denom = jnp.where(l > 0.0, l, 1.0)
        o_ref[0, h] = (acc_ref[h] / denom).astype(o_ref.dtype)


def paged_decode_attention_fwd(
    q: jnp.ndarray,  # (n_slots, Hkv, G, D)
    k_pages: jnp.ndarray,  # (n_pages, Hkv, P, D)
    v_pages: jnp.ndarray,
    block: jnp.ndarray,  # (n_slots, pages_per_slot) int32
    valid_len: jnp.ndarray,  # (n_slots,) int32, each ≥ 1
    *,
    pages_per_chunk: int,
    interpret: bool,
):
    n_slots, Hkv, G, D = q.shape
    P = k_pages.shape[2]
    pps = block.shape[1]
    C = min(pages_per_chunk, pps)
    kernel = lambda *refs: _paged_decode_kernel(
        *refs, pages_per_slot=pps, pages_per_chunk=C
    )
    slot_block = pl.BlockSpec((1, Hkv, G, D), lambda s, blk, vl: (s, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_slots,),
        in_specs=[
            slot_block,
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=slot_block,
        scratch_shapes=[
            pltpu.VMEM((2, Hkv, C * P, D), k_pages.dtype),
            pltpu.VMEM((2, Hkv, C * P, D), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((Hkv, G, 128), jnp.float32),
            pltpu.VMEM((Hkv, G, 128), jnp.float32),
            pltpu.VMEM((Hkv, G, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_slots, Hkv, G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=pltpu.InterpretParams() if interpret else False,
        name="paged_decode_attention",
    )(block.reshape(-1).astype(jnp.int32), valid_len.astype(jnp.int32),
      q, k_pages, v_pages)
