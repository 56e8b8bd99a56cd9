"""Decode caches for every mixer family (pytree NamedTuples).

``serve_step`` lowers ONE new token against a cache of ``seq_len`` — these
structures are what gets sharded by the decode sharding rules (KV sequence
dim over the data axis for `long_500k`, heads over the model axis).

Continuous-batching serving adds a second cache family: ``PagedKVCache``
is a physical **page arena** shared by every in-flight sequence, addressed
through a per-slot **block table** (slot → ordered physical page ids).
Long and short sequences draw from the same pool, so the arena can be
provisioned below ``n_slots × max_seq_len``; the host-side
``PageAllocator`` owns which pages are free.  Page 0 is the reserved
**null page**: freed/inactive slots point their whole block row at it, so
the compiled decode step can keep writing "their" keys without masking —
the writes land in garbage memory no live sequence can see.  That is what
makes join/leave a pure data change (no retrace).

The arena is laid out ``(n_pages, H_kv, page_size, D)``: one page of
every KV head is one contiguous block, and each head's ``(page_size, D)``
rows are whole TPU tiles, so the paged decode kernel
(``kernels/decode_attention/paged_kernel.py``) copies a slot's live pages
straight from the arena.  ``paged_view`` gathers a dense per-slot view for
the XLA decode path.

MLA's serving arena, ``PagedMLACache``, is laid out ``(n_pages,
page_size, W)``: one row per position, the normed latent and the roped
shared key side by side (padded to whole lane tiles), so the paged MLA kernel
(``kernels/decode_attention/paged_mla_kernel.py``) copies one block per
page for both its keys and its values.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp


class KVCache(NamedTuple):
    k: jnp.ndarray  # (B, S, H_kv, D)
    v: jnp.ndarray  # (B, S, H_kv, D)
    index: jnp.ndarray  # scalar int32 — number of valid positions


class MLACache(NamedTuple):
    """DeepSeek MLA latent cache: compressed KV + shared rope key."""

    c_kv: jnp.ndarray  # (B, S, kv_lora_rank)
    k_rope: jnp.ndarray  # (B, S, qk_rope_head_dim)
    index: jnp.ndarray


class MambaCache(NamedTuple):
    conv: jnp.ndarray  # (B, d_conv - 1, d_inner) — conv tail window
    ssm: jnp.ndarray  # (B, d_inner, d_state)


class MLSTMCache(NamedTuple):
    C: jnp.ndarray  # (B, H, Dk, Dv) matrix memory
    n: jnp.ndarray  # (B, H, Dk) normalizer
    m: jnp.ndarray  # (B, H) gate stabilizer


class SLSTMCache(NamedTuple):
    c: jnp.ndarray  # (B, d)
    n: jnp.ndarray  # (B, d)
    h: jnp.ndarray  # (B, d)
    m: jnp.ndarray  # (B, d)


def kv_cache_init(batch: int, seq: int, n_kv: int, head_dim: int, dtype) -> KVCache:
    return KVCache(
        k=jnp.zeros((batch, seq, n_kv, head_dim), dtype),
        v=jnp.zeros((batch, seq, n_kv, head_dim), dtype),
        index=jnp.zeros((), jnp.int32),
    )


def mla_cache_init(batch: int, seq: int, kv_lora: int, rope_dim: int, dtype) -> MLACache:
    return MLACache(
        c_kv=jnp.zeros((batch, seq, kv_lora), dtype),
        k_rope=jnp.zeros((batch, seq, rope_dim), dtype),
        index=jnp.zeros((), jnp.int32),
    )


def mamba_cache_init(batch: int, d_conv: int, d_inner: int, d_state: int, dtype) -> MambaCache:
    return MambaCache(
        conv=jnp.zeros((batch, d_conv - 1, d_inner), dtype),
        ssm=jnp.zeros((batch, d_inner, d_state), jnp.float32),
    )


def mlstm_cache_init(batch: int, heads: int, dk: int, dv: int) -> MLSTMCache:
    return MLSTMCache(
        C=jnp.zeros((batch, heads, dk, dv), jnp.float32),
        n=jnp.zeros((batch, heads, dk), jnp.float32),
        m=jnp.full((batch, heads), -1e30, jnp.float32),
    )


def slstm_cache_init(batch: int, d: int) -> SLSTMCache:
    return SLSTMCache(
        c=jnp.zeros((batch, d), jnp.float32),
        n=jnp.zeros((batch, d), jnp.float32),
        h=jnp.zeros((batch, d), jnp.float32),
        m=jnp.full((batch, d), -1e30, jnp.float32),
    )


# ----------------------------------------------------------------------------
# Paged KV cache — the continuous-batching serving arena
# ----------------------------------------------------------------------------

#: physical page id every freed / inactive block-table entry points at;
#: never handed out by ``PageAllocator``, so masked writes are harmless
NULL_PAGE = 0


class PagedKVCache(NamedTuple):
    """Physical KV page arena for one layer.

    Unlike ``KVCache`` there is no per-sequence axis and no fill index:
    position is owned by the caller's block table + per-slot lengths
    (host-managed, passed as jit *arguments* so slot churn never
    retraces).
    """

    k: jnp.ndarray  # (n_pages, H_kv, page_size, D)
    v: jnp.ndarray  # (n_pages, H_kv, page_size, D)


def paged_kv_cache_init(
    n_pages: int, page_size: int, n_kv: int, head_dim: int, dtype
) -> PagedKVCache:
    return PagedKVCache(
        k=jnp.zeros((n_pages, n_kv, page_size, head_dim), dtype),
        v=jnp.zeros((n_pages, n_kv, page_size, head_dim), dtype),
    )


def paged_view(cache: PagedKVCache, block: jnp.ndarray):
    """Gather each slot's pages into a dense per-slot view (the XLA
    decode path and the reference; the Pallas path reads the arena
    through the block table instead).

    ``block``: (n_slots, pages_per_slot) physical page ids.  Returns
    ``(k, v)`` of shape (n_slots, pages_per_slot · page_size, H_kv, D);
    positions beyond a slot's length hold stale/null-page garbage and
    must be masked by the attention's ``valid_len``.
    """
    n_slots, pp = block.shape
    _, Hkv, P, D = cache.k.shape

    def view(arena):
        pages = jnp.take(arena, block.reshape(-1), axis=0)
        pages = pages.reshape(n_slots, pp, Hkv, P, D).transpose(0, 1, 3, 2, 4)
        return pages.reshape(n_slots, pp * P, Hkv, D)

    with jax.named_scope("kv_gather"):
        return view(cache.k), view(cache.v)


def paged_append(
    cache: PagedKVCache,
    block: jnp.ndarray,  # (n_slots, pages_per_slot)
    length: jnp.ndarray,  # (n_slots,) — tokens already stored per slot
    k_tok: jnp.ndarray,  # (n_slots, H_kv, D) — one new token per slot
    v_tok: jnp.ndarray,
) -> PagedKVCache:
    """Scatter one token per slot at its next logical position.

    Inactive slots need no masking: their block row is all ``NULL_PAGE``,
    so the write lands in the trash page (several inactive slots may
    collide there — by design).
    """
    P = cache.k.shape[2]
    with jax.named_scope("kv_append"):
        page = jnp.take_along_axis(block, (length // P)[:, None], axis=1)[:, 0]
        return _scatter_rows(cache, page, length % P, k_tok, v_tok)


def paged_write(
    cache: PagedKVCache,
    block_row: jnp.ndarray,  # (pages_per_slot,) — ONE slot's pages
    k_seq: jnp.ndarray,  # (..., S, H_kv, D) — prefilled keys, rows < n_valid real
    v_seq: jnp.ndarray,
    n_valid: jnp.ndarray,
) -> PagedKVCache:
    """Write a prefilled sequence into one slot's pages (the join path).

    Rows ≥ ``n_valid`` (prompt-bucket padding) are redirected to the null
    page instead of being masked out, so the scatter shape is static.
    A stack of arenas (leading layer axes on ``cache`` and on the
    sequences alike) is written in one scatter.
    """
    P = cache.k.shape[-2]
    S = k_seq.shape[-3]
    pos = jnp.arange(S)
    page = jnp.where(pos < n_valid, block_row[pos // P], NULL_PAGE)
    return _scatter_rows(cache, page, pos % P, k_seq, v_seq)


def _arena_rows(lead: tuple, n_pages: int, heads: int, P: int, page, off):
    """Row numbers, in an arena stack seen as its ``(rows, D)`` matrix,
    of token t's ``heads`` rows at row ``off[t]`` of page ``page[t]``,
    for every arena of the stack (leading axes ``lead``)."""
    first_page = jnp.arange(math.prod(lead), dtype=page.dtype)[:, None, None] * n_pages
    return (((first_page + page[:, None]) * heads + jnp.arange(heads)) * P
            + off[:, None]).reshape(-1)


def _put_rows(stack, rows, new):
    """Scatter ``new`` as ``D``-wide rows into ``stack``'s ``(rows, D)``
    matrix, which keeps the arena's own layout: a scatter of windows, or
    one batched over the stack, has XLA lay the whole arena out anew
    around it."""
    D = stack.shape[-1]
    flat = stack.reshape(-1, D)
    flat = flat.at[rows].set(new.reshape(-1, D).astype(stack.dtype))
    return flat.reshape(stack.shape)


def _scatter_rows(cache: PagedKVCache, page, off, k_new, v_new) -> PagedKVCache:
    """Write token t's ``(H_kv, D)`` keys and values at row ``off[t]`` of
    page ``page[t]``, in every arena of a stack (``cache`` leaves
    ``(..., n_pages, H_kv, P, D)``, new values ``(..., T, H_kv, D)``)."""
    *lead, n_pages, Hkv, P, _ = cache.k.shape
    rows = _arena_rows(lead, n_pages, Hkv, P, page, off)
    return PagedKVCache(k=_put_rows(cache.k, rows, k_new), v=_put_rows(cache.v, rows, v_new))


# ----------------------------------------------------------------------------
# Paged latent cache — MLA's serving arena
# ----------------------------------------------------------------------------


class PagedMLACache(NamedTuple):
    """Physical page arena of MLA's latent cache for one layer.

    Each position holds one row: the normed latent ``c_kv``
    (kv_lora_rank values), then the roped shared key ``k_pe``
    (qk_rope_head_dim), then zeros up to a whole number of 128-lane
    tiles (``latent_width``: 576 values in 640 lanes for DeepSeek-V3, the
    lanes a 576-wide row takes in the chip's tiled layout anyway, made
    explicit so a page is one aligned copy).  The absorbed decode reads
    the whole row as the key and its first ``kv_lora_rank`` values as the
    value, so one copy of a page feeds both.  Addressed through the same
    block table and ``PageAllocator`` as ``PagedKVCache``.
    """

    kv: jnp.ndarray  # (n_pages, page_size, latent_width)


def latent_width(kv_lora_rank: int, rope_dim: int) -> int:
    """Lanes of one latent row: kv_lora_rank + rope_dim, padded to 128s."""
    return -(-(kv_lora_rank + rope_dim) // 128) * 128


def pad_lanes(x: jnp.ndarray, width: int) -> jnp.ndarray:
    """Zeros appended to the last axis up to ``width``."""
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, width - x.shape[-1])])


def paged_mla_cache_init(n_pages: int, page_size: int, width: int, dtype) -> PagedMLACache:
    return PagedMLACache(kv=jnp.zeros((n_pages, page_size, width), dtype))


def paged_mla_append(cache: PagedMLACache, block, length, row) -> PagedMLACache:
    """Scatter one latent row per slot (``row``: (n_slots, r + dr)) at its
    next logical position; inactive slots write into the null page."""
    P = cache.kv.shape[-2]
    with jax.named_scope("latent_append"):
        page = jnp.take_along_axis(block, (length // P)[:, None], axis=1)[:, 0]
        return _scatter_mla(cache, page, length % P, row)


def paged_mla_write(cache: PagedMLACache, block_row, rows, n_valid) -> PagedMLACache:
    """Write a prefilled sequence's latent rows (``(..., S, r + dr)``, rows
    ≥ ``n_valid`` redirected to the null page) into one slot's pages,
    every arena of a stack in one scatter."""
    P = cache.kv.shape[-2]
    S = rows.shape[-2]
    pos = jnp.arange(S)
    page = jnp.where(pos < n_valid, block_row[pos // P], NULL_PAGE)
    return _scatter_mla(cache, page, pos % P, rows)


def _scatter_mla(cache: PagedMLACache, page, off, new) -> PagedMLACache:
    *lead, n_pages, P, W = cache.kv.shape
    rows = _arena_rows(lead, n_pages, 1, P, page, off)
    return PagedMLACache(kv=_put_rows(cache.kv, rows, pad_lanes(new, W)))


def paged_mla_view(cache: PagedMLACache, block: jnp.ndarray) -> jnp.ndarray:
    """Each slot's pages gathered into a dense (n_slots, pages_per_slot ·
    P, W) view: the XLA decode path and the kernel's reference."""
    n_slots, pp = block.shape
    _, P, W = cache.kv.shape
    with jax.named_scope("kv_gather"):
        return jnp.take(cache.kv, block.reshape(-1), axis=0).reshape(n_slots, pp * P, W)


class PageAllocator:
    """Host-side free-list allocator over a ``PagedKVCache`` arena.

    LIFO reuse keeps recently-freed (cache-warm) pages hot.  Page
    ``NULL_PAGE`` (0) is reserved and never allocated.  Invariants are
    enforced loudly: freeing a page that isn't live raises, allocation
    beyond capacity returns None (callers queue the request instead of
    corrupting a live slot).
    """

    def __init__(self, n_pages: int):
        if n_pages < 2:
            raise ValueError("need ≥ 2 pages (page 0 is the null page)")
        self.n_pages = n_pages
        self._free = list(range(n_pages - 1, 0, -1))  # pop() yields 1, 2, …
        self._used: set = set()

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def used_pages(self) -> int:
        return len(self._used)

    def alloc(self, n: int) -> list | None:
        """``n`` physical page ids, or None if the arena can't supply them
        (all-or-nothing: a partial allocation is never handed out)."""
        if n < 0:
            raise ValueError(f"alloc({n})")
        if n > len(self._free):
            return None
        pages = [self._free.pop() for _ in range(n)]
        self._used.update(pages)
        return pages

    def free(self, pages) -> None:
        for p in pages:
            if p not in self._used:
                raise ValueError(
                    f"free() of page {p} which is not allocated "
                    f"(double free or foreign page)"
                )
            self._used.remove(p)
            self._free.append(p)
