"""Paged decode-attention Pallas kernel (interpret mode) against the XLA
reference over the gathered page view of the same arena.

The kernel reads each slot's live pages through the block table; the
reference gathers every slot's whole page row (``paged_view``) and masks
by the valid length.  Both attend over the same positions, so they agree
to f32 rounding of the softmax sums.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attention import ops
from repro.models.cache import NULL_PAGE, PagedKVCache, paged_view


def _arena(rng, n_slots, pps, Hkv, P, D, dtype, *, shuffle=True):
    """An arena with a distinct page for every block-table entry (plus the
    null page), filled with random K/V, and its block table."""
    n_pages = 1 + n_slots * pps
    k = rng.normal(size=(n_pages, Hkv, P, D))
    v = rng.normal(size=(n_pages, Hkv, P, D))
    ids = np.arange(1, n_pages)
    if shuffle:
        ids = rng.permutation(ids)
    block = ids.reshape(n_slots, pps).astype(np.int32)
    return (PagedKVCache(k=jnp.asarray(k, dtype), v=jnp.asarray(v, dtype)),
            block)


# (name, Hq, Hkv, D, P, pages_per_slot, valid lengths, pages_per_chunk)
CASES = [
    # ragged: 1, a page boundary, one past it, a full row of max_seq
    ("ragged_chunk_divides", 4, 2, 16, 4, 6, [1, 4, 5, 24], 2),
    ("ragged_chunk_not_dividing", 4, 2, 16, 4, 6, [1, 4, 5, 24], 4),
    ("ragged_one_page_chunks", 4, 2, 16, 4, 6, [1, 4, 5, 24], 1),
    ("chunk_wider_than_row", 4, 2, 16, 4, 6, [3, 13, 24], 32),
    ("chunk_of_three", 8, 2, 16, 4, 7, [28, 9, 12, 25, 1], 3),
    ("mha_one_kv_head", 4, 1, 32, 8, 4, [32, 17, 8], 2),
    # qwen2-1.5b's widths: GQA 12/2, head 128, page 16, few pages
    ("qwen2_widths", 12, 2, 128, 16, 5, [80, 17, 1, 33], 2),
]


@pytest.mark.parametrize("case", CASES, ids=[c[0] for c in CASES])
def test_paged_matches_xla_over_view(case):
    name, Hq, Hkv, D, P, pps, valid, C = case
    rng = np.random.default_rng(sum(map(ord, name)))
    n_slots = len(valid)
    arena, block = _arena(rng, n_slots, pps, Hkv, P, D, jnp.float32)
    q = jnp.asarray(rng.normal(size=(n_slots, Hq, D)), jnp.float32)
    vl = jnp.asarray(valid, jnp.int32)
    got = ops.paged_decode_attention(q, arena.k, arena.v, jnp.asarray(block),
                                     vl, pages_per_chunk=C)
    ref = ops.decode_attention_xla(q, *paged_view(arena, jnp.asarray(block)), vl)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("shuffle", [True, False], ids=["shuffled", "in_order"])
def test_inactive_slot_reads_only_the_null_page(shuffle):
    """An inactive slot (block row all ``NULL_PAGE``, valid 1) attends to
    row 0 of the null page and leaves the live slots' results as they
    are; the null page's contents do not reach a live slot."""
    Hq, Hkv, D, P, pps = 4, 2, 16, 4, 5
    rng = np.random.default_rng(3)
    arena, block = _arena(rng, 3, pps, Hkv, P, D, jnp.float32, shuffle=shuffle)
    block[1] = NULL_PAGE
    valid = jnp.asarray([17, 1, 9], jnp.int32)
    q = jnp.asarray(rng.normal(size=(3, Hq, D)), jnp.float32)
    got = ops.paged_decode_attention(q, arena.k, arena.v, jnp.asarray(block),
                                     valid, pages_per_chunk=2)
    ref = ops.decode_attention_xla(q, *paged_view(arena, jnp.asarray(block)), valid)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    # one valid position: the output is that position's value rows
    G = Hq // Hkv
    v0 = np.repeat(np.asarray(arena.v[NULL_PAGE, :, 0]), G, axis=0)
    np.testing.assert_allclose(np.asarray(got[1]), v0, rtol=1e-6, atol=1e-6)
    # a live slot does not see the null page: scribbling on it changes nothing
    noisy = PagedKVCache(k=arena.k.at[NULL_PAGE].set(1e4),
                         v=arena.v.at[NULL_PAGE].set(1e4))
    again = ops.paged_decode_attention(q, noisy.k, noisy.v, jnp.asarray(block),
                                       valid, pages_per_chunk=2)
    np.testing.assert_array_equal(np.asarray(again[0]), np.asarray(got[0]))
    np.testing.assert_array_equal(np.asarray(again[2]), np.asarray(got[2]))


def test_pages_past_the_length_are_never_read():
    """Non-finite values in every page past a slot's last live page do
    not reach its output, nor do the keys past its length in the last
    live page.  (That page's value rows past the length are weighted by
    exact zeros; they are zeros or an earlier request's values, so
    finite, and here they are large.)"""
    Hq, Hkv, D, P, pps = 4, 2, 16, 4, 6
    rng = np.random.default_rng(5)
    arena, block = _arena(rng, 2, pps, Hkv, P, D, jnp.float32)
    valid = np.asarray([6, 13], np.int32)
    k, v = np.array(arena.k), np.array(arena.v)
    for s, n in enumerate(valid):
        live = -(-n // P)
        for j, page in enumerate(block[s]):
            if j >= live:
                k[page], v[page] = np.nan, np.nan
            elif j == live - 1:
                k[page, :, n - j * P:], v[page, :, n - j * P:] = np.inf, 1e30
    q = jnp.asarray(rng.normal(size=(2, Hq, D)), jnp.float32)
    clean = ops.paged_decode_attention(q, arena.k, arena.v, jnp.asarray(block),
                                       jnp.asarray(valid), pages_per_chunk=4)
    dirty = ops.paged_decode_attention(q, jnp.asarray(k), jnp.asarray(v),
                                       jnp.asarray(block), jnp.asarray(valid),
                                       pages_per_chunk=4)
    assert np.isfinite(np.asarray(dirty)).all()
    np.testing.assert_array_equal(np.asarray(dirty), np.asarray(clean))


def test_bf16_arena():
    """bf16 arena and query, as the serving configuration runs them:
    within bf16 rounding of the reference over the same bf16 view."""
    Hq, Hkv, D, P, pps = 12, 2, 128, 16, 4
    rng = np.random.default_rng(7)
    arena, block = _arena(rng, 3, pps, Hkv, P, D, jnp.bfloat16)
    q = jnp.asarray(rng.normal(size=(3, Hq, D)), jnp.bfloat16)
    valid = jnp.asarray([64, 20, 1], jnp.int32)
    got = ops.paged_decode_attention(q, arena.k, arena.v, jnp.asarray(block),
                                     valid, pages_per_chunk=2)
    ref = ops.decode_attention_xla(q, *paged_view(arena, jnp.asarray(block)), valid)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32), rtol=0, atol=2e-2)
