"""Pallas TPU gradient top-k sparsification, sort-free.

Global top-k by magnitude is a *selection* problem; a global sort of a
multi-GB gradient would be HBM-bandwidth disaster.  TPU-native design:

1. ``count_kernel`` — a streaming reduction: for 128 candidate
   thresholds t_j (in SMEM), count per block and per lane how many
   |x| ≥ t_j, accumulating into an output resident across the grid;
   one pass evaluates 128 bisection candidates — the whole threshold
   search costs ~2 passes over the data instead of ~30.
2. host-free binary refinement picks the largest t with count ≥ k;
3. ``mask_kernel`` — one more streaming pass emits x·1{|x| ≥ t}.

Total: 3 passes over HBM (vs. sort's O(log n) passes), MXU untouched (VPU
compare+select only), block shape (8, 1024) keeps tiles lane-aligned.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK = 1024
ROWS = 8
NCAND = 128


def _count_kernel(x_ref, t_ref, o_ref):
    """x: (1, ROWS, BLOCK) block; t: (1, NCAND) SMEM candidates;
    o: (NCAND, BLOCK) per-lane counts, resident across the grid.

    Row j of ``o`` counts, per lane, the elements with |x| ≥ t_j: each
    candidate is a compare + sublane reduction on the tile as it lies,
    with no reshape of the tile and no cast of the compare result (the
    TPU compiler refuses both).  The wrapper sums the lanes.
    """
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = jnp.abs(x_ref[0].astype(jnp.float32))  # (ROWS, BLOCK)

    def count_one(j, carry):
        hits = jnp.where(x >= t_ref[0, j], 1.0, 0.0)
        o_ref[pl.ds(j, 1), :] += jnp.sum(hits, axis=0, keepdims=True)
        return carry

    jax.lax.fori_loop(0, NCAND, count_one, 0)


def _mask_kernel(x_ref, t_ref, o_ref):
    t = t_ref[0, 0]
    x = x_ref[...]
    o_ref[...] = jnp.where(jnp.abs(x.astype(jnp.float32)) >= t, x, 0.0).astype(
        o_ref.dtype
    )


def _valid_mask(i, n):
    """1{position < n} for block i of the padded (ROWS, BLOCK) layout, so
    the tail padding never pollutes the survivor count."""
    row = jax.lax.broadcasted_iota(jnp.int32, (ROWS, BLOCK), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (ROWS, BLOCK), 1)
    pos = i * (ROWS * BLOCK) + row * BLOCK + col
    return pos < n


def _encode_kernel(c_ref, t_ref, n_ref, o_ref, res_ref, cnt_ref):
    """Fused wire encode: ONE pass over c emits survivors, EF residual and
    per-position survivor counts.

    c: (1, ROWS, BLOCK) corrected values (update + carried residual);
    t: (1, 1) SMEM threshold; n: (1, 1) SMEM true element count;
    o = c·1{|c| ≥ t} (the push) as a select, which is what XLA makes of
    the jitted reference's mask-multiply (dropped entries are +0.0), and
    res = c − o (the next EF residual), so the kernel path is bit-equal
    to the jitted pure-jnp wire.  ``cnt`` is a (ROWS, BLOCK) tile resident
    across the grid that counts survivors per position; the wrapper sums
    it.  No compare result is cast: the TPU compiler refuses a bool→f32
    conversion here.
    """
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    c = c_ref[0]  # (ROWS, BLOCK)
    keep = jnp.abs(c) >= t_ref[0, 0]
    o = jnp.where(keep, c, 0.0).astype(c.dtype)
    o_ref[...] = o[None]
    if res_ref is not None:
        res_ref[...] = (c - o)[None]
    counted = jnp.logical_and(keep, _valid_mask(i, n_ref[0, 0]))
    cnt_ref[...] += jnp.where(counted, 1.0, 0.0)


def _select_kernel(c_ref, t_ref, n_ref, o_ref, cnt_ref):
    """`_encode_kernel` without the EF residual output (dense-residual-free
    wires): survivors + survivor counts in one pass."""
    _encode_kernel(c_ref, t_ref, n_ref, o_ref, None, cnt_ref)


def _pad_flat(x: jnp.ndarray):
    flat = x.reshape(-1)
    n = flat.shape[0]
    per = ROWS * BLOCK
    pad = (-n) % per
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), x.dtype)])
    return flat.reshape(-1, ROWS, BLOCK), n


def count_ge(x: jnp.ndarray, thresholds: jnp.ndarray, *, interpret: bool = True):
    """Counts of |x| >= t for each of the NCAND thresholds (zero-padding is
    excluded by construction because thresholds are > 0)."""
    blocks, n = _pad_flat(x)
    nb = blocks.shape[0]
    t = thresholds.reshape(1, NCAND).astype(jnp.float32)
    lanes = pl.pallas_call(
        _count_kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((1, ROWS, BLOCK), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, NCAND), lambda i: (0, 0), memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((NCAND, BLOCK), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((NCAND, BLOCK), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
    )(blocks, t)
    # per-lane counts are exact in f32 (≤ ROWS per block); summing them
    # in int32 keeps the total exact past 2**24 elements
    return jnp.sum(lanes.astype(jnp.int32), axis=1)


def apply_threshold(x: jnp.ndarray, thresh: jnp.ndarray, *, interpret: bool = True):
    blocks, n = _pad_flat(x)
    nb = blocks.shape[0]
    t = thresh.reshape(1, 1).astype(jnp.float32)
    out = pl.pallas_call(
        _mask_kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((1, ROWS, BLOCK), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, ROWS, BLOCK), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(blocks.shape, x.dtype),
        interpret=interpret,
    )(blocks, t)
    return out.reshape(-1)[: x.size].reshape(x.shape)


def encode_threshold(
    c: jnp.ndarray,
    thresh: jnp.ndarray,
    *,
    with_residual: bool = True,
    interpret: bool = True,
):
    """One fused pass: (survivors, EF residual or None, survivor count).

    ``o = c·1{|c| ≥ t}`` and ``res = c − o`` — the exact reference
    formulas, so outputs are bit-equal to the jnp path (signed zeros
    included).  The count excludes tail padding.
    """
    blocks, n = _pad_flat(c)
    nb = blocks.shape[0]
    t = thresh.reshape(1, 1).astype(jnp.float32)
    n_s = jnp.full((1, 1), n, jnp.int32)
    block_spec = pl.BlockSpec((1, ROWS, BLOCK), lambda i: (i, 0, 0))
    smem_spec = pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM)
    cnt_spec = pl.BlockSpec((ROWS, BLOCK), lambda i: (0, 0))
    kernel = _encode_kernel if with_residual else _select_kernel
    out_specs = [block_spec] + ([block_spec] if with_residual else []) + [cnt_spec]
    out_shape = (
        [jax.ShapeDtypeStruct(blocks.shape, c.dtype)]
        + ([jax.ShapeDtypeStruct(blocks.shape, c.dtype)] if with_residual else [])
        + [jax.ShapeDtypeStruct((ROWS, BLOCK), jnp.float32)]
    )
    outs = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[block_spec, smem_spec, smem_spec],
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(blocks, t, n_s)

    def unpad(b):
        return b.reshape(-1)[: c.size].reshape(c.shape)

    count = jnp.sum(outs[-1].astype(jnp.int32))
    if with_residual:
        return unpad(outs[0]), unpad(outs[1]), count
    return unpad(outs[0]), None, count
