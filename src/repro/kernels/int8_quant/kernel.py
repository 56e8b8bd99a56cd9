"""Pallas TPU int8 symmetric quantization for the wire layer.

The jnp reference (``core.compression.int8_compress``) makes three full
passes over each leaf: abs-max reduction, quantize, dequantize.  Here the
same math runs as two streaming kernels:

1. ``_absmax_kernel`` — per-lane running max of |x| into a VMEM scratch
   row (max is exactly order-independent, so the blocked reduction is
   bit-equal to XLA's);
2. ``_quant_kernel`` — clip(round(x/s))·s in ONE pass, emitting the
   dequantized f32 the aggregation path consumes (the int8 intermediate
   never touches HBM).

Block shape (8, 1024) keeps f32 tiles lane-aligned; the scale rides in
(1, 1) SMEM.  All formulas match the reference op-for-op, so the kernel
path is bit-equal to the pure-jnp wire.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK = 1024
ROWS = 8
LANE = 128


def _absmax_kernel(x_ref, o_ref, acc_ref):
    """Streaming per-lane max of |x|; o: (1, LANE) lane maxima (reduce
    outside for the scalar).  Tail padding is zeros and max(|x|, 0) is a
    no-op, so no validity mask is needed."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = jnp.abs(x_ref[0].astype(jnp.float32))  # (ROWS, BLOCK)
    lanes = jnp.max(x.reshape(-1, LANE), axis=0)  # (LANE,)
    acc_ref[...] = jnp.maximum(acc_ref[...], lanes[None, :])

    @pl.when(i == pl.num_programs(0) - 1)
    def _fin():
        o_ref[...] = acc_ref[...]


def _quant_kernel(x_ref, s_ref, o_ref):
    """Quantize→dequantize in one pass: clip(round(x/s), ±127)·s, exactly
    the reference formula including the int8 round-trip cast."""
    s = s_ref[0, 0]
    x = x_ref[0]
    q = jnp.clip(jnp.round(x / s), -127, 127).astype(jnp.int8)
    o_ref[...] = (q.astype(x.dtype) * s)[None]


def _pad_flat(x: jnp.ndarray):
    flat = x.reshape(-1)
    n = flat.shape[0]
    per = ROWS * BLOCK
    pad = (-n) % per
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), x.dtype)])
    return flat.reshape(-1, ROWS, BLOCK), n


def absmax(x: jnp.ndarray, *, interpret: bool = True) -> jnp.ndarray:
    """max |x| over the whole leaf (f32 scalar)."""
    blocks, _ = _pad_flat(x)
    nb = blocks.shape[0]
    lanes = pl.pallas_call(
        _absmax_kernel,
        grid=(nb,),
        in_specs=[pl.BlockSpec((1, ROWS, BLOCK), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((1, LANE), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, LANE), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, LANE), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(blocks)
    return jnp.max(lanes)


def quant_dequant(
    x: jnp.ndarray, scale: jnp.ndarray, *, interpret: bool = True
) -> jnp.ndarray:
    """clip(round(x/scale))·scale, one fused pass."""
    blocks, _ = _pad_flat(x)
    nb = blocks.shape[0]
    s = scale.reshape(1, 1).astype(jnp.float32)
    out = pl.pallas_call(
        _quant_kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((1, ROWS, BLOCK), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, ROWS, BLOCK), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(blocks.shape, x.dtype),
        interpret=interpret,
    )(blocks, s)
    return out.reshape(-1)[: x.size].reshape(x.shape)
