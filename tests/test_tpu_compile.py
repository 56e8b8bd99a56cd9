"""The main path's kernels and the paged decode step, compiled for a
described TPU v5e at real widths.

Nothing runs: the TPU compiler, which is installed with JAX, compiles
for a ``v5e:2x2`` topology that is described, not attached.  It refuses
what interpret mode accepts — a block not aligned to the (8, 128)
tiling, a reshape or cast the vector layout cannot express, a program
that does not fit the chip's memory — so these tests catch a kernel
that would fail on its first call on the chip.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every test worker
imports every test file.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import ops as da_ops
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.int8_quant import kernel as q8_kernel
from repro.kernels.topk_compress import kernel as tk_kernel

#: v5e HBM per chip
HBM_BYTES = 16 * 1024**3


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means: cannot describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    return desc


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def test_decode_attention_at_qwen2_widths(one_chip):
    # qwen2-1.5b serving: 8 slots, 12 query heads over 2 KV heads, head 128
    B, Hq, Hkv, D, S = 8, 12, 2, 128, 2048
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    c = _compile(
        lambda q, k, v, n: da_ops.decode_attention(q, k, v, n, interpret=False),
        sds((B, Hq, D), jnp.bfloat16), sds((B, S, Hkv, D), jnp.bfloat16),
        sds((B, S, Hkv, D), jnp.bfloat16), sds((B,), jnp.int32),
    )
    assert _has_kernel(c)


def test_flash_attention_at_prefill_widths(one_chip):
    B, T, H, D = 1, 2048, 12, 128
    x = jax.ShapeDtypeStruct((B, T, H, D), jnp.bfloat16, sharding=one_chip)
    c = _compile(
        lambda q, k, v: fa_ops.flash_attention(q, k, v, causal=True,
                                               interpret=False),
        x, x, x,
    )
    assert _has_kernel(c)


LEAF = jax.ShapeDtypeStruct((1 << 20,), jnp.float32)


def test_topk_count_ge(one_chip):
    x = jax.ShapeDtypeStruct(LEAF.shape, LEAF.dtype, sharding=one_chip)
    t = jax.ShapeDtypeStruct((tk_kernel.NCAND,), jnp.float32, sharding=one_chip)
    c = _compile(lambda x, t: tk_kernel.count_ge(x, t, interpret=False), x, t)
    assert _has_kernel(c)


@pytest.mark.parametrize("with_residual", [True, False])
def test_topk_encode_threshold(one_chip, with_residual):
    x = jax.ShapeDtypeStruct(LEAF.shape, LEAF.dtype, sharding=one_chip)
    t = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    c = _compile(
        lambda x, t: tk_kernel.encode_threshold(
            x, t, with_residual=with_residual, interpret=False
        ),
        x, t,
    )
    assert _has_kernel(c)


def test_int8_absmax(one_chip):
    x = jax.ShapeDtypeStruct(LEAF.shape, LEAF.dtype, sharding=one_chip)
    c = _compile(lambda x: q8_kernel.absmax(x, interpret=False), x)
    assert _has_kernel(c)


def test_int8_quant_dequant(one_chip):
    x = jax.ShapeDtypeStruct(LEAF.shape, LEAF.dtype, sharding=one_chip)
    s = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    c = _compile(lambda x, s: q8_kernel.quant_dequant(x, s, interpret=False),
                 x, s)
    assert _has_kernel(c)


def test_paged_decode_step_qwen2_fits_one_chip(one_chip, monkeypatch):
    """The continuous engine's one compiled step at qwen2-1.5b's
    published widths and depth: it takes the Pallas decode kernel and
    its arguments plus temporaries fit one v5e chip."""
    from repro.configs import get_config
    from repro.models import transformer as tf
    from repro.serve import continuous

    # the kernel wrapper picks interpret mode from the backend, which
    # here is the CPU; the compile is for the TPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = get_config("qwen2-1.5b")
    n_slots, page, max_seq = 8, 16, 288
    pps = -(-max_seq // page)
    n_pages = 1 + n_slots * pps

    def on_chip(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
            tree,
        )

    params = on_chip(jax.eval_shape(lambda: tf.init_params(jax.random.key(0), cfg)))
    cache = on_chip(jax.eval_shape(
        lambda: tf.init_paged_cache(cfg, n_pages, page, jnp.bfloat16)
    ))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    step = continuous._build_step(cfg, "pallas", 0.0, True)
    c = step.lower(
        params, i32(n_slots, 1), cache, i32(n_slots, pps), i32(n_slots),
        i32(n_slots),
    ).compile()
    assert _has_kernel(c)
    mem = c.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES
