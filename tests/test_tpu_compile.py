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
import re
from pathlib import Path

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


def test_paged_decode_attention_at_qwen2_widths(one_chip):
    # the decode-heavy cell's arena: 32 slots of 144 pages of 16, bf16
    n_slots, Hq, Hkv, D, P, pps = 32, 12, 2, 128, 16, 144
    n_pages = 1 + n_slots * pps
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    arena = sds((n_pages, Hkv, P, D), jnp.bfloat16)
    c = _compile(
        lambda q, k, v, b, n: da_ops.paged_decode_attention(
            q, k, v, b, n, interpret=False),
        sds((n_slots, Hq, D), jnp.bfloat16), arena, arena,
        sds((n_slots, pps), jnp.int32), sds((n_slots,), jnp.int32),
    )
    assert _has_kernel(c)


def test_paged_mla_decode_at_deepseek_v3_widths(one_chip):
    # the reasoning cell's latent arena: 64 slots of 128 pages of 64
    # positions, rows of 576 values in 640 lanes, 128 query heads
    n_slots, H, W, R, P, pps = 64, 128, 640, 512, 64, 128
    n_pages = 1 + n_slots * pps
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    c = _compile(
        lambda q, kv, b, n: da_ops.paged_mla_decode(
            q, kv, b, n, value_width=R, scale=0.1, interpret=False),
        sds((n_slots, H, W), jnp.float32), sds((n_pages, P, W), jnp.bfloat16),
        sds((n_slots, pps), jnp.int32), sds((n_slots,), jnp.int32),
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


def test_flash_attention_at_deepseek_v3_prefill_widths(one_chip):
    # MLA's serving prefill at the reasoning cell's largest bucket: 128
    # heads, 192-wide keys ([q_nope ‖ q_rope]), 128-wide values, 512 blocks
    B, T, H = 1, 4096, 128
    qk = jax.ShapeDtypeStruct((B, T, H, 192), jnp.bfloat16, sharding=one_chip)
    v = jax.ShapeDtypeStruct((B, T, H, 128), jnp.bfloat16, sharding=one_chip)
    c = _compile(
        lambda q, k, v: fa_ops.flash_attention(q, k, v, causal=True, scale=0.1, bq=512,
                                               bk=512, interpret=False),
        qk, qk, v,
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


def _roofline_kernel_pattern() -> str:
    """The kernel-name pattern of the benchmark's decode-kernel reader."""
    src = (Path(__file__).resolve().parents[1] / "chipbench" / "metrics"
           / "decode_attention_roofline.py").read_text()
    return re.search(r'^KERNEL = r"(.+)"$', src, re.M).group(1)


def _gathers_from(hlo: str, dim: int) -> tuple:
    """(every gather, the gathers whose operand has a ``dim``-long axis)
    of an optimized HLO module, over all its computations."""
    dims = {}
    for m in re.finditer(r"^\s*(?:ROOT )?%(\S+) = \w+\[([\d,]*)\]", hlo, re.M):
        dims[m.group(1)] = [int(d) for d in m.group(2).split(",") if d]
    gathers = re.findall(r"^\s*(?:ROOT )?%(\S+) = \S+ gather\(%([^,)]+)", hlo, re.M)
    return gathers, [g for g, operand in gathers if dim in dims[operand]]


def test_paged_decode_step_qwen2_fits_one_chip(one_chip, monkeypatch):
    """The continuous engine's one compiled step at qwen2-1.5b's
    published widths and depth: it takes the paged Pallas decode kernel,
    under a name the benchmark's roofline reader matches; no gather
    reads the page arena; and its arguments plus temporaries fit one
    v5e chip."""
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
    hlo = c.as_text()
    kernels = [m.group(1) for m in re.finditer(
        r"^\s*%(\S+) = .*custom_call_target=\"tpu_custom_call\"", hlo, re.M)]
    assert kernels and all(k.startswith("paged_decode_attention") for k in kernels)
    assert all(re.search(_roofline_kernel_pattern(), k) for k in kernels)
    gathers, from_arena = _gathers_from(hlo, n_pages)
    assert gathers  # the embedding row and the block-table lookup
    assert not from_arena
    mem = c.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES


def _reader_pattern(metric: str) -> str:
    src = (Path(__file__).resolve().parents[1] / "chipbench" / "metrics"
           / f"{metric}.py").read_text()
    return re.search(r'^KERNEL = r"(.+)"$', src, re.M).group(1)


def test_paged_decode_step_deepseek_v3_fits_one_chip(one_chip, monkeypatch):
    """The engine's compiled step for the DeepSeek-V3 cell's cut (5
    layers, 8 of 256 experts held, vocabulary 16,160, bf16) at the cell's
    arena (64 slots, 8,192 positions, page 64): MLA decodes through the
    paged MLA kernel, under the name the benchmark's reader matches and
    the GQA reader's pattern would also match only by that name; the held
    experts run as ragged dots; arguments plus temporaries fit one v5e
    chip."""
    from chipbench import harness
    from repro.models import transformer as tf
    from repro.serve import continuous

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    ref = harness.config_module("deepseek-v3")
    cfg = ref.program_config(harness.config("deepseek-v3"))
    n_slots, page, max_seq = 64, 64, 8192
    pps = max_seq // page
    n_pages = 1 + n_slots * pps

    def on_chip(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(lambda: tf.init_params(jax.random.key(0), cfg)))
    cache = on_chip(jax.eval_shape(
        lambda: tf.init_paged_cache(cfg, n_pages, page, jnp.bfloat16)))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    step = continuous._build_step(cfg, "pallas", 0.0, True)
    c = step.lower(params, i32(n_slots, 1), cache, i32(n_slots, pps), i32(n_slots),
                   i32(n_slots)).compile()
    hlo = c.as_text()
    kernels = {m.group(1) for m in re.finditer(
        r"^\s*%(\S+) = .*custom_call_target=\"tpu_custom_call\"", hlo, re.M)}
    mla_kernels = {k for k in kernels if re.search(_reader_pattern("mla_decode_roofline"), k)}
    assert len(mla_kernels) == 2  # one in each segment's layer scan
    assert {k for k in kernels if k.startswith("ragged-dot-none")}
    assert not any(k.startswith("paged_decode_attention") for k in kernels)
    mem = c.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES
