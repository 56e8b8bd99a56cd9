"""Pallas TPU kernels for the framework's compute hot spots.

Each kernel package ships ``kernel.py`` (pl.pallas_call + BlockSpec VMEM
tiling), ``ops.py`` (jit'd public wrapper, interpret-mode off-TPU) and
``ref.py`` (pure-jnp oracle used by the allclose test sweeps).

* ``flash_attention``  — train/prefill attention (GQA, causal, windows;
  MLA's serving prefill on TPU)
* ``decode_attention`` — 1-token decode vs long KV cache (flash-decode)
* ``topk_compress``    — gradient top-k for the low-comm push path (§5)
* ``int8_quant``       — symmetric int8 wire quantization, fused round-trip
* ``pdist_argmin``     — k-means / k-windows E-step (ℓ1/ℓ2/ℓ∞)
"""
