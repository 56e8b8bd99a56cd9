"""Serving launcher — a thin CLI over the ``repro.serve`` subsystem.

Two paths, matching the two model families the repo trains:

* ``--arch`` (LM decode): batched prefill + decode through a
  ``ServeEngine``/``MicroBatcher`` pair, with per-request bytes metered
  on the engine's ``CommLedger``.  Attention architectures prefill the
  whole prompt in ONE call (the KV cache append supports T ≥ 1 tokens);
  recurrent mixers (mamba/xLSTM and hybrids) keep the token-by-token
  loop their single-step caches require.
* ``--strategy`` (classical fits): train a small ``api.fit``, publish it
  to a ``ModelRegistry``, load it back, and serve a query batch — the
  fit → publish → serve round trip on one command line.

Examples:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --reduced \
      --batch 4 --prompt-len 32 --gen 16
  PYTHONPATH=src python -m repro.launch.serve --strategy gd \
      --registry /tmp/registry --requests 12
"""

from __future__ import annotations

import argparse
import json
import tempfile
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.models import transformer as tf
from repro.utils.compile_cache import enable_compile_cache


# ----------------------------------------------------------------------------
# Prefill + decode (reused by OptimizerStrategy.predict_fn closures)
# ----------------------------------------------------------------------------

def batched_prefill_supported(cfg) -> bool:
    """True when every layer's mixer can append the whole prompt in one
    decode call (the capability is declared by the model layer:
    ``transformer.MULTI_TOKEN_MIXERS``)."""
    return all(
        spec.mixer in tf.MULTI_TOKEN_MIXERS for spec in tf.layer_specs(cfg)
    )


def _decode_fn(params, cfg, tokens, cache):
    return tf.decode_step(params, cfg, tokens, cache)


def _prefill_fn(params, cfg, tokens, cache):
    B, P = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(P), (B, P))
    return tf.decode_step(params, cfg, tokens, cache, positions=positions)


# the pre-call cache is dead after every decode step — donating it lets
# XLA update the KV buffers in place instead of copying the whole cache
# per generated token.  CPU ignores donation (and warns), so both
# variants exist and the caller picks by backend at runtime.
_decode = partial(jax.jit, static_argnames=("cfg",))(_decode_fn)
_decode_donated = partial(
    jax.jit, static_argnames=("cfg",), donate_argnames=("cache",)
)(_decode_fn)
_prefill_batched = partial(jax.jit, static_argnames=("cfg",))(_prefill_fn)
_prefill_donated = partial(
    jax.jit, static_argnames=("cfg",), donate_argnames=("cache",)
)(_prefill_fn)


def prefill_and_decode(cfg, params, prompts, *, gen: int, cache_len: int,
                       temperature: float = 0.0, seed: int = 0,
                       prefill: str = "auto"):
    """prompts: (B, P) int32 → returns (B, gen) generated ids.

    ``prefill``: "batched" (one call over the whole prompt — attention
    archs only), "loop" (token by token — every mixer family), or "auto".
    """
    B, P = prompts.shape
    cache = tf.init_cache(cfg, B, cache_len, jnp.float32)
    donate = jax.default_backend() != "cpu"
    decode = _decode_donated if donate else _decode
    prefill_step = _prefill_donated if donate else _prefill_batched

    if prefill == "auto":
        prefill = "batched" if batched_prefill_supported(cfg) else "loop"
    if prefill == "batched":
        if not batched_prefill_supported(cfg):
            raise ValueError(
                f"{cfg.name} has recurrent mixers — batched prefill needs "
                "an attention/MLA-only stack; use prefill='loop'"
            )
        logits, cache = prefill_step(params, cfg, prompts, cache)
    elif prefill == "loop":
        logits = None
        for t in range(P):
            logits, cache = decode(params, cfg, prompts[:, t : t + 1], cache)
    else:
        raise ValueError(f"unknown prefill mode {prefill!r}")

    outs = []
    key = jax.random.key(seed)
    for g in range(gen):
        lg = logits[:, -1, : cfg.vocab_size]
        if temperature > 0:
            # per-row keys: a row's sample depends only on its index, so
            # batch padding (always appended at the end) cannot change a
            # real request's tokens — the batcher's padding contract
            key, k = jax.random.split(key)
            row_keys = jax.vmap(lambda i: jax.random.fold_in(k, i))(
                jnp.arange(B)
            )
            tok = jax.vmap(jax.random.categorical)(
                row_keys, lg / temperature
            )[:, None]
        else:
            tok = jnp.argmax(lg, axis=-1)[:, None]
        outs.append(tok[:, 0])
        logits, cache = decode(params, cfg, tok.astype(jnp.int32), cache)
    return jnp.stack(outs, axis=1)


def lm_predict_fn(cfg, *, gen: int, temperature: float = 0.0, seed: int = 0):
    """The ``OptimizerStrategy.predict_fn`` closure for LM serving:
    prompts in, generated ids out, cache sized per prompt length."""

    def predict(params, prompts):
        P = prompts.shape[1]
        return prefill_and_decode(
            cfg, params, prompts, gen=gen, cache_len=P + gen + 1,
            temperature=temperature, seed=seed,
        )

    return predict


# ----------------------------------------------------------------------------
# CLI
# ----------------------------------------------------------------------------

def _serve_continuous(args):
    """Continuous-batching LM serving: requests join and retire
    independently over a paged KV cache (see docs/SERVING.md)."""
    from repro.serve import ContinuousLMEngine
    from repro.telemetry.report import RunReport
    from repro.telemetry.trace import Tracer

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    params = tf.init_params(jax.random.key(args.seed), cfg)
    tracer = Tracer()
    engine = ContinuousLMEngine(
        cfg, params, n_slots=args.batch, page_size=args.page_size,
        max_seq=args.prompt_len + args.gen,
        temperature=args.temperature, seed=args.seed,
        tracer=tracer, tag=f"serve/{cfg.name}",
    )
    rng = np.random.default_rng(args.seed + 1)
    prompts = rng.integers(
        0, cfg.vocab_size, size=(args.requests, args.prompt_len)
    ).astype(np.int32)
    print(f"continuous serving {cfg.name} (slots={args.batch}, "
          f"page_size={args.page_size}, plan={engine.kernel_plan})")
    tickets = [engine.submit(p, max_new=args.gen) for p in prompts]
    engine.run_until_idle()
    outs = np.stack([t.result() for t in tickets])
    print(json.dumps({k: round(v, 4) if isinstance(v, float) else v
                      for k, v in engine.stats().items()}))
    print(RunReport.from_serve(engine).to_markdown())
    print("sample:", outs[0].tolist())
    return outs


def _serve_arch(args):
    from repro.api.strategy import OptimizerStrategy
    from repro.serve import MicroBatcher, ServeEngine

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if cfg.is_encoder_decoder:
        raise SystemExit("enc-dec serving: see examples/whisper_serve.py")

    params = tf.init_params(jax.random.key(args.seed), cfg)
    strategy = OptimizerStrategy(
        None, None,
        predict_fn=lm_predict_fn(
            cfg, gen=args.gen, temperature=args.temperature, seed=args.seed
        ),
    )
    mesh = _make_mesh(args)
    engine = ServeEngine(strategy, params, mesh=mesh, tag=f"serve/{cfg.name}")
    batcher = MicroBatcher(
        engine, max_batch=args.batch, timeout_s=args.timeout_ms / 1e3
    )
    prompts = jax.random.randint(
        jax.random.key(args.seed + 1),
        (args.requests, args.prompt_len),
        0,
        cfg.vocab_size,
    )
    mode = "batched" if batched_prefill_supported(cfg) else "loop"
    print(f"serving {cfg.name} ({mode} prefill, "
          f"buckets={batcher.buckets}, mesh={bool(mesh)})")
    tickets = [batcher.submit(np.asarray(p)) for p in prompts]
    _drain(batcher)
    outs = jnp.stack([t.result() for t in tickets])
    stats = engine.stats()
    print(json.dumps({k: round(v, 4) if isinstance(v, float) else v
                      for k, v in stats.items()}))
    print("sample:", np.asarray(outs[0]).tolist())
    return outs


def _serve_strategy(args):
    from repro import api
    from repro.ml.linear import lsq_loss
    from repro.serve import MicroBatcher, ModelRegistry, ServeEngine

    rng = np.random.default_rng(args.seed)
    registry = ModelRegistry(
        args.registry or tempfile.mkdtemp(prefix="registry-")
    )
    mesh = _make_mesh(args)

    if args.strategy == "gd":
        K, Nk, n = 8, 32, 16
        X = jnp.asarray(rng.normal(size=(K, Nk, n)))
        w = jnp.asarray(rng.normal(size=(n,)))
        y = jnp.einsum("kni,i->kn", X, w)
        strategy = api.GradientDescent(lsq_loss, lr=0.1)
        res = api.fit(strategy, (X, y), transport="allreduce", steps=200)
        like = None
    elif args.strategy == "kwindows":
        from repro.core.schedules import round_robin
        from repro.ml.kwindows import KWindowsStrategy

        K, Nk, d = 4, 64, 2
        centers = rng.normal(size=(3, d)) * 4.0
        Xs = jnp.asarray(
            centers[rng.integers(0, 3, size=(K, Nk))]
            + rng.normal(size=(K, Nk, d)) * 0.3
        )
        strategy = KWindowsStrategy(
            jax.random.key(args.seed), num_windows=6, r=1.0
        )
        res = api.fit(strategy, Xs, transport="sequential_server",
                      schedule=round_robin(K, 1))
        like = res.theta
    else:
        raise SystemExit(f"unknown --strategy {args.strategy!r}")

    version = registry.publish(args.strategy, res.theta,
                               meta={"transport": res.metrics["transport"]})
    engine = ServeEngine.from_registry(
        registry, args.strategy, strategy, like=like, mesh=mesh,
        tag=f"serve/{args.strategy}",
    )
    batcher = MicroBatcher(engine, max_batch=args.batch,
                           timeout_s=args.timeout_ms / 1e3)
    if args.strategy == "gd":
        dim = engine.theta.shape[0]
        queries = rng.normal(size=(args.requests, dim))
    else:
        # query near the true clusters so assignments are observable
        # (far-off points are correctly -1 / uncaptured)
        queries = (
            centers[rng.integers(0, len(centers), size=args.requests)]
            + rng.normal(size=(args.requests, centers.shape[1])) * 0.3
        )
    tickets = [
        batcher.submit(q.astype(np.float32)) for q in queries
    ]
    _drain(batcher)
    preds = [np.asarray(t.result()) for t in tickets]
    print(f"published {args.strategy} v{version} -> {registry.root}")
    print(json.dumps(engine.stats()))
    print("predictions:", np.asarray(preds)[: min(8, len(preds))].round(3).tolist())
    return preds


def _drain(batcher) -> None:
    """Serve the queue the way a real loop would: full buckets flushed on
    arrival (submit), the ragged tail by timeout — so ``--timeout-ms``
    is an observable latency bound, not just a constructor argument."""
    while batcher.pending():
        if not batcher.poll():
            time.sleep(batcher.timeout_s / 4)


def _make_mesh(args):
    if not args.mesh:
        return None
    from repro.launch.mesh import make_node_mesh

    return make_node_mesh()


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="")
    ap.add_argument("--strategy", default="",
                    help="serve a classical fit instead: gd | kwindows")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4,
                    help="largest microbatch bucket")
    ap.add_argument("--requests", type=int, default=0,
                    help="number of synthetic requests (default: --batch)")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--continuous", action="store_true",
                    help="continuous batching: slot-scheduled decode over "
                         "a paged KV cache (--batch = n_slots)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page (continuous path)")
    ap.add_argument("--timeout-ms", type=float, default=10.0)
    ap.add_argument("--registry", default="",
                    help="model registry root (strategy path)")
    ap.add_argument("--mesh", action="store_true",
                    help="place the engine on a mesh over all local devices")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    if not args.requests:
        args.requests = args.batch
    if args.strategy:
        return _serve_strategy(args)
    if not args.arch:
        args.arch = "qwen2-1.5b"
    if args.continuous:
        return _serve_continuous(args)
    return _serve_arch(args)


if __name__ == "__main__":
    main()
