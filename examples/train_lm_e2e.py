"""End-to-end driver: train a ~100M-class LM for a few hundred steps with
the paper's techniques on (staleness + compressed push), checkpoint, then
serve it with a batched decode loop.

Default is a CPU-friendly ~10M variant (a couple of minutes); pass --full
for the ~100M-parameter configuration (hours on CPU, minutes on a real
accelerator — same code path).

  PYTHONPATH=src python examples/train_lm_e2e.py [--full] [--steps 300]
"""

import argparse
import tempfile

import jax
import jax.numpy as jnp

from repro.checkpoint import latest_step, restore, save
from repro.configs import get_config
from repro.launch.serve import prefill_and_decode
from repro.launch.train import main as train_main
from repro.models import transformer as tf


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="~100M params")
    ap.add_argument("--steps", type=int, default=300)
    args = ap.parse_args()

    arch = "tinyllama-1.1b"
    if args.full:
        # ~100M-parameter family member: 12 layers, d_model 768
        cfg = get_config(arch).replace(
            num_layers=12, d_model=768, num_heads=12, num_kv_heads=4,
            head_dim=64, d_ff=2048, vocab_size=8192,
            param_dtype="float32", compute_dtype="float32",
        )
        seq, batch = 512, 8
    else:
        cfg = get_config(arch).reduced()
        seq, batch = 128, 8

    n_params = sum(
        x.size for x in jax.tree.leaves(tf.init_params(jax.random.key(0), cfg))
    )
    print(f"model: {n_params/1e6:.1f}M params, seq {seq}, batch {batch}")

    with tempfile.TemporaryDirectory() as ckpt:
        # --- train with the paper's §5 features on
        hist = train_main(
            [
                "--arch", arch, *([] if args.full else ["--reduced"]),
                "--steps", str(args.steps), "--batch", str(batch),
                "--seq", str(seq), "--lr", "1e-3",
                "--staleness", "1",            # the paper's θ_{t-1} handoff
                "--compress-topk", "0.25",     # low-communication push
                "--log-every", str(max(args.steps // 10, 1)),
                "--ckpt-dir", ckpt, "--ckpt-every", str(args.steps // 2),
            ]
        )["history"]
        assert hist[-1]["loss"] < hist[0]["loss"], "training must improve"

        # --- restore the final checkpoint and serve it
        step = latest_step(ckpt)
        print(f"\nrestoring checkpoint step {step} and serving:")
        cfg_srv = cfg
        params = tf.init_params(jax.random.key(0), cfg_srv)
        params = restore(ckpt, step, params)
        prompts = jax.random.randint(jax.random.key(9), (4, 16), 0, cfg_srv.vocab_size)
        out = prefill_and_decode(
            cfg_srv, params, prompts, gen=24, cache_len=48
        )
        print("generated:", out[0].tolist())
        print("e2e OK: trained → checkpointed → restored → served")


if __name__ == "__main__":
    main()
