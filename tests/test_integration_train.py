"""End-to-end integration: the real launchers on reduced configs (CPU)."""

import jax
import jax.numpy as jnp
import pytest

from repro.launch import serve as serve_mod
from repro.launch import train as train_mod


def test_train_loss_decreases():
    hist = train_mod.main(
        [
            "--arch", "tinyllama-1.1b", "--reduced", "--steps", "60",
            "--batch", "8", "--seq", "64", "--log-every", "20", "--lr", "1e-3",
        ]
    )["history"]
    assert hist[-1]["loss"] < hist[0]["loss"] - 0.1


def test_train_with_staleness_and_compression():
    hist = train_mod.main(
        [
            "--arch", "qwen2-1.5b", "--reduced", "--steps", "40",
            "--batch", "4", "--seq", "32", "--log-every", "20",
            "--staleness", "2", "--compress-topk", "0.2", "--lr", "1e-3",
        ]
    )["history"]
    assert all(jnp.isfinite(jnp.asarray(h["loss"])) for h in hist)
    assert hist[-1]["loss"] < hist[0]["loss"] + 0.5  # no divergence


def test_train_checkpointing(tmp_path):
    from repro.checkpoint import latest_step

    train_mod.main(
        [
            "--arch", "xlstm-125m", "--reduced", "--steps", "10",
            "--batch", "2", "--seq", "16", "--log-every", "5",
            "--ckpt-dir", str(tmp_path), "--ckpt-every", "5",
        ]
    )
    assert latest_step(str(tmp_path)) == 10


def test_serve_generates():
    out = serve_mod.main(
        [
            "--arch", "qwen2-1.5b", "--reduced", "--batch", "2",
            "--prompt-len", "8", "--gen", "4",
        ]
    )
    assert out.shape == (2, 4)
    assert bool(jnp.all((out >= 0) & (out < 512)))


def test_serve_greedy_deterministic():
    a = serve_mod.main(
        ["--arch", "tinyllama-1.1b", "--reduced", "--batch", "1",
         "--prompt-len", "6", "--gen", "3"]
    )
    b = serve_mod.main(
        ["--arch", "tinyllama-1.1b", "--reduced", "--batch", "1",
         "--prompt-len", "6", "--gen", "3"]
    )
    assert jnp.array_equal(a, b)
