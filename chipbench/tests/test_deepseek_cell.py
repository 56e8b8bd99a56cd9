"""A whole run of a tiny MLA + expert serving cell on the CPU, through
``serve_saturated`` and the ``deepseek-v3`` reference (the look for a
chip skipped): sound, it comes out correct; with a served token altered
where the compiled step produces it, it comes out not correct.

The tiny tree is the benchmark's drivers and readers with this test's
own configuration, traffic and cell files written beside them; the
configuration keeps DeepSeek-V3's keys and cuts every size."""

import contextlib
import io
import json
import shutil

import jax.numpy as jnp
import pytest

from chipbench import harness
from chipbench import run as run_mod
from chipbench.tests import tiny

CELL = "serve.ds-tiny.reasoning"
#: bf16 compute against the float32 reference: the widest gap over seeds
#: 2**33 + 17..20 on the CPU was 0.031; a token altered reads 0.5 and more
LIMIT = 0.1


def _config() -> dict:
    c = harness.config("deepseek-v3")
    c.update(name="ds-tiny", hidden_size=64, intermediate_size=96, num_attention_heads=4,
             num_key_value_heads=4, q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
             qk_rope_head_dim=8, v_head_dim=16, moe_intermediate_size=24, n_group=4,
             topk_group=2, n_routed_experts_published=16, n_routed_experts=4,
             experts_held=[0, 1, 2, 3], num_experts_per_tok=4, vocab_size=300,
             num_hidden_layers=3, param_dtype="float32")
    c["rope_scaling"] = dict(c["rope_scaling"], original_max_position_embeddings=16)
    return c


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tiny.make_root(tmp_path_factory.mktemp("ds"))
    shutil.copy(harness.ROOT / "configs" / "deepseek-v3.py", root / "configs" / "ds-tiny.py")
    files = {
        "configs/ds-tiny.json": _config(),
        "traffic/short-reasoning.json": {
            "prompt": {"dist": "lognormal", "median": 10, "sigma": 0.45, "min": 6, "max": 20},
            "output": {"dist": "lognormal", "median": 10, "sigma": 0.45, "min": 6, "max": 16},
            "requests": 3000, "backlog": 2, "check_requests": 2},
        f"workloads/{CELL}.json": {
            "config": "ds-tiny", "traffic": "short-reasoning", "driver": "serve_saturated",
            "chips": 1, "slots": 4, "page_size": 4, "max_seq": 36,
            "limits": {"max_logit_gap": LIMIT}},
    }
    for name, body in files.items():
        (root / name).write_text(json.dumps(body))
    return root


def _bench() -> dict:
    return {"workloads": [{"name": CELL, "config": "ds-tiny", "traffic": "short-reasoning",
                           "chips": 1}],
            "end_to_end": [{"name": "output_tokens_per_s", "unit": "tokens/s",
                            "workloads": [CELL]},
                           {"name": "setup_s", "unit": "s"}],
            "per_layer": []}


def _run(root, seed: int) -> dict:
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = run_mod.main(["--workload", CELL, "--seed", str(seed), "--seconds", "1",
                           "--trace", "0"], require_tpu=False, root=root, bench=_bench())
    assert rc == 0
    return json.loads(buf.getvalue().strip().splitlines()[-1])


@contextlib.contextmanager
def _fresh_programs():
    from repro.api.executor import clear_program_cache

    clear_program_cache()
    try:
        yield
    finally:
        clear_program_cache()


def test_sound_run_is_correct(root):
    with _fresh_programs():
        out = _run(root, 2**33 + 17)
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"output_tokens_per_s", "setup_s"}


def test_altered_token_is_not_correct(root, monkeypatch):
    from repro.serve import continuous

    build = continuous._build_step

    def broken(cfg, impl, temperature, donate):
        step = build(cfg, impl, temperature, donate)

        def wrapped(params, tokens, cache, block, length, seeds):
            nxt, *rest = step(params, tokens, cache, block, length, seeds)
            return (jnp.where(length % 2 == 1, (nxt + 1) % cfg.vocab_size, nxt), *rest)

        return wrapped

    monkeypatch.setattr(continuous, "_build_step", broken)
    with _fresh_programs():
        out = _run(root, 2**33 + 17)
    assert out["correct"] is False
    assert out["checks"]["max_logit_gap"]["value"] > LIMIT
