"""DeepSeek-V3's layers on the serving path, against the plain reference
(``chipbench/configs/deepseek-v3.py``) at a small size, with seeded
weights, in float32 on the CPU.

Every width is cut; the mechanisms are the published ones: MLA with YaRN
rope, the paged latent arena and its kernel (interpret mode), the grouped
sigmoid routing over all routed experts, and an expert layer that holds a
share of them.  Unless a case says otherwise, the program and the
reference both compute in float32, so the tolerances below bound f32
summation-order differences, not a precision gap.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness
from repro.api.executor import clear_program_cache
from repro.kernels.decode_attention import ops as da_ops
from repro.models import cache as cache_lib, mla, moe, transformer as tf
from repro.models.layers import yarn_frequencies, yarn_mscale
from repro.serve import ContinuousLMEngine

ref = harness.config_module("deepseek-v3")

#: f32 program against the f32 reference over a 3-layer stack: logits of
#: O(1) agree to ~1e-6; 1e-4 leaves room for summation order on any CPU
LOGIT_TOL = 1e-4


def small_file(**kw) -> dict:
    c = harness.config("deepseek-v3")
    c.update(hidden_size=64, intermediate_size=96, num_attention_heads=4, num_key_value_heads=4,
             q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
             v_head_dim=16, moe_intermediate_size=24, n_group=4, topk_group=2,
             n_routed_experts_published=16, n_routed_experts=4, experts_held=[4, 5, 6, 7],
             num_experts_per_tok=4, vocab_size=300, num_hidden_layers=3,
             param_dtype="float32", compute_dtype="float32", kv_cache_dtype="float32")
    # a context of 16 puts the YaRN ramp inside the small rope width
    c["rope_scaling"] = dict(c["rope_scaling"], original_max_position_embeddings=16)
    c.update(kw)
    return c


@pytest.fixture(scope="module")
def model():
    c = small_file()
    cfg = ref.program_config(c)
    shapes = jax.eval_shape(lambda k: tf.init_params(k, cfg), jax.random.key(0))
    return c, cfg, ref.make_params(shapes, jax.random.key(1))


def _serve_recording(cfg, params, requests, *, use_kernel):
    """Serve ``requests`` [(prompt, max_new)] through the engine with 3
    slots, so that requests join and retire while others decode; return
    each request's served tokens and the logits the engine produced them
    from, position by position (prefill's last row, then each decode
    step's row for the request's slot)."""
    rows = []  # every decode step's logits, (n_slots, V)
    prefill = []  # every join's prefill logits, (bucket, V)
    paged_step, decode_step = tf.paged_decode_step, tf.decode_step

    def paged(*a, **kw):
        out = paged_step(*a, **kw)
        jax.debug.callback(lambda lg: rows.append(np.asarray(lg)), out[0][:, 0], ordered=True)
        return out

    def dense(*a, **kw):
        out = decode_step(*a, **kw)
        jax.debug.callback(lambda lg: prefill.append(np.asarray(lg)), out[0][0], ordered=True)
        return out

    clear_program_cache()
    mp = pytest.MonkeyPatch()
    mp.setattr(tf, "paged_decode_step", paged)
    mp.setattr(tf, "decode_step", dense)
    try:
        eng = ContinuousLMEngine(cfg, params, n_slots=3, page_size=4, max_seq=48,
                                 use_kernel=use_kernel)
        slot_of, first_row = {}, {}
        admit = eng.sched.admit

        def record_admit(req):
            slot = admit(req)
            if slot is not None:
                slot_of[req.rid] = slot
                first_row[req.rid] = (len(rows), len(prefill))
            return slot

        eng.sched.admit = record_admit
        tickets = [eng.submit(p, max_new=n) for p, n in requests]
        eng.run_until_idle()
    finally:
        mp.undo()
        clear_program_cache()
    out = []
    for rid, t in enumerate(tickets):
        served = t.result()
        step0, pre = first_row[rid]
        # the join's prefill row, then one decode row per later token, in
        # the steps after the join, from the request's own slot
        got = [prefill[pre][len(requests[rid][0]) - 1]]
        got += [rows[step0 + j][slot_of[rid]] for j in range(len(served) - 1)]
        out.append((served, np.stack(got)[:, : cfg.vocab_size]))
    return out


@pytest.mark.parametrize("use_kernel", [False, True], ids=["xla", "pallas_interpret"])
def test_engine_matches_reference_forward(model, use_kernel):
    """Prefill plus paged decode through ``ContinuousLMEngine``, with
    slots joining and retiring, gives at every served position the
    logits of the reference's full forward over prompt + served tokens."""
    c, cfg, params = model
    g = np.random.default_rng(0)
    requests = [(g.integers(0, 300, n).astype(np.int32), m)
                for n, m in [(9, 12), (5, 20), (13, 7), (3, 30), (7, 9)]]
    for (prompt, _), (served, got) in zip(
            requests, _serve_recording(cfg, params, requests, use_kernel=use_kernel)):
        seq = np.concatenate([prompt, served[:-1]])
        want = ref.logits(c, params, seq)[len(prompt) - 1:]
        assert got.shape == want.shape
        np.testing.assert_allclose(got, want, atol=LOGIT_TOL, rtol=0)
        np.testing.assert_array_equal(served, np.argmax(want, axis=-1))


def test_absorbed_paged_matches_dense_unabsorbed(model):
    """The absorbed decode over the paged latent arena (one token per slot,
    pages shuffled) gives the logits of the paper's form over a dense
    latent cache."""
    _, cfg, params = model
    B, T, P = 3, 11, 4
    g = np.random.default_rng(1)
    toks = jnp.asarray(g.integers(0, 300, (B, T)), jnp.int32)
    pps = -(-T // P)
    block = jnp.asarray(1 + g.permutation(B * pps).reshape(B, pps), jnp.int32)
    paged = tf.init_paged_cache(cfg, 1 + B * pps, P, jnp.float32)
    dense = tf.init_cache(cfg, B, T, jnp.float32)
    for t in range(T):
        want, dense = tf.decode_step(params, cfg, toks[:, t:t + 1], dense)
        got, paged, _ = tf.paged_decode_step(params, cfg, toks[:, t:t + 1], paged, block,
                                             jnp.full((B,), t, jnp.int32))
        # one position's logits, f32 both ways: the absorbed form only
        # reorders the products of W_uk and W_uv
        np.testing.assert_allclose(got, want, atol=LOGIT_TOL, rtol=0)


@pytest.mark.parametrize("dtype, tol", [
    (jnp.float32, 1e-5),  # f32 in, f32 sums: summation order only
    # bf16 arena: the kernel rounds the softmax weights to bf16 for the
    # value product (8 bits of mantissa, 2^-9 relative on values of O(1))
    (jnp.bfloat16, 2e-2),
], ids=["f32", "bf16"])
def test_paged_mla_kernel_matches_xla(dtype, tol):
    """The paged MLA kernel (interpret mode) against its XLA path over the
    gathered view: pages shuffled, lengths not multiples of the page,
    chunks of 2 pages so that a slot spans several."""
    g = np.random.default_rng(2)
    n_slots, H, r, dr, P, pps = 4, 8, 64, 16, 8, 6
    W = cache_lib.latent_width(r, dr)
    n_pages = 1 + n_slots * pps
    arena = cache_lib.PagedMLACache(jnp.asarray(g.normal(size=(n_pages, P, W)), dtype))
    block = jnp.asarray(1 + g.permutation(n_slots * pps).reshape(n_slots, pps), jnp.int32)
    valid = jnp.asarray([1, 7, 19, 45], jnp.int32)
    q = jnp.asarray(g.normal(size=(n_slots, H, W)), jnp.float32)
    got = da_ops.paged_mla_decode(q, arena.kv, block, valid, value_width=r, scale=0.2,
                                  pages_per_chunk=2)
    want = da_ops.paged_mla_decode_xla(q.astype(dtype), cache_lib.paged_mla_view(arena, block),
                                       valid, value_width=r, scale=0.2)
    np.testing.assert_allclose(got, want, atol=tol, rtol=0)


@pytest.mark.parametrize("dtype, tol", [
    (jnp.float32, 1e-5),  # f32 in, f32 sums: summation order only
    # bf16 operands: the kernel's online softmax rounds its weights to
    # bf16 for the value product, the XLA path rounds the normalised ones
    (jnp.bfloat16, 2e-2),
], ids=["f32", "bf16"])
def test_prefill_flash_kernel_matches_chunked_attention(model, dtype, tol):
    """The serving prefill's flash-kernel path of MLA (interpret mode:
    192-wide keys as [q_nope ‖ q_rope] · [k_nope ‖ k_pe], 128-wide values,
    the YaRN softmax scale) against the chunked XLA path, causal over a
    prompt that fills the cache, in blocks smaller than the prompt."""
    _, cfg, params = model
    p = jax.tree.map(lambda a: a[0].astype(dtype), params["seg0"]["l0"]["mixer"])
    m, H, T = cfg.mla, cfg.num_heads, 24
    g = np.random.default_rng(4)
    normal = lambda *shape: jnp.asarray(g.normal(size=shape), dtype)
    q_nope, q_rope = normal(1, T, H, m.qk_nope_head_dim), normal(1, T, H, m.qk_rope_head_dim)
    c_kv, k_pe = normal(1, T, m.kv_lora_rank), normal(1, T, m.qk_rope_head_dim)
    want = mla._attend(p, cfg, q_nope, q_rope, c_kv, k_pe, jnp.arange(T))
    mp = pytest.MonkeyPatch()
    mp.setattr(mla, "_FLASH_BLOCK", 8)
    try:
        got = mla._attend(p, cfg, q_nope, q_rope, c_kv, k_pe, jnp.arange(T), use_kernel=True)
    finally:
        mp.undo()
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                               atol=tol, rtol=0)


def test_routing_picks_reference_experts(model):
    """The grouped sigmoid routing chooses the reference's experts and
    gates; scores are drawn so that no two of a token's biased scores tie
    (f32 gaps of at least 1e-4)."""
    c, cfg, params = model
    g = np.random.default_rng(3)
    pr = params["seg1"]["l0"]["ffn"]["router"]
    pr = jax.tree.map(lambda a: a[0], pr)
    x = jnp.asarray(g.normal(size=(256, cfg.d_model)), jnp.float32)
    s = np.asarray(jax.nn.sigmoid(x @ pr["kernel"])) + np.asarray(pr["select_bias"])
    x = x[np.diff(np.sort(s, axis=-1), axis=-1).min(axis=-1) > 1e-4]
    assert len(x) >= 64
    gates, ids, _ = moe.route(pr, cfg, x)
    want_gates, want_ids = ref.routing(c, x, pr["kernel"], pr["select_bias"])
    np.testing.assert_array_equal(np.sort(ids, -1), np.sort(want_ids, -1))
    order = lambda i, w: np.take_along_axis(np.asarray(w), np.argsort(np.asarray(i), -1), -1)
    np.testing.assert_allclose(order(ids, gates), order(want_ids, want_gates), rtol=1e-6)
    # every chosen expert lies in one of the topk_group best groups
    groups = np.asarray(ids) // (16 // 4)
    assert all(len(set(row)) <= 2 for row in groups)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_route_fragile_flags_every_reroute(model, seed):
    """Moving each biased score by less than the slack (20 random draws
    for each of 512 tokens) changes which held experts the reference
    chooses only for tokens that ``route_fragile`` flags; the move does
    change some, and the rule flags a small share of the tokens."""
    c, cfg, params = model
    g = np.random.default_rng(10 + seed)
    pr = jax.tree.map(lambda a: a[0], params["seg1"]["l0"]["ffn"]["router"])
    x = jnp.asarray(g.normal(size=(512, cfg.d_model)), jnp.float32)
    held = np.asarray(c["experts_held"])
    slack = 0.005

    def held_choice(bias):
        _, ids = ref.routing(c, x, pr["kernel"], bias)
        return np.stack([np.isin(held, row) for row in np.asarray(ids)])

    fragile = np.asarray(ref.route_fragile(c, x, pr["kernel"], pr["select_bias"], slack))
    base = held_choice(pr["select_bias"])
    changed = np.zeros(len(x), bool)
    for _ in range(20):
        u = g.uniform(-1.0, 1.0, size=(len(x), 16)).astype(np.float32)
        changed |= np.any(held_choice(pr["select_bias"][None, :] + 0.999 * slack * u) != base,
                          axis=-1)
    assert changed.any()
    assert not np.any(changed & ~fragile)
    assert fragile.mean() < 0.25


@pytest.mark.parametrize("factor, fast, slow, ctx, d", [
    (40.0, 32.0, 1.0, 4096, 64),  # DeepSeek-V3's rope_scaling
    (40.0, 32.0, 1.0, 16, 8),  # the small model's: the ramp lands inside 4 pairs
    (4.0, 32.0, 1.0, 1024, 32),
])
def test_yarn_matches_closed_form(factor, fast, slow, ctx, d):
    """YaRN frequencies: base θ^(-2i/d) below the fast correction
    dimension, base / factor above the slow one, a linear ramp between;
    the softmax's mscale is 0.1·ln(factor) + 1.  Both as written in the
    YaRN paper (arXiv:2309.00071), in float64."""
    theta = 10000.0
    corr = lambda rot: d * math.log(ctx / (rot * 2 * math.pi)) / (2 * math.log(theta))
    lo, hi = max(math.floor(corr(fast)), 0), min(math.ceil(corr(slow)), d - 1)
    want = []
    for i in range(d // 2):
        base = theta ** (-2 * i / d)
        ramp = min(max((i - lo) / max(hi - lo, 1e-3), 0.0), 1.0)
        want.append(base * (1 - ramp) + base / factor * ramp)
    got = yarn_frequencies(d, theta, factor, fast, slow, ctx)
    np.testing.assert_allclose(got, want, rtol=1e-6)  # f32 of an f64 closed form
    np.testing.assert_allclose(ref.yarn_inv_freq({
        "qk_rope_head_dim": d, "rope_theta": theta,
        "rope_scaling": {"factor": factor, "beta_fast": fast, "beta_slow": slow,
                         "original_max_position_embeddings": ctx}}), want, rtol=1e-6)
    assert yarn_mscale(factor, 1.0) == pytest.approx(0.1 * math.log(factor) + 1.0, rel=1e-12)
    if (factor, d) == (40.0, 64):
        cfg = ref.program_config(harness.config("deepseek-v3"))
        assert mla.softmax_scale(cfg) == pytest.approx(
            192 ** -0.5 * (0.1 * math.log(40) + 1) ** 2, rel=1e-12)


@pytest.mark.parametrize("shares", [4, 2], ids=["4-shares", "2-shares"])
def test_expert_shares_add_up_to_the_uncut_layer(shares):
    """With the routed experts divided into ``shares`` shares, the held
    parts of every share, plus the shared expert counted once, add up to
    the reference's uncut layer (every routed expert held)."""
    c = small_file()
    E = c["n_routed_experts_published"]
    all_file = dict(c, experts_held=list(range(E)), n_routed_experts=E)
    cfg_all = ref.program_config(all_file)
    p_all = moe.moe_init(jax.random.key(4), cfg_all)
    p_all["router"]["select_bias"] = 0.01 * jax.random.normal(jax.random.key(5), (E,))
    x = jax.random.normal(jax.random.key(6), (2, 9, cfg_all.d_model))
    want = ref._experts(all_file, False, x.reshape(-1, cfg_all.d_model), p_all)
    n = E // shares
    total = jnp.zeros_like(x)
    held_total = jnp.zeros(x.shape[:2], jnp.int32)
    for i in range(shares):
        held = tuple(range(i * n, (i + 1) * n))
        cfg = dataclasses.replace(cfg_all, moe=dataclasses.replace(cfg_all.moe, held_experts=held))
        p = dict(p_all, experts=jax.tree.map(lambda w: w[i * n:(i + 1) * n], p_all["experts"]))
        if i:  # the shared expert is computed by every chip alike: count it once
            p = {k: v for k, v in p.items() if k != "shared"}
        y, held_routes = moe.moe_apply_held(p, cfg, x)
        total = total + y
        held_total = held_total + held_routes
    # every routed pair lands on exactly one share
    np.testing.assert_array_equal(held_total, cfg_all.moe.top_k)
    # f32 both ways; the shares' sum reorders the experts' additions
    np.testing.assert_allclose(total.reshape(-1, cfg_all.d_model), want, atol=1e-5, rtol=0)
