"""deepseek-v3: the program's configuration from the file, weights from the
seed, and the plain float32 reference that decides ``correct``.

The reference is written from the published description (DeepSeek-V3,
arXiv:2412.19437, and the released config.json): a pre-norm decoder with
RMSNorm; multi-head latent attention — queries through a rank-1536
bottleneck with its own norm, keys and values up-projected from a normed
rank-512 latent, and a 64-wide rotary key shared by all heads, with YaRN
frequencies and the softmax scale (nope + rope)^-0.5 · mscale²; then a
SwiGLU FFN on the leading dense layer and, on the others, a mixture of
experts: sigmoid scores over all routed experts, the experts chosen by
score plus a selection-only bias, limited to the best groups (each group
scored by the sum of its two best biased scores), gates the chosen
scores normalised and scaled, plus one shared SwiGLU expert; an untied
output head.

The chip holds a share of the routed experts (``experts_held``): the
reference routes over all of them and sums only the held experts' part,
as the program does; the absent experts' part is left out of both.  It
runs MLA in the paper's form (per-head keys and values up-projected from
the latent), so it checks the program's absorbed decode independently.

It imports nothing of the program and reads only the weights that
``make_params`` made here, in the program's tree layout.  Everything runs
in float32 with every matrix product at ``Precision.HIGHEST``, one layer
at a time under ``lax.scan`` (each routed expert's weights upcast one at
a time), attention in blocks of queries, and the output head in blocks
of rows, so that it fits beside the served weights at 8,192 positions.

The control (``quantize=True``) rounds the inputs of every projection,
expert and head product to float8 (e4m3, one scale per tensor): the
precision below the bfloat16 compute that the configuration states.

The gaps leave out the positions whose routing, in the reference itself,
would choose a held expert differently at some expert layer if its
biased scores moved by at most ``SCORE_SLACK`` (``route_fragile``).
There the rounding of bfloat16 compute can trade a held expert for an
absent one, and the position's output moves by a whole expert's part: a
sound answer to a question the published routing leaves nearly
undecided.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
HEAD_BLOCK = 256
QUERY_BLOCK = 128
#: the move of a biased routing score that bfloat16 compute is allowed
#: (``route_fragile``); positions it can reroute are left out of the gaps.
#: On a v5e every served gap above 0.15, over 5,637 checked positions of
#: the reasoning cell, was a held expert rerouted by a move under 0.005
#: (PERF.md, section 2)
SCORE_SLACK = 0.006


def program_config(c: dict):
    """The program's ``ModelConfig`` with every size taken from the file."""
    from repro.configs import get_config
    from repro.models.config import MLAConfig, MoEConfig

    base = get_config(c["program_arch"])
    rs = c["rope_scaling"]
    return dataclasses.replace(
        base,
        num_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"],
        d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"],
        tie_embeddings=c["tie_word_embeddings"],
        rope_theta=c["rope_theta"],
        rms_eps=c["rms_norm_eps"],
        yarn_factor=rs["factor"],
        yarn_beta_fast=rs["beta_fast"],
        yarn_beta_slow=rs["beta_slow"],
        yarn_original_max_pos=rs["original_max_position_embeddings"],
        yarn_mscale_all_dim=rs["mscale_all_dim"],
        mla=MLAConfig(
            q_lora_rank=c["q_lora_rank"],
            kv_lora_rank=c["kv_lora_rank"],
            qk_nope_head_dim=c["qk_nope_head_dim"],
            qk_rope_head_dim=c["qk_rope_head_dim"],
            v_head_dim=c["v_head_dim"],
        ),
        moe=MoEConfig(
            num_experts=c["n_routed_experts_published"],
            top_k=c["num_experts_per_tok"],
            d_ff_expert=c["moe_intermediate_size"],
            num_shared_experts=c["n_shared_experts"],
            d_ff_shared=c["moe_intermediate_size"],
            layer_mode="after_first_k",
            first_k_dense=c["first_k_dense_replace"],
            scoring=c["scoring_func"],
            n_group=c["n_group"],
            topk_group=c["topk_group"],
            routed_scaling=c["routed_scaling_factor"],
            held_experts=tuple(c["experts_held"]),
        ),
        num_mtp_layers=c["num_nextn_predict_layers"],
        param_dtype=c["param_dtype"],
        compute_dtype=c["compute_dtype"],
        remat_policy="none",
    )


def _scale(path: str, shape) -> tuple:
    """(multiplier, offset) of a standard normal draw for one leaf."""
    if path.endswith("kernel") or path.split("/")[-1] in ("w_gate", "w_up", "w_down"):
        return (1.0 / shape[-2]) ** 0.5, 0.0
    if path.endswith("select_bias"):
        return 0.01, 0.0
    if path.endswith("scale"):
        return 0.1, 1.0
    if path.endswith("embedding"):
        return 0.02, 0.0
    raise ValueError(f"no initializer for leaf {path}")


def make_params(shapes, key):
    """Random weights in the layout ``shapes`` (the program's parameter
    tree as ``jax.eval_shape`` gives it), made on the device in one
    jitted call, in the dtype the program serves them in."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    paths = ["/".join(str(getattr(k, "key", k)) for k in p) for p, _ in flat]
    specs = tuple((s.shape, s.dtype, _scale(path, s.shape)) for path, (_, s) in zip(paths, flat))

    @jax.jit
    def gen(key):
        out = []
        for i, (shape, dtype, (mul, off)) in enumerate(specs):
            x = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
            out.append((off + mul * x).astype(dtype))
        return out

    return jax.tree_util.tree_unflatten(treedef, gen(key))


# ----------------------------------------------------------------------------
# the plain reference
# ----------------------------------------------------------------------------


def _fp8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, quantize: bool):
    w = w.astype(jnp.float32)
    if quantize:
        x, w = _fp8(x), _fp8(w)
    return jnp.matmul(x, w, precision=HI)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale.astype(
        jnp.float32)


def yarn_inv_freq(c: dict):
    """YaRN (arXiv:2309.00071) inverse frequencies of the rope dimensions,
    from the closed form: the base frequency theta^(-2i/d) where the
    dimension turns more than beta_fast times over the original context,
    that over the factor where it turns fewer than beta_slow times, and a
    linear ramp between the two correction dimensions."""
    import numpy as np

    d, theta = c["qk_rope_head_dim"], float(c["rope_theta"])
    rs = c["rope_scaling"]
    ctx = rs["original_max_position_embeddings"]

    def corr(rot):
        return d * math.log(ctx / (rot * 2 * math.pi)) / (2 * math.log(theta))

    lo = max(math.floor(corr(rs["beta_fast"])), 0)
    hi = min(math.ceil(corr(rs["beta_slow"])), d - 1)
    base = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    ramp = np.clip((np.arange(d // 2) - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    return (base * (1.0 - ramp) + base / rs["factor"] * ramp).astype(np.float32)


def softmax_scale(c: dict) -> float:
    rs = c["rope_scaling"]
    mscale = 0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0
    return (c["qk_nope_head_dim"] + c["qk_rope_head_dim"]) ** -0.5 * mscale ** 2


def _rope(x, pos, inv):
    """x: (T, ..., D); rotate the pair (x_i, x_{i + D/2}) by pos · inv_i."""
    d = x.shape[-1]
    ang = pos.astype(jnp.float32).reshape((-1,) + (1,) * (x.ndim - 2) + (1,)) * inv
    cs, sn = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cs - x2 * sn, x2 * cs + x1 * sn], axis=-1)


def _attention(c: dict, quantize: bool, x, a):
    T = x.shape[0]
    H = c["num_attention_heads"]
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    eps = c["rms_norm_eps"]
    pos = jnp.arange(T)
    inv = jnp.asarray(yarn_inv_freq(c))
    cq = _rms(_mm(x, a["w_dq"]["kernel"], quantize), a["q_norm"]["scale"], eps)
    q = _mm(cq, a["w_uq"]["kernel"], quantize).reshape(T, H, dn + dr)
    q_nope, q_pe = q[..., :dn], _rope(q[..., dn:], pos, inv)
    ckv = _rms(_mm(x, a["w_dkv"]["kernel"], quantize), a["kv_norm"]["scale"], eps)
    k_pe = _rope(_mm(x, a["w_kr"]["kernel"], quantize), pos, inv)  # (T, dr)
    k_nope = _mm(ckv, a["w_uk"]["kernel"], quantize).reshape(T, H, dn)
    v = _mm(ckv, a["w_uv"]["kernel"], quantize).reshape(T, H, dv)
    scale = softmax_scale(c)

    def block(xs):
        qn, qp, qi = xs  # (QB, H, dn), (QB, H, dr), (QB,)
        s = (jnp.einsum("thd,shd->hts", qn, k_nope, precision=HI)
             + jnp.einsum("thd,sd->hts", qp, k_pe, precision=HI)) * scale
        s = jnp.where(pos[None, None, :] <= qi[None, :, None], s, -jnp.inf)
        return jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v, precision=HI)

    qb = QUERY_BLOCK if T % QUERY_BLOCK == 0 else T
    nb = T // qb
    o = jax.lax.map(block, (q_nope.reshape(nb, qb, H, dn), q_pe.reshape(nb, qb, H, dr),
                            pos.reshape(nb, qb)))
    return _mm(o.reshape(T, H * dv), a["w_o"]["kernel"], quantize)


def _swiglu(x, w_gate, w_up, w_down, quantize):
    g = _mm(x, w_gate, quantize)
    u = _mm(x, w_up, quantize)
    return _mm(jax.nn.silu(g) * u, w_down, quantize)


def routing(c: dict, x, w_router, bias):
    """(gates, chosen experts), each (T, k), of DeepSeek-V3's grouped
    sigmoid routing over all routed experts."""
    E, G = c["n_routed_experts_published"], c["n_group"]
    k, kg = c["num_experts_per_tok"], c["topk_group"]
    s = jax.nn.sigmoid(jnp.matmul(x, w_router.astype(jnp.float32), precision=HI))  # (T, E)
    biased = s + bias.astype(jnp.float32)
    per_group = biased.reshape(-1, G, E // G)
    group_score = jnp.sum(jax.lax.top_k(per_group, 2)[0], axis=-1)  # (T, G)
    rank = jnp.argsort(jnp.argsort(-group_score, axis=-1), axis=-1)
    allowed = jnp.repeat(rank < kg, E // G, axis=-1)  # (T, E)
    _, chosen = jax.lax.top_k(jnp.where(allowed, biased, -jnp.inf), k)
    w = jnp.take_along_axis(s, chosen, axis=-1)
    w = w / jnp.sum(w, axis=-1, keepdims=True) * c["routed_scaling_factor"]
    return w, chosen


def route_fragile(c: dict, x, w_router, bias, slack: float):
    """Per token, (T,) bool: whether moving each biased score by at most
    ``slack`` can change which held experts the routing chooses.  Such a
    move changes the choice within the kept groups where a held expert's
    score lies within 2·slack of the k-th chosen score (if it is left out)
    or of the first score left out (if chosen), and swaps a kept group for
    one left out where their group scores lie within 4·slack (a group
    score sums two scores); a token is fragile where the choice changes
    in the kept groups, or one such swap changes it or leaves a held
    expert that near.  Swaps of two group pairs at once are not counted."""
    import numpy as np

    E, G = c["n_routed_experts_published"], c["n_group"]
    k, kg, n = c["num_experts_per_tok"], c["topk_group"], E // G
    held = np.asarray(c["experts_held"])
    s = jax.nn.sigmoid(jnp.matmul(x, w_router.astype(jnp.float32), precision=HI))
    biased = s + bias.astype(jnp.float32)
    group_score = jnp.sum(jax.lax.top_k(biased.reshape(-1, G, n), 2)[0], axis=-1)  # (T, G)
    order = jnp.argsort(-group_score, axis=-1)  # groups by rank
    ranked = jnp.take_along_axis(group_score, order, axis=-1)
    kept_g = jnp.argsort(order, axis=-1) < kg

    def held_choice(kept_g):
        kept = jnp.repeat(kept_g, n, axis=-1)[:, held]
        top = jax.lax.top_k(jnp.where(jnp.repeat(kept_g, n, axis=-1), biased, -jnp.inf),
                            k + 1)[0]
        last_in, first_out = top[:, k - 1:k], top[:, k:k + 1]
        bh = biased[:, held]
        chosen = kept & (bh >= last_in)
        near = kept & (jnp.where(chosen, bh - first_out, last_in - bh) < 2 * slack)
        return chosen, jnp.any(near, axis=-1)

    chosen, fragile = held_choice(kept_g)
    for a in range(kg):
        for b in range(kg, G):
            swapped = (kept_g & ~jax.nn.one_hot(order[:, a], G, dtype=bool)
                       | jax.nn.one_hot(order[:, b], G, dtype=bool))
            ch, near = held_choice(swapped)
            close = ranked[:, a] - ranked[:, b] < 4 * slack
            fragile = fragile | (close & (jnp.any(ch != chosen, axis=-1) | near))
    return fragile


def _experts(c: dict, quantize: bool, x, f):
    """The held routed experts' part plus the shared expert."""
    w, chosen = routing(c, x, f["router"]["kernel"], f["router"]["select_bias"])
    held = jnp.asarray(c["experts_held"])
    # each token's gate on each held expert (0 where not chosen)
    on_held = jnp.sum(jnp.where(chosen[:, None, :] == held[None, :, None], w[:, None, :], 0.0),
                      axis=-1)  # (T, n_held)
    e = f["experts"]

    def one(acc, xs):
        wg, wu, wd, gate = xs
        return acc + gate[:, None] * _swiglu(x, wg, wu, wd, quantize), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (e["w_gate"], e["w_up"], e["w_down"], on_held.T))
    s = f["shared"]
    return y + _swiglu(x, s["w_gate"]["kernel"], s["w_up"]["kernel"], s["w_down"]["kernel"],
                       quantize)


def _layer(c: dict, quantize: bool, moe: bool, h, p):
    """(the layer's output, each position's ``route_fragile`` there)."""
    eps = c["rms_norm_eps"]
    h = h + _attention(c, quantize, _rms(h, p["mixer_norm"]["scale"], eps), p["mixer"])
    x = _rms(h, p["ffn_norm"]["scale"], eps)
    f = p["ffn"]
    if moe:
        fragile = route_fragile(c, x, f["router"]["kernel"], f["router"]["select_bias"],
                                SCORE_SLACK)
        return h + _experts(c, quantize, x, f), fragile
    return h + _swiglu(x, f["w_gate"]["kernel"], f["w_up"]["kernel"], f["w_down"]["kernel"],
                       quantize), jnp.zeros(h.shape[:1], bool)


def _hidden(c: dict, quantize: bool, params, tokens):
    """(the final-normed hidden state of every position, (T, d); whether
    each position's routing is ``route_fragile`` at some expert layer, (T,))."""
    h = params["embed"]["embedding"][tokens].astype(jnp.float32)
    fragile = jnp.zeros(h.shape[:1], bool)
    n_dense = c["first_k_dense_replace"]
    for si in range(2 if n_dense < c["num_hidden_layers"] else 1):
        moe = si == 1 or n_dense == 0
        h, f = jax.lax.scan(partial(_layer, c, quantize, moe), h, params[f"seg{si}"]["l0"])
        fragile = fragile | jnp.any(f, axis=0)
    return _rms(h, params["final_norm"]["scale"], c["rms_norm_eps"]), fragile


@partial(jax.jit, static_argnames=("c_items",))
def _logits(params, tokens, c_items):
    c = _config(c_items)
    return _mm(_hidden(c, False, params, tokens)[0],
               params["lm_head"]["kernel"][:, : c["vocab_size"]], False)


def logits(c: dict, params, tokens):
    """Every position's logits over the vocabulary, (T, V): the whole
    forward at once, for small sizes (the tests)."""
    import numpy as np

    return np.asarray(_logits(params, jnp.asarray(tokens, jnp.int32), _items(c)))


@partial(jax.jit, static_argnames=("c_items", "quantize"))
def _logit_stats(params, tokens, targets, c_items, quantize):
    c = _config(c_items)
    V = c["vocab_size"]
    h, fragile = _hidden(c, quantize, params, tokens)
    head = params["lm_head"]["kernel"][:, :V]
    T = h.shape[0]
    hb = h.reshape(T // HEAD_BLOCK, HEAD_BLOCK, -1)
    tb = targets.reshape(T // HEAD_BLOCK, HEAD_BLOCK)

    def block(xs):
        hh, tt = xs
        lg = _mm(hh, head, quantize)
        return (jnp.max(lg, axis=-1), jnp.argmax(lg, axis=-1).astype(jnp.int32),
                jnp.take_along_axis(lg, tt[:, None], axis=-1)[:, 0])

    best, top, at = jax.lax.map(block, (hb, tb))
    return best.reshape(T), top.reshape(T), at.reshape(T), fragile


def _items(c: dict) -> tuple:
    keys = ("vocab_size", "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "rms_norm_eps", "rope_theta", "n_group", "topk_group",
            "num_experts_per_tok", "routed_scaling_factor", "n_routed_experts_published",
            "first_k_dense_replace", "num_hidden_layers")
    out = [(k, c[k]) for k in keys]
    out.append(("experts_held", tuple(c["experts_held"])))
    out.append(("rope_scaling", tuple(sorted(c["rope_scaling"].items()))))
    return tuple(out)


def _config(items: tuple) -> dict:
    c = dict(items)
    c["rope_scaling"] = dict(c["rope_scaling"])
    return c


def logit_stats(c: dict, params, tokens, targets, length: int, *, quantize: bool = False):
    """Per position of ``tokens`` (padded with zeros to ``length``, a
    multiple of 256): the largest logit, the token that has it, the logit
    of ``targets`` there, and whether its routing is ``route_fragile`` at
    some expert layer."""
    import numpy as np

    n = len(tokens)
    tok = np.zeros(length, np.int32)
    tok[:n] = tokens
    tgt = np.zeros(length, np.int32)
    tgt[: len(targets)] = targets
    out = _logit_stats(params, jnp.asarray(tok), jnp.asarray(tgt), _items(c), quantize)
    return tuple(np.asarray(x)[:n] for x in out)


def position_gaps(c: dict, params, prompt, served, length: int, *, control: bool = False):
    """(gap, fragile) at each served position, each (len(served),):
    the gap by which the served token's logit lies below the reference's
    largest logit there, or with ``control`` the gap of the token the
    float8 control puts first (given the same tokens before it)."""
    import numpy as np

    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    P = len(prompt)
    if control:
        _, tgt, _, _ = logit_stats(c, params, seq, np.zeros(len(seq), np.int32), length,
                                   quantize=True)
    else:
        tgt = np.zeros(len(seq), np.int32)
        tgt[P - 1:] = served
    best, _, at, fragile = logit_stats(c, params, seq, tgt, length)
    return best[P - 1:] - at[P - 1:], fragile[P - 1:]


def _widest(gap, fragile) -> float:
    import numpy as np

    return float(np.max(np.where(fragile, 0.0, gap), initial=0.0))


def served_gap(c: dict, params, prompt, served, length: int) -> float:
    """Widest gap by which a served token's logit lies below the
    reference's largest logit at its position, over the positions whose
    routing is not ``route_fragile``."""
    return _widest(*position_gaps(c, params, prompt, served, length))


def control_gap(c: dict, params, prompt, served, length: int) -> float:
    """The same gap for the tokens the float8 control puts first at each
    served position (given the same prompt and served tokens before it)."""
    return _widest(*position_gaps(c, params, prompt, served, length, control=True))
