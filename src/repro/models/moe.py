"""Mixture-of-Experts FFN with top-k routing, shared experts, aux loss.

Capacity-based scatter dispatch (Switch-style) chosen for TPU SPMD:

* tokens are ranked within their expert by a **sort-based** position
  computation (O(M log M) memory-lean; avoids the (M, E) one-hot cumsum
  which at deepseek-v3 scale would materialize ~0.5 GB per device);
* tokens beyond ``capacity = cf · M · k / E`` are dropped (gate contribution
  zero) — standard capacity truncation;
* the (E, C, d) expert buffer is sharded over the ``model`` axis (expert
  parallelism): the scatter/gather between token-sharded and expert-sharded
  layouts is exactly the MoE all-to-all the roofline analysis tracks.

Aux load-balance loss (Switch/DeepSeek form): ``E · Σ_e f_e · P_e`` with
``f_e`` the dispatch fraction and ``P_e`` the mean router probability.

``moe_apply_held`` is the serving path of expert parallelism: the layer is
told which experts it holds (``MoEConfig.held_experts``), routes over all
of them, and computes only its held experts' part of the result for the
(token, expert) pairs routed to them — grouped by expert with
``jax.lax.ragged_dot``, with no capacity and no dropped tokens.  The part
of the absent experts is left out; there is no exchange.

Routing is ``route``: softmax top-k, or DeepSeek-V3's grouped sigmoid
(``scoring="sigmoid"``): scores s = sigmoid(x·W_r); experts chosen by
s + b (b a selection-only bias); each group of E / n_group scored by the
sum of its two best s + b; the ``topk_group`` best groups kept; the
top-k experts among them; gates the chosen s, normalised to sum 1 and
scaled by ``routed_scaling``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ModelConfig
from repro.models.layers import dense_init, swiglu, swiglu_init, truncated_normal


def moe_init(key, cfg: ModelConfig, dtype=jnp.float32):
    m = cfg.moe
    kr, ke, ks = jax.random.split(key, 3)
    d, f, E = cfg.d_model, m.d_ff_expert, m.num_experts
    n_w = len(m.held_experts) or E  # expert weights this layer holds
    k1, k2, k3 = jax.random.split(ke, 3)
    std_in, std_out = (1.0 / d) ** 0.5, (1.0 / f) ** 0.5
    p = {
        "router": dense_init(kr, d, E, dtype=jnp.float32),  # router kept fp32
        "experts": {
            "w_gate": truncated_normal(k1, (n_w, d, f), dtype, std_in),
            "w_up": truncated_normal(k2, (n_w, d, f), dtype, std_in),
            "w_down": truncated_normal(k3, (n_w, f, d), dtype, std_out),
        },
    }
    if m.scoring == "sigmoid":
        # selection-only bias (DeepSeek-V3's e_score_correction_bias)
        p["router"]["select_bias"] = jnp.zeros((E,), jnp.float32)
    if m.num_shared_experts > 0:
        p["shared"] = swiglu_init(
            ks, d, m.d_ff_shared * m.num_shared_experts, dtype=dtype
        )
    return p


def route(pr, cfg: ModelConfig, x: jnp.ndarray):
    """(gates, expert ids, probabilities) of every token of ``x`` (..., d),
    in f32: ``(..., k)``, ``(..., k)`` and ``(..., E)`` (the probabilities
    feed the aux loss; for sigmoid scoring, the scores normalised)."""
    m = cfg.moe
    k = m.top_k
    logits = x.astype(jnp.float32) @ pr["kernel"]  # (..., E)
    if m.scoring == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)
        gates, ids = jax.lax.top_k(probs, k)
        gates = gates / jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
        return gates, ids, probs
    if m.scoring != "sigmoid":
        raise ValueError(f"unknown scoring {m.scoring!r}")
    E, G = m.num_experts, m.n_group
    s = jax.nn.sigmoid(logits)
    choice = s + pr["select_bias"]
    grouped = choice.reshape(*choice.shape[:-1], G, E // G)
    group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)  # (..., G)
    _, top_groups = jax.lax.top_k(group_score, m.topk_group)
    keep = jnp.sum(jax.nn.one_hot(top_groups, G, dtype=jnp.int32), axis=-2) > 0
    keep = jnp.repeat(keep, E // G, axis=-1)
    _, ids = jax.lax.top_k(jnp.where(keep, choice, -jnp.inf), k)
    gates = jnp.take_along_axis(s, ids, axis=-1)
    gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20) * m.routed_scaling
    return gates, ids, s / jnp.sum(s, axis=-1, keepdims=True)


def moe_apply_held(p, cfg: ModelConfig, x: jnp.ndarray, *, compute_dtype=None):
    """The held experts' part of the layer, plus the shared expert.
    x: (B, T, d).  Returns (y, held) with ``held`` (B, T) int32 the number
    of each token's routed experts that this layer holds.

    Every (token, expert) pair is sorted by the expert's place among the
    held ones (pairs on absent experts last), and each held expert's
    SwiGLU runs on its own rows as one ``ragged_dot`` group; rows past
    the held groups come out zero.  The combine gathers each token's k
    rows back into route order and sums them with their gates (zero on
    absent experts) in f32: a gather, where a scatter-add of the N·k rows
    cost a B=1 prefill of 4,096 tokens 45 ms on a v5e."""
    m = cfg.moe
    B, T, d = x.shape
    k, held = m.top_k, m.held_experts
    n = len(held)
    cd = compute_dtype or x.dtype
    xf = x.reshape(B * T, d)
    with jax.named_scope("moe_route"):
        gates, ids, _ = route(p["router"], cfg, xf)  # (N, k)
        lookup = np.full((m.num_experts,), n, np.int32)
        lookup[list(held)] = np.arange(n)
        local = jnp.asarray(lookup)[ids]  # (N, k): place among the held, n if absent
        flat = local.reshape(-1)
        order = jnp.argsort(flat, stable=True)
        back = jnp.argsort(order)  # each pair's row among the sorted ones
        sizes = jnp.bincount(flat, length=n + 1)[:n].astype(jnp.int32)
        w = jnp.where(local < n, gates, 0.0)  # (N, k)
    with jax.named_scope("moe_experts"):
        e = p["experts"]
        xs = xf[order // k].astype(cd)
        g = jax.lax.ragged_dot(xs, e["w_gate"].astype(cd), sizes)
        u = jax.lax.ragged_dot(xs, e["w_up"].astype(cd), sizes)
        h = jax.lax.ragged_dot(jax.nn.silu(g) * u, e["w_down"].astype(cd), sizes)
        rows = h[back].reshape(B * T, k, d).astype(jnp.float32)
        y = jnp.sum(rows * w[..., None], axis=1).astype(cd)
    y = y.reshape(B, T, d)
    if "shared" in p:
        y = y + swiglu(p["shared"], x, compute_dtype=cd)
    return y.astype(x.dtype), jnp.sum(local < n, axis=-1).reshape(B, T).astype(jnp.int32)


def _positions_in_expert(ids_f: jnp.ndarray, num_experts: int) -> jnp.ndarray:
    """Rank of each dispatch entry within its expert, via stable sort —
    O(M) memory instead of the (M, E) cumsum."""
    M = ids_f.shape[0]
    order = jnp.argsort(ids_f, stable=True)
    sorted_ids = ids_f[order]
    idx = jnp.arange(M, dtype=jnp.int32)
    is_start = jnp.concatenate(
        [jnp.ones((1,), bool), sorted_ids[1:] != sorted_ids[:-1]]
    )
    run_start = jax.lax.associative_scan(jnp.maximum, jnp.where(is_start, idx, 0))
    rank_sorted = idx - run_start
    return jnp.zeros((M,), jnp.int32).at[order].set(rank_sorted)


def moe_apply(p, cfg: ModelConfig, x: jnp.ndarray, *, compute_dtype=None):
    """Returns (y, aux_loss).  x: (B, T, d).

    Dispatch is **grouped per batch row** (group = sequence): positions and
    capacity are computed within each row, so every intermediate stays
    sharded (batch → data axis, experts → model axis) and the only
    resharding is the (B, E, C, d) expert buffer — the MoE all-to-all.
    A globally-flattened dispatch would force SPMD to replicate the (N·k, d)
    gather (~68 GB/device at olmoe train scale).  Per-row capacity is the
    standard group-limited variant (slightly stricter than global capacity).
    """
    m = cfg.moe
    B, T, d = x.shape
    E, k = m.num_experts, m.top_k
    cd = compute_dtype or x.dtype
    C = max(1, int(m.capacity_factor * T * k / E))

    # --- routing (fp32)
    gate_vals, expert_ids, probs = route(p["router"], cfg, x)  # (B, T, k)

    # --- aux load-balance loss (global over the batch)
    f_e = jnp.mean(
        jnp.sum(jax.nn.one_hot(expert_ids, E, dtype=jnp.float32), axis=2),
        axis=(0, 1),
    ) / k
    P_e = jnp.mean(probs, axis=(0, 1))
    aux = m.aux_loss_coef * E * jnp.sum(f_e * P_e)

    M = T * k
    tok_f = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)  # (M,)

    def dispatch_row(xr, ids, gates):
        """xr (T, d); ids/gates (T, k) → buffer (E, C, d) + combine info."""
        ids_f = ids.reshape(M)
        gate_f = gates.reshape(M)
        pos = _positions_in_expert(ids_f, E)
        keep = pos < C
        dest = jnp.where(keep, ids_f * C + pos, E * C)  # overflow → trash
        buf = jnp.zeros((E * C + 1, d), cd).at[dest].set(xr[tok_f].astype(cd))
        return buf[: E * C].reshape(E, C, d), dest, keep, gate_f

    xe, dest, keep, gate_f = jax.vmap(dispatch_row)(x, expert_ids, gate_vals)
    # xe: (B, E, C, d) — resharding to (data, model, ·, ·) is the all-to-all.
    # Pin the layout explicitly: without the constraint the SPMD partitioner
    # has been observed to replicate the buffer and re-slice it (an
    # all-gather of the whole dispatch buffer) instead of emitting the
    # token-sized all-to-all — see EXPERIMENTS.md §Perf hillclimb A.
    from repro.sharding.rules import maybe_shard

    xe = maybe_shard(xe, "batch", "model", None, None)

    # --- expert FFN (SwiGLU), batched over experts
    w = p["experts"]
    g = jnp.einsum("becd,edf->becf", xe, w["w_gate"].astype(cd))
    u = jnp.einsum("becd,edf->becf", xe, w["w_up"].astype(cd))
    h = jnp.einsum("becf,efd->becd", jax.nn.silu(g) * u, w["w_down"].astype(cd))
    h = maybe_shard(h, "batch", "model", None, None)

    def combine_row(hr, dest_r, keep_r, gate_r):
        hf = hr.reshape(E * C, d)
        ent = jnp.where(
            keep_r[:, None], hf[jnp.minimum(dest_r, E * C - 1)], 0.0
        ) * gate_r[:, None].astype(cd)
        return jnp.zeros((T, d), cd).at[tok_f].add(ent)

    y = jax.vmap(combine_row)(h, dest, keep, gate_f)

    if "shared" in p:
        y = y + swiglu(p["shared"], x, compute_dtype=cd)

    return y.astype(x.dtype), aux
