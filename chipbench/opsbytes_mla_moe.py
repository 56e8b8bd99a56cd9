"""Operations and bytes of a decode step of an MLA + mixture-of-experts
decoder (DeepSeek-V3's layout) that holds a share of the routed experts,
from its shapes.  Like ``opsbytes``, these count what the algorithm
needs: the absorbed latent attention over each token's valid context,
every weight a decoded token is multiplied by, and the held experts'
weights once per route to them.  ``c`` is the configuration's file
(Hugging Face key names; ``n_routed_experts_published`` is the router's
width).
"""

from __future__ import annotations


def _mla_params(c: dict) -> int:
    """One MLA layer's projections, in the absorbed form: the query's
    down- and up-projection, the latent and rope-key projection, W_uk
    absorbed into the query, W_uv after the attention, and the output."""
    d, H = c["hidden_size"], c["num_attention_heads"]
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    qr, r = c["q_lora_rank"], c["kv_lora_rank"]
    return d * qr + qr * H * (dn + dr) + d * (r + dr) + r * H * dn + r * H * dv + H * dv * d


def dense_token_params(c: dict) -> int:
    """Weights every decoded token is multiplied by, the routed experts
    left out: MLA in every layer, the dense FFN of the leading dense
    layers, the shared experts and the router of the expert layers, and
    the output head over the vocabulary."""
    d = c["hidden_size"]
    L, n_dense = c["num_hidden_layers"], c["first_k_dense_replace"]
    shared = 3 * d * c["moe_intermediate_size"] * c["n_shared_experts"]
    router = d * c["n_routed_experts_published"]
    return (L * _mla_params(c) + n_dense * 3 * d * c["intermediate_size"]
            + (L - n_dense) * (shared + router) + c["vocab_size"] * d)


def latent_attention_flops(c: dict, valid: int) -> float:
    """Absorbed attention over ``valid`` cached positions (summed over
    tokens), every layer: per head, scores against the whole latent row
    (kv_lora_rank + rope) and values from its latent part."""
    H, r, dr = c["num_attention_heads"], c["kv_lora_rank"], c["qk_rope_head_dim"]
    return 2.0 * H * (r + dr + r) * valid * c["num_hidden_layers"]


def held_route_flops(c: dict) -> float:
    """One (token, expert) pair on a held expert: its SwiGLU's three
    products."""
    return 2.0 * 3 * c["hidden_size"] * c["moe_intermediate_size"]


def decode_flops(c: dict, tokens: int, valid: int, held_routes: int) -> float:
    """Decoded ``tokens`` whose contexts hold ``valid`` positions in all,
    with ``held_routes`` routes to held experts among them."""
    return (2.0 * dense_token_params(c) * tokens + latent_attention_flops(c, valid)
            + held_routes * held_route_flops(c))


def mla_decode_cost(c: dict, valid: int, queries: int, itemsize: int = 2) -> tuple:
    """(flops, bytes) of the paged MLA decode kernel for ``queries`` tokens
    whose contexts hold ``valid`` positions in all, every layer: each
    valid position's latent row (kv_lora_rank + rope values) read once,
    each query (H latent rows) read and its (H, kv_lora_rank) context
    written once."""
    H, r, dr = c["num_attention_heads"], c["kv_lora_rank"], c["qk_rope_head_dim"]
    L = c["num_hidden_layers"]
    nbytes = ((r + dr) * valid + queries * H * (r + dr) + queries * H * r) * itemsize * L
    return latent_attention_flops(c, valid), float(nbytes)
