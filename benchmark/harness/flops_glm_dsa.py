"""Operations and bytes the GLM-5 encoder's ALGORITHM needs, from shapes
alone; ``model`` is the published ``config.json``'s keys as the
configuration's file holds them (``n_routed_experts`` = the experts HELD
by this chip, ``experts_held.of`` = the router's width). As in
``flops.py``: a matmul of ``(m, k)`` by ``(k, n)`` is ``2*m*k*n``
operations; norms, rotary, activations, the softmax and the bisection's
compares are left out of the operations (the selection is priced by its
bytes).

Three counts follow what the program MET and come from its counters, not
from shapes: a routed expert's operations the rows routed to it; index
scoring the pairs SCORED (a query times the positions up to its own);
the core the pairs the selection ADMITTED (``min(reached, index_topk)``
a query), so that a core which visits more than it admits (every key
block reached, under a mask) has still done no more than the algorithm
asks.
"""

from __future__ import annotations


def _heads(model: dict) -> int:
    return model["num_attention_heads"]


def mla_params(model: dict) -> int:
    """q_a, q_norm, q_b, kv_a, kv_norm, kv_b and o of one layer."""
    e, h = model["hidden_size"], _heads(model)
    nope, rope, v = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                     model["v_head_dim"])
    q, kv = model["q_lora_rank"], model["kv_lora_rank"]
    return (e * q + q + q * h * (nope + rope) + e * (kv + rope) + kv
            + kv * h * (nope + v) + h * v * e)


def indexer_params(model: dict) -> int:
    """wq_b, wk, k_norm's weight and bias, weights_proj of one layer."""
    e, hi, d = (model["hidden_size"], model["index_n_heads"],
                model["index_head_dim"])
    return model["q_lora_rank"] * hi * d + e * d + 2 * d + e * hi


def expert_params(model: dict) -> int:
    """One routed or shared expert: gate, up and down."""
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def router_width(model: dict) -> int:
    held = model.get("experts_held")
    return held["of"] if held else model["n_routed_experts"]


def router_params(model: dict) -> int:
    """The router's matrix and its ``e_score_correction_bias``."""
    return (model["hidden_size"] + 1) * router_width(model)


def dense_mlp_params(model: dict) -> int:
    return 3 * model["hidden_size"] * model["intermediate_size"]


def layer_counts(model: dict) -> tuple:
    """``(dense layers, expert layers)``."""
    dense = model["first_k_dense_replace"]
    return dense, model["num_hidden_layers"] - dense


def _attention_params(model: dict) -> int:
    """Latent attention, the indexer and the layer's two norms."""
    return mla_params(model) + indexer_params(model) \
        + 2 * model["hidden_size"]


def dense_layer_params(model: dict) -> int:
    return _attention_params(model) + dense_mlp_params(model)


def expert_layer_params(model: dict) -> int:
    """An expert layer as this chip holds it: attention and indexer
    whole, the router, the shared experts and the held routed ones."""
    return (_attention_params(model) + router_params(model)
            + (model["n_shared_experts"] + model["n_routed_experts"])
            * expert_params(model))


def embedding_params(model: dict) -> int:
    return model["vocab_size"] * model["hidden_size"]


def held_params(model: dict) -> int:
    """Everything held, to the unit: the layers, the slice of the
    embedding and the final norm (no LM head, no prediction module)."""
    dense, moe = layer_counts(model)
    return (dense * dense_layer_params(model)
            + moe * expert_layer_params(model)
            + embedding_params(model) + model["hidden_size"])


def weight_bytes(model: dict, bytes_per_weight: int = 2) -> int:
    """What one program reads at the least: every held weight but the
    embedding (a gather)."""
    return (held_params(model) - embedding_params(model)) * bytes_per_weight


def token_matmul_params(model: dict) -> int:
    """Weights that multiply EVERY lane position: latent attention's five
    matrices and the indexer's three in every layer, the dense MLPs, each
    expert layer's router and shared experts."""
    e, h = model["hidden_size"], _heads(model)
    nope, rope, v = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                     model["v_head_dim"])
    q, kv = model["q_lora_rank"], model["kv_lora_rank"]
    hi, d = model["index_n_heads"], model["index_head_dim"]
    attention = (e * q + q * h * (nope + rope) + e * (kv + rope) + h * v * e
                 + q * hi * d + e * d + e * hi)
    dense, moe = layer_counts(model)
    return ((dense + moe) * attention + dense * dense_mlp_params(model)
            + moe * (model["hidden_size"] * router_width(model)
                     + model["n_shared_experts"] * expert_params(model)))


def routed_flops(model: dict, routed_rows: float) -> float:
    """The grouped matmuls: every routed row meets one expert."""
    return 2.0 * routed_rows * expert_params(model)


def index_pair_flops(model: dict) -> float:
    """Index scoring of ONE scored pair: a dot product of
    ``index_head_dim`` a head (2 x 32 x 128 = 8,192)."""
    return 2.0 * model["index_n_heads"] * model["index_head_dim"]


def index_bytes(model: dict, scored_pairs: float, lane_steps: float,
                cache_steps: float, state_bytes: int = 2) -> float:
    """What one layer's scoring must move: the index keys of the
    positions met, the index queries and weights of the lanes run, one
    float32 score a pair out."""
    hi, d = model["index_n_heads"], model["index_head_dim"]
    return (cache_steps * d * state_bytes + lane_steps * hi * (d * 2 + 4)
            + scored_pairs * 4)


def select_bytes(scored_pairs: float) -> float:
    """What one layer's selection must move: every score read once (4 B)
    and one byte of mask a pair written."""
    return scored_pairs * (4 + 1)


def pair_flops(model: dict) -> float:
    """Scores and values of ONE admitted query-key pair, all heads, in
    the expanded form (64 x 2 x (192 + 64 + 256) = 65,536)."""
    return 2.0 * _heads(model) * (
        model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
        + model["v_head_dim"])


def expand_flops(model: dict) -> float:
    """``W_kvb`` over one position's latent."""
    return 2.0 * model["kv_lora_rank"] * _heads(model) * (
        model["qk_nope_head_dim"] + model["v_head_dim"])


def core_flops(model: dict, selected_pairs: float,
               cache_steps: float) -> float:
    """One layer's core: the admitted pairs, and ``W_kvb`` over every
    position a program's rows had reached (``cache_steps``: the
    expanded form re-derives keys and values of what it meets)."""
    return selected_pairs * pair_flops(model) \
        + cache_steps * expand_flops(model)


def core_bytes(model: dict, lane_steps: float, cache_steps: float,
               selected_pairs: float, state_bytes: int = 2) -> float:
    """What one layer's core must move: the latent rows met, the queries
    in bfloat16 and the output in float32 for the lanes run, one byte of
    mask an admitted pair."""
    h = _heads(model)
    latent = model["kv_lora_rank"] + model["qk_rope_head_dim"]
    per_query = h * (model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
                     ) * 2 + h * model["v_head_dim"] * 4
    return (cache_steps * latent * state_bytes + lane_steps * per_query
            + selected_pairs)


def forward_flops(model: dict, lane_steps: float, routed_rows: float,
                  scored_pairs: float, selected_pairs: float,
                  cache_steps: float) -> float:
    """The whole forward for the lane positions RUN (padding lanes
    included: a matmul cannot skip a lane the batcher gave it), the rows
    routed, and the pairs scored and admitted; the two pair counts and
    ``cache_steps`` are sums over the layers."""
    layers = model["num_hidden_layers"]
    return (2.0 * token_matmul_params(model) * lane_steps
            + routed_flops(model, routed_rows)
            + scored_pairs * index_pair_flops(model)
            + selected_pairs * pair_flops(model)
            + layers * cache_steps * expand_flops(model))
