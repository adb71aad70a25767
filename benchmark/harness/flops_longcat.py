"""Operations and bytes the LongCat-Flash encoder's ALGORITHM needs, from
shapes alone; ``model`` is the published ``config.json``'s keys as the
configuration's file holds them (``n_routed_experts`` = the experts HELD
by this chip, ``experts_held.of`` = the experts with weights over all
chips, ``zero_expert_num`` the router's outputs without). As in
``flops.py``: a matmul of ``(m, k)`` by ``(k, n)`` is ``2*m*k*n``
operations; norms, rotary, activations, the softmaxes, the top-k and the
identity experts' one multiply a token are left out (under 1 %).

A layer is TWO latent-attention sublayers and TWO dense FFNs beside one
routed branch, so everything that counts attention counts ``2 *
num_layers`` sublayers. A routed expert's operations follow the rows
ROUTED to it, so they are counted from the program's counters, not from
shapes; the attention core's follow the positions attended.
"""

from __future__ import annotations

from typing import Iterable


def latent_sublayers(model: dict) -> int:
    """Latent-attention sublayers, each with a cache of its own."""
    return 2 * model["num_layers"]


def _heads(model: dict) -> int:
    return model["num_attention_heads"]


def mla_params(model: dict) -> int:
    """q_a, q_b, kv_a, kv_b and o of one sublayer."""
    e, h = model["hidden_size"], _heads(model)
    nope, rope, v = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                     model["v_head_dim"])
    return (e * model["q_lora_rank"]
            + model["q_lora_rank"] * h * (nope + rope)
            + e * (model["kv_lora_rank"] + rope)
            + model["kv_lora_rank"] * h * (nope + v)
            + h * v * e)


def dense_ffn_params(model: dict) -> int:
    """One sublayer's dense SwiGLU: gate, up and down."""
    return 3 * model["hidden_size"] * model["ffn_hidden_size"]


def expert_params(model: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * model["hidden_size"] * model["expert_ffn_hidden_size"]


def router_width(model: dict) -> int:
    """The router's outputs: the experts with weights over all chips and
    the zero-compute ones."""
    held = model.get("experts_held")
    return (held["of"] if held else model["n_routed_experts"]) \
        + model.get("zero_expert_num", 0)


def router_params(model: dict) -> int:
    return model["hidden_size"] * router_width(model)


def norm_params(model: dict) -> int:
    """A layer's norms: each sublayer's input and post-attention norm,
    and its two low-rank norms."""
    return 2 * (2 * model["hidden_size"] + model["q_lora_rank"]
                + model["kv_lora_rank"])


def layer_params(model: dict) -> int:
    """A layer as this chip holds it: two MLAs, two dense FFNs, the
    router, the held experts and the norms."""
    return (2 * mla_params(model) + 2 * dense_ffn_params(model)
            + router_params(model)
            + model["n_routed_experts"] * expert_params(model)
            + norm_params(model))


def embedding_params(model: dict) -> int:
    return model["vocab_size"] * model["hidden_size"]


def held_params(model: dict) -> int:
    """Everything held: the layers, the embedding's slice, the final
    norm."""
    return (model["num_layers"] * layer_params(model)
            + embedding_params(model) + model["hidden_size"])


def weight_bytes(model: dict, bytes_per_weight: int = 2) -> int:
    """What one program reads at the least: every held matrix but the
    embedding (a gather)."""
    return (held_params(model) - embedding_params(model)) * bytes_per_weight


def held_expert_bytes(model: dict, bytes_per_weight: int = 2) -> int:
    """The held experts' matrices of all layers: what the grouped
    matmuls of one program read when every held expert gets a row."""
    return model["num_layers"] * model["n_routed_experts"] \
        * expert_params(model) * bytes_per_weight


def token_matmul_params(model: dict) -> int:
    """Weights that multiply EVERY valid token: each layer's two MLAs,
    two dense FFNs and router."""
    return model["num_layers"] * (
        2 * mla_params(model) + 2 * dense_ffn_params(model)
        + router_params(model))


def routed_flops(model: dict, routed_rows: float) -> float:
    """The grouped matmuls: every routed row meets one expert."""
    return 2.0 * routed_rows * expert_params(model)


def pair_flops(model: dict) -> float:
    """Scores and values of ONE query-key pair, all heads: ``nope + rope
    + v`` multiply-adds a head (the expanded form, which the program
    runs)."""
    return 2.0 * _heads(model) * (
        model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
        + model["v_head_dim"])


def expand_flops(model: dict) -> float:
    """``W_kvb`` over one position's latent."""
    return 2.0 * model["kv_lora_rank"] * _heads(model) * (
        model["qk_nope_head_dim"] + model["v_head_dim"])


def attention_flops(model: dict, lengths: Iterable[int]) -> float:
    """What the algorithm needs over whole documents: a query at
    position t meets t + 1 keys in every sublayer (``W_kvb`` once a
    token is in ``token_matmul_params``)."""
    pairs = sum(n * (n + 1) // 2 for n in lengths)
    return pair_flops(model) * pairs * latent_sublayers(model)


def core_flops(model: dict, queries: int, cache_steps: float) -> float:
    """The cached core as a group RAN it, all sublayers: every row of
    every chunk program meets the ``cache_steps`` positions its cache
    had reached, ``queries`` queries a row, and expands them first."""
    return latent_sublayers(model) * cache_steps * (
        expand_flops(model) + queries * pair_flops(model))


def core_bytes(model: dict, queries: int, rows: float, cache_steps: float,
               cache_bytes: int = 2) -> float:
    """What the core must move: the latent rows it meets, the queries in
    bfloat16, the output in float32."""
    h = _heads(model)
    latent = model["kv_lora_rank"] + model["qk_rope_head_dim"]
    per_query = h * (model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
                     ) * 2 + h * model["v_head_dim"] * 4
    return latent_sublayers(model) * (
        cache_steps * latent * cache_bytes + rows * queries * per_query)


def encoder_flops(model: dict, valid_tokens: float, routed_rows: float,
                  lengths: Iterable[int]) -> float:
    """The whole forward for the valid tokens of whole documents."""
    return (2.0 * token_matmul_params(model) * valid_tokens
            + routed_flops(model, routed_rows)
            + attention_flops(model, lengths))
