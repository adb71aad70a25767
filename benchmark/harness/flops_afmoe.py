"""Operations and bytes the AFMoE encoder's ALGORITHM needs, from shapes
alone; ``model`` is the published ``config.json``'s keys as the
configuration's file holds them (``num_experts`` = the experts HELD by
this chip, ``experts_held.of`` = the router's width, ``layer_types`` the
kinds of the layers held). As in ``flops.py``: a matmul of ``(m, k)`` by
``(k, n)`` is ``2*m*k*n`` operations; norms, rotary, the gate's sigmoid,
activations, the softmax and the top-k are left out (under 1 %).

A routed expert's operations follow the rows ROUTED to it, so they are
counted from the program's counters, not from shapes; an attention
core's follow the positions attended, which a sliding layer's window
caps.
"""

from __future__ import annotations

from typing import Iterable

SLIDING, FULL = "sliding_attention", "full_attention"


def _head_width(model: dict) -> int:
    return model["num_attention_heads"] * model["head_dim"]


def _kv_width(model: dict) -> int:
    return model["num_key_value_heads"] * model["head_dim"]


def attention_params(model: dict) -> int:
    """q, k, v, the output gate and o of one layer."""
    e = model["hidden_size"]
    return e * (_head_width(model) + 2 * _kv_width(model)) \
        + 2 * e * _head_width(model)


def expert_params(model: dict) -> int:
    """One routed or shared expert: gate, up and down."""
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def router_params(model: dict) -> int:
    held = model.get("experts_held")
    return model["hidden_size"] * (
        held["of"] if held else model["num_experts"])


def dense_mlp_params(model: dict) -> int:
    return 3 * model["hidden_size"] * model["intermediate_size"]


def layer_counts(model: dict) -> tuple:
    """``(dense layers, expert layers)``."""
    dense = model["num_dense_layers"]
    return dense, model["num_hidden_layers"] - dense


def layers_of(model: dict, kind: str) -> int:
    return sum(t == kind for t in model["layer_types"])


def expert_layer_params(model: dict) -> int:
    """An expert layer as this chip holds it: the whole attention, the
    router, the shared experts and the held routed ones."""
    return (attention_params(model) + router_params(model)
            + (model["num_shared_experts"] + model["num_experts"])
            * expert_params(model))


def dense_layer_params(model: dict) -> int:
    return attention_params(model) + dense_mlp_params(model)


def held_params(model: dict) -> int:
    """Every matrix held, the slice of the embedding included."""
    dense, moe = layer_counts(model)
    return (dense * dense_layer_params(model)
            + moe * expert_layer_params(model)
            + model["vocab_size"] * model["hidden_size"])


def weight_bytes(model: dict, bytes_per_weight: int = 2) -> int:
    """What one program reads at the least: every held matrix but the
    embedding (a gather)."""
    return (held_params(model) - model["vocab_size"] * model["hidden_size"]
            ) * bytes_per_weight


def token_matmul_params(model: dict) -> int:
    """Weights that multiply EVERY valid token: the attention
    projections of every layer, the dense MLPs, each expert layer's
    router and shared experts."""
    dense, moe = layer_counts(model)
    return ((dense + moe) * attention_params(model)
            + dense * dense_mlp_params(model)
            + moe * (router_params(model)
                     + model["num_shared_experts"] * expert_params(model)))


def routed_flops(model: dict, routed_rows: float) -> float:
    """The grouped matmuls: every routed row meets one expert."""
    return 2.0 * routed_rows * expert_params(model)


def pair_flops(model: dict) -> float:
    """Scores and values of ONE query-key pair, all query heads."""
    return 2.0 * 2 * _head_width(model)


def attention_flops(model: dict, lengths: Iterable[int]) -> float:
    """What the algorithm needs over whole documents: a query at
    position t meets t + 1 keys in a full layer and ``min(t + 1,
    sliding_window)`` in a sliding one."""
    w = model["sliding_window"]
    full = windowed = 0
    for n in lengths:
        full += n * (n + 1) // 2
        m = min(n, w)
        windowed += m * (m + 1) // 2 + (n - m) * w
    return pair_flops(model) * (full * layers_of(model, FULL)
                                + windowed * layers_of(model, SLIDING))


def core_flops(model: dict, kind: str, queries: int, steps: float) -> float:
    """The cached cores of the layers of one ``kind`` as a group RAN
    them: every row of every chunk program meets the ``steps`` positions
    its cache (its ring) held, ``queries`` queries a row."""
    return layers_of(model, kind) * steps * queries * pair_flops(model)


def core_bytes(model: dict, kind: str, queries: int, rows: float,
               steps: float, cache_bytes: int = 2) -> float:
    """What those cores must move: the keys and values they meet, the
    queries in bfloat16, the output in float32."""
    per_query = _head_width(model) * (2 + 4)
    return layers_of(model, kind) * (
        steps * 2 * _kv_width(model) * cache_bytes
        + rows * queries * per_query)


def encoder_flops(model: dict, valid_tokens: float, routed_rows: float,
                  lengths: Iterable[int]) -> float:
    """The whole forward for the valid tokens of whole documents."""
    return (2.0 * token_matmul_params(model) * valid_tokens
            + routed_flops(model, routed_rows)
            + attention_flops(model, lengths))
