"""Operations and bytes the SmallThinker encoder's ALGORITHM needs, from
shapes alone; ``model`` is the published ``config.json``'s keys as the
configuration's file holds them (``moe_num_primary_experts`` = the
experts HELD by this chip, ``experts_held.of`` = the router's width, the
two layouts of the layers held). As in ``flops.py``: a matmul of ``(m,
k)`` by ``(k, n)`` is ``2*m*k*n`` operations; norms, rotary, the
activation, the softmaxes and the top-k are left out (under 1 %).

Every layer is the same: attention (q, k, v, o; no gate matrix), a
router, the held routed experts; no shared expert and no dense layer. A
routed expert's operations follow the rows ROUTED to it, so they are
counted from the program's counters, not from shapes; an attention
core's follow the positions attended, which a sliding layer's window
caps.
"""

from __future__ import annotations

from typing import Iterable


def _head_width(model: dict) -> int:
    return model["num_attention_heads"] * model["head_dim"]


def _kv_width(model: dict) -> int:
    return model["num_key_value_heads"] * model["head_dim"]


def attention_params(model: dict) -> int:
    """q, k, v and o of one layer."""
    return model["hidden_size"] * 2 * (_head_width(model) + _kv_width(model))


def expert_params(model: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * model["hidden_size"] * model["moe_ffn_hidden_size"]


def router_params(model: dict) -> int:
    held = model.get("experts_held")
    return model["hidden_size"] * (
        held["of"] if held else model["moe_num_primary_experts"])


def layer_params(model: dict) -> int:
    """A layer as this chip holds it: attention, the router, the held
    experts and the two norms."""
    return (attention_params(model) + router_params(model)
            + model["moe_num_primary_experts"] * expert_params(model)
            + 2 * model["hidden_size"])


def embedding_params(model: dict) -> int:
    return model["vocab_size"] * model["hidden_size"]


def held_params(model: dict) -> int:
    """Everything held: the layers, the embedding, the final norm."""
    return (model["num_hidden_layers"] * layer_params(model)
            + embedding_params(model) + model["hidden_size"])


def weight_bytes(model: dict, bytes_per_weight: int = 2) -> int:
    """What one program reads at the least: every held matrix but the
    embedding (a gather)."""
    return (held_params(model) - embedding_params(model)) * bytes_per_weight


def held_expert_bytes(model: dict, bytes_per_weight: int = 2) -> int:
    """The routed experts' matrices of all layers: what the grouped
    matmuls of one program read when every held expert gets a row."""
    return model["num_hidden_layers"] * model["moe_num_primary_experts"] \
        * expert_params(model) * bytes_per_weight


def token_matmul_params(model: dict) -> int:
    """Weights that multiply EVERY valid token: each layer's attention
    projections and router."""
    return model["num_hidden_layers"] * (
        attention_params(model) + router_params(model))


def routed_flops(model: dict, routed_rows: float) -> float:
    """The grouped matmuls: every routed row meets one expert."""
    return 2.0 * routed_rows * expert_params(model)


def pair_flops(model: dict) -> float:
    """Scores and values of ONE query-key pair, all query heads."""
    return 2.0 * 2 * _head_width(model)


def attention_flops(model: dict, lengths: Iterable[int]) -> float:
    """What the algorithm needs over whole documents: a query at
    position t meets t + 1 keys in a global layer and ``min(t + 1,
    sliding_window_size)`` in a sliding one."""
    w = model["sliding_window_size"]
    sliding = sum(model["sliding_window_layout"])
    full = windowed = 0
    for n in lengths:
        full += n * (n + 1) // 2
        m = min(n, w)
        windowed += m * (m + 1) // 2 + (n - m) * w
    return pair_flops(model) * (
        full * (model["num_hidden_layers"] - sliding) + windowed * sliding)


def encoder_flops(model: dict, valid_tokens: float, routed_rows: float,
                  lengths: Iterable[int]) -> float:
    """The whole forward for the valid tokens of whole documents."""
    return (2.0 * token_matmul_params(model) * valid_tokens
            + routed_flops(model, routed_rows)
            + attention_flops(model, lengths))
