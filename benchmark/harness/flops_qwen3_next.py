"""Operations and bytes the Qwen3-Next encoder's ALGORITHM needs, from
shapes alone; ``model`` is the published ``config.json``'s keys as the
configuration's file holds them (``num_experts`` = the experts HELD by
this chip, ``experts_held.of`` = the router's width). As in ``flops.py``:
a matmul of ``(m, k)`` by ``(k, n)`` is ``2*m*k*n`` operations; norms,
the conv (8 operations a channel), rotary, the gates' sigmoids,
activations, the softmaxes and the top-k are left out (under 1 %).

A routed expert's operations follow the rows ROUTED to it, so they are
counted from the program's counters, not from shapes; the attention
core's follow the positions met; the delta-rule recurrence is counted as
the chunked algorithm runs it, with the masked half of each ``(C, C)``
product left out (a chip that skipped it would still have done all the
algorithm asks) and the key-key and query-key tiles once a KEY head.
"""

from __future__ import annotations

from typing import Iterable

GDN_CHUNK = 64  # models/qwen3_next.py::_GDN_CHUNK


def layer_kinds(model: dict) -> tuple:
    """``(linear layers, softmax-attention layers)``: layer ``i`` is
    softmax attention where ``(i + 1) % full_attention_interval == 0``."""
    full = sum((i + 1) % model["full_attention_interval"] == 0
               for i in range(model["num_hidden_layers"]))
    return model["num_hidden_layers"] - full, full


def _key_width(model: dict) -> int:
    return model["linear_num_key_heads"] * model["linear_key_head_dim"]


def _value_width(model: dict) -> int:
    return model["linear_num_value_heads"] * model["linear_value_head_dim"]


def _query_width(model: dict) -> int:
    return model["num_attention_heads"] * model["head_dim"]


def _kv_width(model: dict) -> int:
    return model["num_key_value_heads"] * model["head_dim"]


def gdn_params(model: dict) -> int:
    """One linear layer: ``[q | k | v]``, ``z``, ``[b | a]`` and ``o``,
    the conv's taps, ``A_log``, ``dt_bias`` and the head norm."""
    e, conv = model["hidden_size"], 2 * _key_width(model) \
        + _value_width(model)
    heads = model["linear_num_value_heads"]
    return (e * (conv + _value_width(model) + 2 * heads)
            + conv * model["linear_conv_kernel_dim"]
            + _value_width(model) * e
            + 2 * heads + model["linear_value_head_dim"])


def attention_params(model: dict) -> int:
    """One softmax-attention layer: the doubled ``q_proj`` (a head's
    query and gate), ``k``, ``v``, ``o`` and the two head norms."""
    e = model["hidden_size"]
    return (e * (2 * _query_width(model) + 2 * _kv_width(model))
            + _query_width(model) * e + 2 * model["head_dim"])


def mixer_params(model: dict) -> int:
    linear, full = layer_kinds(model)
    return linear * gdn_params(model) + full * attention_params(model)


def expert_params(model: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def shared_params(model: dict) -> int:
    """The shared expert and its gate a token."""
    return 3 * model["hidden_size"] \
        * model["shared_expert_intermediate_size"] + model["hidden_size"]


def router_params(model: dict) -> int:
    held = model.get("experts_held")
    return model["hidden_size"] * (
        held["of"] if held else model["num_experts"])


def expert_layer_params(model: dict) -> int:
    """An expert layer as this chip holds it: the router, the gated
    shared expert and the held routed ones."""
    return (router_params(model) + shared_params(model)
            + model["num_experts"] * expert_params(model))


def embedding_params(model: dict) -> int:
    return model["vocab_size"] * model["hidden_size"]


def norm_params(model: dict) -> int:
    """Two norms a layer and the final one."""
    return (2 * model["num_hidden_layers"] + 1) * model["hidden_size"]


def held_params(model: dict) -> int:
    """Everything held, to the unit: the mixers, every layer's expert
    layer, the embedding's slice and the norms."""
    return (mixer_params(model)
            + model["num_hidden_layers"] * expert_layer_params(model)
            + embedding_params(model) + norm_params(model))


def weight_bytes(model: dict, bytes_per_weight: int = 2) -> int:
    """What one program reads at the least: every held weight but the
    embedding (a gather)."""
    return (held_params(model) - embedding_params(model)) * bytes_per_weight


def held_expert_bytes(model: dict, bytes_per_weight: int = 2) -> int:
    """The held experts' matrices of all layers: what the grouped
    matmuls of one program read when every held expert gets a row."""
    return model["num_hidden_layers"] * model["num_experts"] \
        * expert_params(model) * bytes_per_weight


def token_matmul_params(model: dict) -> int:
    """Weights that multiply EVERY valid token: the mixers' projections
    and each layer's router, shared expert and its gate."""
    e = model["hidden_size"]
    conv = 2 * _key_width(model) + _value_width(model)
    linear, full = layer_kinds(model)
    return (linear * (e * (conv + _value_width(model)
                           + 2 * model["linear_num_value_heads"])
                      + _value_width(model) * e)
            + full * (e * (2 * _query_width(model) + 2 * _kv_width(model))
                      + _query_width(model) * e)
            + model["num_hidden_layers"] * (
                router_params(model) + shared_params(model)))


def routed_flops(model: dict, routed_rows: float) -> float:
    """The grouped matmuls: every routed row meets one expert."""
    return 2.0 * routed_rows * expert_params(model)


def gdn_flops_per_token(model: dict, chunk: int = GDN_CHUNK) -> float:
    """One linear layer's recurrence, a token (a lane-step): a KEY head's
    causal halves of the key-key and the query-key tile (``C * dk``
    each); a VALUE head's forward substitution for what the chunk writes
    and the causal half of the tile times it (``C * dv`` each) and its
    three products with the state (``K S``, ``Q S``, ``K^T D``: ``2 * dk
    * dv`` each)."""
    dk, dv = model["linear_key_head_dim"], model["linear_value_head_dim"]
    return (model["linear_num_key_heads"] * 2.0 * chunk * dk
            + model["linear_num_value_heads"] * (
                2.0 * chunk * dv + 6.0 * dk * dv))


def gdn_bytes_per_token(model: dict, in_bytes: int = 2) -> float:
    """What one linear layer's recurrence must move a token: q, k, v in
    the compute type, the decay and beta of each value head in float32,
    o out in float32."""
    return ((2 * _key_width(model) + _value_width(model)) * in_bytes
            + 8 * model["linear_num_value_heads"]
            + 4 * _value_width(model))


def gdn_state_bytes_per_row(model: dict, state_bytes: int = 4) -> int:
    """One layer's matrix state, read and written once a program."""
    return 2 * _value_width(model) * model["linear_key_head_dim"] \
        * state_bytes


def pair_flops(model: dict) -> float:
    """Scores and values of ONE query-key pair, all query heads."""
    return 2.0 * 2 * _query_width(model)


def attention_flops(model: dict, lengths: Iterable[int]) -> float:
    """The softmax-attention layers over whole documents: a query at
    position t meets t + 1 keys."""
    pairs = sum(n * (n + 1) // 2 for n in lengths)
    return pair_flops(model) * pairs * layer_kinds(model)[1]


def core_flops(model: dict, queries: int, cache_steps: float) -> float:
    """The cached cores as a group RAN them, all softmax-attention
    layers: every row of every chunk program meets the ``cache_steps``
    positions its cache had reached, ``queries`` queries a row."""
    return layer_kinds(model)[1] * cache_steps * queries * pair_flops(model)


def core_bytes(model: dict, queries: int, rows: float, cache_steps: float,
               cache_bytes: int = 2) -> float:
    """What those cores must move: the keys and values they meet, the
    queries in bfloat16, the output in float32."""
    per_query = _query_width(model) * (2 + 4)
    return layer_kinds(model)[1] * (
        cache_steps * 2 * _kv_width(model) * cache_bytes
        + rows * queries * per_query)


def encoder_flops(model: dict, valid_tokens: float, routed_rows: float,
                  lengths: Iterable[int]) -> float:
    """The whole forward for the valid tokens of whole documents."""
    return (valid_tokens * (2.0 * token_matmul_params(model)
                            + layer_kinds(model)[0]
                            * gdn_flops_per_token(model))
            + routed_flops(model, routed_rows)
            + attention_flops(model, lengths))
