"""Operations and bytes the bailing-hybrid encoder's ALGORITHM needs, from
shapes alone; ``model`` is the published ``config.json``'s keys as the
configuration's file holds them (``num_experts`` = the experts HELD by
this chip, ``experts_held.of`` = the router's width). As in ``flops.py``:
a matmul of ``(m, k)`` by ``(k, n)`` is ``2*m*k*n`` operations; norms,
the conv (8 operations a channel), rotary, gates' sigmoids, activations,
the softmax and the top-k are left out (under 1 %).

A routed expert's operations follow the rows ROUTED to it, so they are
counted from the program's counters, not from shapes; the latent core's
follow the positions attended; the delta-rule recurrence is counted as
the chunked algorithm runs it, with the masked half of each ``(C, C)``
product left out (a chip that skipped it would still have done all the
algorithm asks).
"""

from __future__ import annotations

from typing import Iterable

KDA_CHUNK = 64  # models/bailing_hybrid.py::_KDA_CHUNK


def _kda_width(model: dict) -> int:
    return model["num_attention_heads"] * model["head_dim"]


def layer_kinds(model: dict) -> tuple:
    """``(linear layers, latent layers)``: layer ``i`` is latent where
    ``(i + 1) % layer_group_size == 0``."""
    latent = sum((i + 1) % model["layer_group_size"] == 0
                 for i in range(model["num_hidden_layers"]))
    return model["num_hidden_layers"] - latent, latent


def layer_counts(model: dict) -> tuple:
    """``(dense layers, expert layers)``."""
    dense = model["first_k_dense_replace"]
    return dense, model["num_hidden_layers"] - dense


def kda_params(model: dict) -> int:
    """q, k, v, the decay gate, the output gate, beta and o of one
    linear layer (conv taps, ``A_log``, ``dt_bias`` and the head norm
    are under 0.1 %)."""
    e, d = model["hidden_size"], _kda_width(model)
    return e * (3 * d + 2 * d + model["num_attention_heads"]) + d * e


def mla_params(model: dict) -> int:
    """q (one matrix), kv_a, kv_b, the head-wise gate and o of one
    latent layer."""
    e, h = model["hidden_size"], model["num_attention_heads"]
    nope, rope, v = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                     model["v_head_dim"])
    return (e * h * (nope + rope) + e * (model["kv_lora_rank"] + rope)
            + model["kv_lora_rank"] * h * (nope + v) + e * h + h * v * e)


def mixer_params(model: dict) -> int:
    """The mixers of every layer held."""
    linear, latent = layer_kinds(model)
    return linear * kda_params(model) + latent * mla_params(model)


def expert_params(model: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def shared_params(model: dict) -> int:
    return 3 * model["hidden_size"] * model["num_shared_experts"] \
        * model["moe_shared_expert_intermediate_size"]


def router_params(model: dict) -> int:
    held = model.get("experts_held")
    return model["hidden_size"] * (
        held["of"] if held else model["num_experts"])


def dense_mlp_params(model: dict) -> int:
    return 3 * model["hidden_size"] * model["intermediate_size"]


def held_params(model: dict) -> int:
    """Every matrix held, the slice of the embedding included."""
    dense, moe = layer_counts(model)
    return (mixer_params(model) + dense * dense_mlp_params(model)
            + moe * (router_params(model) + shared_params(model)
                     + model["num_experts"] * expert_params(model))
            + model["vocab_size"] * model["hidden_size"])


def weight_bytes(model: dict, bytes_per_weight: int = 2) -> int:
    """What one program reads at the least: every held matrix but the
    embedding (a gather)."""
    return (held_params(model) - model["vocab_size"] * model["hidden_size"]
            ) * bytes_per_weight


def token_matmul_params(model: dict) -> int:
    """Weights that multiply EVERY valid token: the mixers' projections
    (``W_ukv`` once a token among them), the dense MLPs, each expert
    layer's router and shared expert."""
    dense, moe = layer_counts(model)
    return (mixer_params(model) + dense * dense_mlp_params(model)
            + moe * (router_params(model) + shared_params(model)))


def routed_flops(model: dict, routed_rows: float) -> float:
    """The grouped matmuls: every routed row meets one expert."""
    return 2.0 * routed_rows * expert_params(model)


def kda_flops_per_token(model: dict, chunk: int = KDA_CHUNK) -> float:
    """One linear layer's recurrence, a token: the causal half of the
    key-key tile, of the query-key tile and of that tile times what the
    chunk writes (``C * d`` each a head), the forward substitution of
    ``[W | U]`` (``2 * C * d``), and the three products with the state
    (``W S``, ``Q S``, ``K^T D``: ``2 * d * d`` each)."""
    d = model["head_dim"]
    return model["num_attention_heads"] * (5.0 * chunk * d + 6.0 * d * d)


def kda_bytes_per_token(model: dict, in_bytes: int = 2) -> float:
    """What one linear layer's recurrence must move a token: q, k, v in
    the compute type, the decays and beta in float32, o out in float32."""
    w = _kda_width(model)
    return 3 * w * in_bytes + 4 * w + 4 * model["num_attention_heads"] \
        + 4 * w


def kda_state_bytes_per_row(model: dict, state_bytes: int = 4) -> int:
    """One layer's matrix state, read and written once a program."""
    return 2 * _kda_width(model) * model["head_dim"] * state_bytes


def pair_flops(model: dict) -> float:
    """Scores and values of ONE query-key pair, all heads of a latent
    layer (the expanded form, which the program runs)."""
    return 2.0 * model["num_attention_heads"] * (
        model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
        + model["v_head_dim"])


def attention_flops(model: dict, lengths: Iterable[int]) -> float:
    """The latent layers over whole documents: a query at position t
    meets t + 1 keys."""
    pairs = sum(n * (n + 1) // 2 for n in lengths)
    return pair_flops(model) * pairs * layer_kinds(model)[1]


def encoder_flops(model: dict, valid_tokens: float, routed_rows: float,
                  lengths: Iterable[int]) -> float:
    """The whole forward for the valid tokens of whole documents."""
    return (valid_tokens * (2.0 * token_matmul_params(model)
                            + layer_kinds(model)[0]
                            * kda_flops_per_token(model))
            + routed_flops(model, routed_rows)
            + attention_flops(model, lengths))
