"""Operations and bytes the hybrid (Mamba-2 + attention) encoder's
ALGORITHM needs, from shapes alone; ``model`` is the published
``config.json``'s keys. As in ``flops.py``: a matmul of ``(m, k)`` by
``(k, n)`` is ``2*m*k*n`` operations; norms, activations, the conv
(8 operations a channel) and the softmax are left out (under 1 %).

The scan is counted as the chunked algorithm runs it, with the masked
half of each ``(Q, Q)`` product left out: a chip that skipped it would
still have done all the algorithm asks.
"""

from __future__ import annotations

from typing import Iterable


def _mlp_params(model: dict) -> int:
    e, f = model["hidden_size"], model["shared_intermediate_size"]
    return e * 2 * f + f * e


def d_inner(model: dict) -> int:
    return model["mamba_n_heads"] * model["mamba_d_head"]


def mamba_matmul_params(model: dict) -> int:
    """in_proj (z, xBC, dt columns), out_proj and the layer's MLP."""
    e, di = model["hidden_size"], d_inner(model)
    cols = 2 * di + 2 * model["mamba_n_groups"] * model["mamba_d_state"] \
        + model["mamba_n_heads"]
    return e * cols + di * e + _mlp_params(model)


def attention_matmul_params(model: dict) -> int:
    """q, o (E x E), k, v (E x kv heads x head size) and the MLP."""
    e = model["hidden_size"]
    kv = model["num_key_value_heads"] * (e // model["num_attention_heads"])
    return 2 * e * e + 2 * e * kv + _mlp_params(model)


def layer_counts(model: dict) -> tuple:
    kinds = list(model["layer_types"])
    return kinds.count("mamba"), kinds.count("attention")


def matmul_params(model: dict) -> int:
    """Weights that multiply every token (the embedding is a gather)."""
    m, a = layer_counts(model)
    return m * mamba_matmul_params(model) + a * attention_matmul_params(model)


def weight_bytes(model: dict, bytes_per_weight: int = 2) -> int:
    return matmul_params(model) * bytes_per_weight


def scan_flops_per_token(model: dict, chunk_tokens: int) -> float:
    """One Mamba layer's scan, a token, in a program of ``chunk_tokens``
    tokens a row (the scan's chunk is the smaller of that and
    ``mamba_chunk_size``): the causal half of C.B^T (Q*N) and of its
    product with dt*x (Q*H*P), the chunk's state (2*H*P*N) and the
    read-out of the carried state (2*H*P*N)."""
    q = min(int(model["mamba_chunk_size"]), int(chunk_tokens))
    hp = d_inner(model)
    n = model["mamba_d_state"]
    return q * n + q * hp + 4.0 * hp * n


def scan_bytes_per_token(model: dict, in_bytes: int = 2) -> float:
    """What one Mamba layer's scan must move a token: x, B, C in the
    compute type, dt in float32, y out in float32."""
    hp, n = d_inner(model), model["mamba_d_state"]
    return (hp + 2 * n) * in_bytes + 4 * model["mamba_n_heads"] + 4 * hp


def scan_state_bytes_per_row(model: dict, state_bytes: int = 4) -> int:
    """One layer's state, read and written once a program."""
    return 2 * d_inner(model) * model["mamba_d_state"] * state_bytes


def attention_flops(model: dict, lengths: Iterable[int]) -> float:
    """All attention layers over whole documents: a query at position t
    meets t + 1 keys, ``4 * heads * head size`` operations a pair."""
    _, a = layer_counts(model)
    pairs = sum(n * (n + 1) // 2 for n in lengths)
    return 4.0 * model["hidden_size"] * pairs * a


def encoder_flops(model: dict, groups: Iterable[tuple],
                  lengths: Iterable[int]) -> float:
    """The whole forward: ``groups`` are ``(valid tokens, tokens a row
    of the program that ran them)`` pairs, ``lengths`` the documents'
    token lengths (attention meets whole documents)."""
    m, _ = layer_counts(model)
    total = attention_flops(model, lengths)
    for tokens, chunk_tokens in groups:
        total += tokens * (2.0 * matmul_params(model)
                           + m * scan_flops_per_token(model, chunk_tokens))
    return total
