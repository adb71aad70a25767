"""Operations and bytes the EvaByte encoder's ALGORITHM needs, from
shapes alone; ``model`` is the published ``config.json``'s keys as the
configuration's file holds them. As in ``flops.py``: a matmul of ``(m,
k)`` by ``(k, n)`` is ``2*m*k*n`` operations; norms, rotary, the SiLU and
the softmaxes are left out (under 1 %).

The joint core's operations follow the query-key pairs its mask ADMITS
(a query's own block up to itself, one summary a chunk of every block
before), so they are counted from the program's counters
(``eva_singleton_pairs`` + ``eva_summary_pairs``: a head a layer), not
from shapes: a core that visits more than it admits (whole key blocks,
a block's masked half) has still done no more than the algorithm asks.
"""

from __future__ import annotations


def _width(model: dict) -> int:
    """All heads of a position's queries (keys, values): 32 x 128."""
    return model["hidden_size"]


def layer_params(model: dict) -> int:
    """One layer: q, k, v, o; the SwiGLU's gate, up and down; two norms;
    ``phi`` and ``mu`` a head."""
    e, f = model["hidden_size"], model["intermediate_size"]
    return 4 * e * e + 3 * e * f + 2 * e + 2 * _width(model)


def embedding_params(model: dict) -> int:
    return model["vocab_size"] * model["hidden_size"]


def held_params(model: dict) -> int:
    """Everything held, to the unit: the layers, the embedding and the
    final norm (no LM head, no multi-byte heads)."""
    return (model["num_hidden_layers"] * layer_params(model)
            + embedding_params(model) + model["hidden_size"])


def weight_bytes(model: dict, bytes_per_weight: int = 2) -> int:
    """What one program reads at the least: every held weight but the
    embedding (a gather)."""
    return (held_params(model) - embedding_params(model)) * bytes_per_weight


def layer_matmul_params(model: dict) -> int:
    """Weights that multiply EVERY position of a layer: four projections
    and one SwiGLU (2 x this = 404,750,336 operations a position)."""
    e, f = model["hidden_size"], model["intermediate_size"]
    return 4 * e * e + 3 * e * f


def pair_flops(model: dict) -> float:
    """Scores and values of ONE admitted query-key pair, all heads."""
    return 2.0 * 2 * _width(model)


def summaries_flops_per_position(model: dict) -> float:
    """One layer's chunk summaries, a position: ``phi . k`` and the two
    weighted sums, every head."""
    return 3 * 2.0 * _width(model)


def summaries_bytes_per_position(model: dict, in_bytes: int = 2,
                                 state_bytes: int = 2) -> float:
    """What one layer's summaries must move a position: k and v in the
    compute type in, a ``chunk_size``-th of a summary key and value out."""
    return 2 * _width(model) * (
        in_bytes + state_bytes / model["chunk_size"])


def core_flops(model: dict, pairs: float) -> float:
    """One layer's joint core for ``pairs`` admitted pairs a head."""
    return pairs * pair_flops(model)


def core_bytes(model: dict, pairs: float, queries: int, lane_steps: float,
               cache_bytes: int = 2) -> float:
    """What one layer's core must move at the least: the keys and values
    a program reads (its ``queries`` queries share them: at the least
    the mean of what its queries met, ``pairs / queries`` keys of either
    kind, a key and a value of every head each), the queries in bfloat16
    and the output in float32 for the lane-steps run."""
    return (pairs / queries * 2 * _width(model) * cache_bytes
            + lane_steps * _width(model) * (2 + 4))


def forward_flops(model: dict, lane_steps: float, pairs: float) -> float:
    """The whole forward for the lane-steps RUN (padding lanes included:
    a matmul cannot skip a lane the batcher gave it) and the pairs the
    cores admitted."""
    layers = model["num_hidden_layers"]
    return layers * (
        lane_steps * (2.0 * layer_matmul_params(model)
                      + summaries_flops_per_position(model))
        + core_flops(model, pairs))
