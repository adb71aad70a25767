"""Operations and bytes the ALGORITHM needs, from shapes alone.

Not the compiler's count (``cost_analysis`` includes recomputation and
padding): a matmul of an ``(m, k)`` by ``(k, n)`` operand is ``2*m*k*n``
operations, and nothing else is counted (elementwise gate math is under
1 % of the matmuls at these widths). ``encoder_matmul_params`` is the
copy of ``bench.py::_flops_per_token``'s arithmetic, split so that the
serve path (encoder only) and the train path (encoder + tied decoder)
can each take their part.
"""

from __future__ import annotations


def layer_size(model: dict, layer: int) -> int:
    """Hidden size per layer: ``n_hid`` except the last, which is
    ``emb_sz`` so the decoder can tie with the embedding."""
    return model["emb_sz"] if layer == model["n_layers"] - 1 \
        else model["n_hid"]


def encoder_matmul_params(model: dict) -> int:
    """Weights that multiply every token in the encoder's recurrent
    layers (the embedding is a gather, not a matmul)."""
    total = 0
    for li in range(model["n_layers"]):
        in_dim = model["emb_sz"] if li == 0 else model["n_hid"]
        h = layer_size(model, li)
        if model.get("qrnn"):
            window = 2 if li == 0 else 1
            total += 3 * h * window * in_dim
        else:
            total += 4 * h * (in_dim + h)
    return total


def encoder_flops_per_token(model: dict) -> float:
    return 2.0 * encoder_matmul_params(model)


def lm_forward_flops_per_token(model: dict) -> float:
    """Encoder plus the (tied) decoder projection onto the vocabulary."""
    return encoder_flops_per_token(model) \
        + 2.0 * model["emb_sz"] * model["vocab_size"]


def lm_train_flops_per_token(model: dict) -> float:
    """Forward + backward = 3 x forward; recomputation does not count."""
    return 3.0 * lm_forward_flops_per_token(model)


def encoder_weight_bytes(model: dict, bytes_per_weight: int = 2) -> int:
    """Bytes of the recurrent-layer weights as the matmuls read them
    (bf16 compute: 2 bytes each)."""
    return encoder_matmul_params(model) * bytes_per_weight


def roofline_seconds(flops: float, bytes_moved: float, peaks: dict,
                     flops_key: str = "bf16_flops_per_s") -> tuple:
    """``(least seconds, "compute" | "memory")``: the larger of
    operations over peak rate and bytes over peak bandwidth."""
    t_c = flops / peaks[flops_key]
    t_m = bytes_moved / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
