"""The encoder contract: what ``InferenceEngine`` asks of a model.

The engine streams a document through fixed-size chunk programs and
pools the hidden states it gets back; it never looks inside the model.
Anything that offers these eight names can be served on the ``groups``
path (``embed_issues``, ``embed_ids_batch``, ``embed_text``, the server
with ``--scheduler groups``):

``out_dim``
    width of a hidden state; a served row is ``3 * out_dim`` wide.
``init_states(batch, positions=None)``
    the zero state of ``batch`` rows, as a pytree, for documents of up
    to ``positions`` tokens (``None``: the longest the encoder takes).
    Only an encoder whose state GROWS with the document (a key/value
    cache) reads ``positions``; it raises ``ValueError`` for a document
    it cannot hold.
``encode(params, tokens, states, lengths=None) -> (hidden (B, T, out_dim), new_states)``
    one chunk, evaluation semantics, ``new_states`` of the structure
    and shapes of ``states``. ``lengths`` ``(B,)`` are each row's valid
    tokens in this chunk (the lanes after them are padding, a padding
    row's are all padding): the engine hands them to every encoder that
    names the parameter; an encoder whose work does not depend on them
    ignores them. (The ISSUE called it ``apply``; Flax owns that name on
    ``AWDLSTMEncoder``.)
``state_bytes_per_row(max_len=None)``
    bytes of carried state one row holds for a document of ``max_len``
    tokens: what sets the batch once the state is large.
``cache_positions(positions=None)`` and ``window_positions(positions=None)``
    the two kinds of state that depend on the document's length, each as
    the positions a row is allocated for documents of ``positions``
    tokens. The first is the cache that GROWS with the document (keys
    and values of layers that attend to all of it, a latent cache;
    where a row holds two such caches of different widths a layer, a
    latent cache and the index keys a learned selection scores
    (`models/glm_moe_dsa.py`), both are written a position a token and
    this is the one length of both); the
    second the RING of layers that attend under a sliding window, which
    grows like the first until it holds the window and one chunk and
    then stops: a chunk program's cores meet ``min(positions reached,
    allocated)`` of each. 0 where an encoder has no state of that kind
    (both, where the whole state is of fixed size). One row may hold
    both, layer by layer; the engine counts each and looks inside
    neither. For a state that is neither, both answers are UPPER
    BOUNDS: an encoder whose block of a window's slots EMPTIES at every
    multiple of the window, beside summaries that grow at a fraction of
    the document's rate and become visible a block at a time
    (`models/evabyte.py`), answers the second with the block's slots
    and the first with the positions the summaries are allocated FOR
    (their slots times the chunk they summarise), so that the engine's
    allocation, its in-flight bound and ``state_bytes_per_row`` hold;
    an encoder whose every query attends only the positions a learned
    indexer selects answers the first with what it allocates and scores
    (the positions reached) though its core admits ``min(reached,
    index_topk)`` of them;
    such cores meet less than ``min(positions reached, allocated)`` of
    either (a query a quarter into its block meets a quarter of the
    block, and one summary for every chunk before it), and what they met
    is what the encoder counts on the device (``state_counters``).
``state_counters(states)`` and ``counter_attrs(counted)``
    counts the encoder keeps ON THE DEVICE in its carried state (rows
    routed to experts): the first picks them out of a group's last
    ``new_states`` (a device array, ``None`` where the encoder counts
    nothing), the second turns the fetched ones of a flush's groups into
    attributes of its ``engine.finalize`` span. Traced calls only; the
    fetch rides the pooled rows'.

``slots`` and ``ragged`` reach into the AWD encoder's layers
(``inference/slots.py``) and take no other encoder.
"""

from __future__ import annotations

from typing import Any, Mapping, Optional, Protocol, Tuple, runtime_checkable

import jax.numpy as jnp

from code_intelligence_tpu.models.afmoe import AfmoeConfig, AfmoeEncoder
from code_intelligence_tpu.models.awd_lstm import AWDLSTMConfig, AWDLSTMEncoder
from code_intelligence_tpu.models.bailing_hybrid import (
    BailingHybridConfig, BailingHybridEncoder)
from code_intelligence_tpu.models.deepseek_v3 import (
    DeepseekV3Config, DeepseekV3Encoder)
from code_intelligence_tpu.models.evabyte import EvaByteConfig, EvaByteEncoder
from code_intelligence_tpu.models.glm_moe_dsa import (
    GlmMoeDsaConfig, GlmMoeDsaEncoder)
from code_intelligence_tpu.models.granite_hybrid import (
    GraniteHybridConfig, GraniteHybridEncoder)
from code_intelligence_tpu.models.longcat_flash import (
    LongcatFlashConfig, LongcatFlashEncoder)
from code_intelligence_tpu.models.qwen3_next import (
    Qwen3NextConfig, Qwen3NextEncoder)
from code_intelligence_tpu.models.smallthinker import (
    SmallThinkerConfig, SmallThinkerEncoder)


@runtime_checkable
class ChunkEncoder(Protocol):
    out_dim: int

    def init_states(self, batch: int, positions: Optional[int] = None): ...

    def encode(self, params, tokens, states,
               lengths=None) -> Tuple[Any, Any]: ...

    def state_bytes_per_row(self, max_len: Optional[int] = None) -> int: ...

    def cache_positions(self, positions: Optional[int] = None) -> int: ...

    def window_positions(self, positions: Optional[int] = None) -> int: ...

    def state_counters(self, states): ...

    def counter_attrs(self, counted) -> Mapping[str, Any]: ...


def _awd_config(model: Mapping, **extra) -> AWDLSTMConfig:
    kw = dict(model, **extra)
    if "dtype" in kw:
        kw["dtype"] = jnp.dtype(kw["dtype"])
    return AWDLSTMConfig(**kw)


def _in_weights_dtype(encoder_cls):
    """An encoder that computes in the type of the weights it will be
    handed (``params``' embedding)."""
    def build(config, params=None):
        if params is None:
            return encoder_cls(config)
        return encoder_cls(config, dtype=params["embedding"].dtype)
    return build


# architecture -> (configuration class, configuration from a mapping,
# encoder from a configuration and the weights)
ENCODERS = {
    AWDLSTMConfig.architecture: (
        AWDLSTMConfig, _awd_config, lambda config, params=None:
        AWDLSTMEncoder(config)),
    GraniteHybridConfig.architecture: (
        GraniteHybridConfig, GraniteHybridConfig.from_dict,
        _in_weights_dtype(GraniteHybridEncoder)),
    DeepseekV3Config.architecture: (
        DeepseekV3Config, DeepseekV3Config.from_dict,
        _in_weights_dtype(DeepseekV3Encoder)),
    AfmoeConfig.architecture: (
        AfmoeConfig, AfmoeConfig.from_dict, _in_weights_dtype(AfmoeEncoder)),
    BailingHybridConfig.architecture: (
        BailingHybridConfig, BailingHybridConfig.from_dict,
        _in_weights_dtype(BailingHybridEncoder)),
    SmallThinkerConfig.architecture: (
        SmallThinkerConfig, SmallThinkerConfig.from_dict,
        _in_weights_dtype(SmallThinkerEncoder)),
    LongcatFlashConfig.architecture: (
        LongcatFlashConfig, LongcatFlashConfig.from_dict,
        _in_weights_dtype(LongcatFlashEncoder)),
    Qwen3NextConfig.architecture: (
        Qwen3NextConfig, Qwen3NextConfig.from_dict,
        _in_weights_dtype(Qwen3NextEncoder)),
    EvaByteConfig.architecture: (
        EvaByteConfig, EvaByteConfig.from_dict,
        _in_weights_dtype(EvaByteEncoder)),
    GlmMoeDsaConfig.architecture: (
        GlmMoeDsaConfig, GlmMoeDsaConfig.from_dict,
        _in_weights_dtype(GlmMoeDsaEncoder)),
}


def make_config(architecture: str, model: Mapping, **extra):
    """The configuration of ``architecture`` from a ``model`` mapping
    (a benchmark configuration's block, an export's ``config.json``)."""
    if architecture not in ENCODERS:
        raise ValueError(
            f"unknown architecture {architecture!r}: one of "
            f"{sorted(ENCODERS)}")
    return ENCODERS[architecture][1](model, **extra)


def build_encoder(config, params=None) -> ChunkEncoder:
    """The encoder ``config`` describes."""
    entry = ENCODERS.get(getattr(type(config), "architecture", None))
    if entry is None or not isinstance(config, entry[0]):
        raise ValueError(
            f"no encoder for a {type(config).__name__}: the configurations "
            f"of {sorted(ENCODERS)}")
    return entry[2](config, params)
