"""The encoder contract: what ``InferenceEngine`` asks of a model.

The engine streams a document through fixed-size chunk programs and
pools the hidden states it gets back; it never looks inside the model.
Anything that offers these five names can be served on the ``groups``
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
``encode(params, tokens, states) -> (hidden (B, T, out_dim), new_states)``
    one chunk, evaluation semantics, ``new_states`` of the structure
    and shapes of ``states``. (The ISSUE called it ``apply``; Flax owns
    that name on ``AWDLSTMEncoder``.)
``state_bytes_per_row(max_len=None)``
    bytes of carried state one row holds for a document of ``max_len``
    tokens: what sets the batch once the state is large.
``cache_positions(positions=None)``
    the part of the state that grows with the document: positions of
    key/value cache a row is allocated for documents of ``positions``
    tokens; 0 where the whole state is of fixed size.

``slots`` and ``ragged`` reach into the AWD encoder's layers
(``inference/slots.py``) and take no other encoder.
"""

from __future__ import annotations

from typing import Any, Mapping, Optional, Protocol, Tuple, runtime_checkable

import jax.numpy as jnp

from code_intelligence_tpu.models.awd_lstm import AWDLSTMConfig, AWDLSTMEncoder
from code_intelligence_tpu.models.granite_hybrid import (
    GraniteHybridConfig, GraniteHybridEncoder)


@runtime_checkable
class ChunkEncoder(Protocol):
    out_dim: int

    def init_states(self, batch: int, positions: Optional[int] = None): ...

    def encode(self, params, tokens, states) -> Tuple[Any, Any]: ...

    def state_bytes_per_row(self, max_len: Optional[int] = None) -> int: ...

    def cache_positions(self, positions: Optional[int] = None) -> int: ...


def make_config(architecture: str, model: Mapping, **extra):
    """The configuration of ``architecture`` from a ``model`` mapping
    (a benchmark configuration's block, an export's ``config.json``)."""
    if architecture == AWDLSTMConfig.architecture:
        kw = dict(model, **extra)
        if "dtype" in kw:
            kw["dtype"] = jnp.dtype(kw["dtype"])
        return AWDLSTMConfig(**kw)
    if architecture == GraniteHybridConfig.architecture:
        return GraniteHybridConfig.from_dict(model, **extra)
    raise ValueError(
        f"unknown architecture {architecture!r}: "
        f"{AWDLSTMConfig.architecture!r} or "
        f"{GraniteHybridConfig.architecture!r}")


def build_encoder(config, params=None) -> ChunkEncoder:
    """The encoder ``config`` describes. The hybrid computes in the type
    of the weights it will be handed (``params``' embedding)."""
    if isinstance(config, AWDLSTMConfig):
        return AWDLSTMEncoder(config)
    if isinstance(config, GraniteHybridConfig):
        if params is None:
            return GraniteHybridEncoder(config)
        return GraniteHybridEncoder(config, dtype=params["embedding"].dtype)
    raise ValueError(f"no encoder for a {type(config).__name__}")
