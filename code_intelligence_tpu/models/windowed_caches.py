"""Key/value state of two kinds in one encoder, and its arithmetic: the
part of the encoder contract (`models/contract.py`) that the encoders
whose attention layers are GLOBAL (keys and values grow with the
document) or SLIDING (a ring of the window + one chunk) share. One copy:
what the engine allocates, counts and narrows follows these lines for
every such model.

An encoder that mixes this in has ``self.config`` with ``kv_positions``,
``chunk_positions``, ``ring_positions``, ``sliding_layers`` (a bool a
layer), ``num_key_value_heads``, ``head_dim`` and ``state_dtype``, and
says which counts it carries (``counts``, a `models/blocks.py::Counts`).
"""

from __future__ import annotations

import jax.numpy as jnp


def ring_positions(window: int, chunk: int) -> int:
    """Slots of a sliding layer's ring: the whole chunks that hold what
    a chunk's queries can see of the chunks before, and the chunk
    itself."""
    return chunk * (-(-window // chunk) + 1)


class WindowedCaches:
    def cache_positions(self, positions=None) -> int:
        """Positions a global layer's cache is allocated at for documents
        of up to ``positions`` tokens: their own length where one chunk
        holds them, else the smallest of ``kv_positions`` halved that
        does, so that the groups of a call compile a few cache sizes and
        not one a length."""
        cfg = self.config
        if positions is None:
            return cfg.kv_positions
        if positions > cfg.kv_positions:
            raise ValueError(
                f"a document of {positions} positions does not fit the "
                f"key/value cache of kv_positions={cfg.kv_positions}")
        if positions <= cfg.chunk_positions:
            return positions
        size = cfg.kv_positions
        while size % 2 == 0 and size // 2 >= positions:
            size //= 2
        return size

    def window_positions(self, positions=None) -> int:
        """Slots a sliding layer's ring is allocated: the global layers'
        allocation until that passes the window + one chunk, the ring's
        length from there on."""
        if not any(self.config.sliding_layers):
            return 0
        return min(self.cache_positions(positions),
                   self.config.ring_positions)

    def _slots(self, positions):
        """Each layer's cache length."""
        full, ring = (self.cache_positions(positions),
                      self.window_positions(positions))
        return [ring if sliding else full
                for sliding in self.config.sliding_layers]

    def init_states(self, batch: int, positions=None):
        """Zeroed keys and values a layer (head-major, as
        ``ops/attention.py`` reads them), one position counter for the
        lock-step group, and the encoder's counts."""
        cfg = self.config

        def caches():
            return tuple(jnp.zeros(
                (batch, cfg.num_key_value_heads, slots, cfg.head_dim),
                cfg.state_dtype) for slots in self._slots(positions))

        return {"k": caches(), "v": caches(),
                "pos": jnp.zeros((), jnp.int32),
                "counts": self.counts.zeros()}

    def state_bytes_per_row(self, max_len=None) -> int:
        """Bytes of keys and values one row holds for a document of
        ``max_len`` tokens: the global layers' part grows with it, the
        sliding layers' stops at the ring."""
        cfg = self.config
        return sum(self._slots(max_len)) * 2 * cfg.num_key_value_heads \
            * cfg.head_dim * cfg.state_dtype.itemsize
