"""EvaByte style encoder: a byte-level dense transformer whose attention
is EVA (chunked linearized attention): every query attends exactly to
its own block of ``window_size`` bytes and, under the same softmax, to
one summary key and value for every ``chunk_size`` bytes of everything
before that block.

Published as ``model_type: evabyte``; the field names of
:class:`EvaByteConfig` are those of the model's ``config.json``.
Equations (pre-norm residual blocks, the stream and both additions
float32, ``eps`` = ``rms_norm_eps``, no biases; ``ops/eva.py`` holds the
mechanism):

    norm(x; w) = x * rsqrt(mean(x^2) + eps) * (1 + w)   (norm_add_unit_offset)
    h = E[ids]                      # 64 specials + 256 bytes
    every layer:  h += Attn(norm(h; w1));  h += MLP(norm(h; w2))
    out = norm(h; w_f)              # pooled; no LM head, no multi-byte heads

    Attn(u), H heads of d:
      q = u W_q, k = u W_k, v = u W_v; rotary (``rotate_half`` pairs) on
        all d dims of q and k at the position's absolute index; keys are
        turned BEFORE they are cached or summarised
      chunk c (positions chunk_size c ..), head h:
        a_m = softmax_m(d^-0.5 phi_h . k_m);  ksum_c = sum a_m k_m + mu_h;
        vsum_c = sum a_m v_m
      query i, block B = i // window_size: softmax at scale d^-0.5 over
        {k_j : B(j) = B, j <= i} and {ksum_c : chunk c in a block < B},
        the values v_j and vsum_c; then W_o
    MLP(u) = (silu(u W_g) * (u W_u)) W_d

What the published config does not settle is listed in the benchmark
configuration's ``assumed`` (the ``rotate_half`` pairing, keys turned
before they are pooled, the two learned vectors a head standing where
the paper samples, the specials' ids, float32 norm statistics).

A plain class, not a Flax module: it owns no parameters. The tree it
reads (``benchmark/reference/evabyte.py::init_params`` makes one from a
seed), matrices as ``(in, out)``, a dict of leaves a layer:

    embedding (V, E), final_norm (E,)
    layers/layer_<i>: norm, mlp_norm (E,); qkv (E, 3 H d): [q | k | v];
      phi, mu (H, d); o (H d, E); w_in (E, 2 F): [gate | up]; w_out (F, E)

The compute type is the type of the weights; norm statistics, rotary,
the chunk softmax, scores and softmax are float32 always.

**State carried between chunk programs: a third behaviour of
length-dependent state** (``init_states``). A layer's BLOCK cache
``(rows, H, window, d)`` twice holds position ``p`` in slot ``p %
window`` and is overwritten from slot 0 at every multiple of
``window_size``: it neither grows with the document nor slides over it,
it EMPTIES. A layer's SUMMARY cache ``(rows, H, positions / chunk_size,
d)`` twice grows at a ``chunk_size``-th of the document's rate and
becomes visible a block at a time. ``cache_positions`` answers with the
positions the summaries are allocated FOR and ``window_positions`` with
the block's slots: both are upper bounds of what a chunk program's core
meets (a query a quarter into its block meets a quarter of the block,
and a sixteenth of the positions before it), so what the cores met is
counted here, on the device (``counts``: the pairs the mask admitted).

No rule of the engine is relied on beyond the one ``ops/attention.py``
already states, "every chunk program of a group runs one length": a
program's first position is then a multiple of its length, a block
cache of whole programs (``ops/eva.py`` refuses any other) keeps every
program inside one block, and a program's own summaries stay masked
until the block has passed.
"""

from __future__ import annotations

import dataclasses
from typing import Any, ClassVar, Mapping

import jax
import jax.numpy as jnp

from code_intelligence_tpu.models.blocks import (
    Counts, config_from_dict, embed, matmul, rms_norm, rope_qk,
    split_heads, valid_lanes)
from code_intelligence_tpu.models.windowed_caches import WindowedCaches
from code_intelligence_tpu.ops import eva, mla, moe

# published switches the encoder implements one value of: a configuration
# that states another is refused, not guessed
_IMPLEMENTED = {
    "attention_class": "eva", "hidden_act": "silu", "rope_scaling": None,
    "attention_bias": False, "norm_add_unit_offset": True,
    "fp32_skip_add": True, "mixedp_attn": True, "num_chunks": None,
}


@dataclasses.dataclass(frozen=True)
class EvaByteConfig:
    architecture: ClassVar[str] = "evabyte"

    vocab_size: int = 320
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    window_size: int = 2048
    chunk_size: int = 16
    rms_norm_eps: float = 1e-5
    rope_theta: float = 100000.0
    attention_class: str = "eva"
    hidden_act: str = "silu"
    rope_scaling: Any = None
    attention_bias: bool = False
    norm_add_unit_offset: bool = True
    fp32_skip_add: bool = True
    mixedp_attn: bool = True
    num_chunks: Any = None
    # serving: positions one document's summaries are allocated for at
    # most, and the longest chunk a program runs
    kv_positions: int = 32768
    chunk_positions: int = 512
    state_dtype: Any = jnp.bfloat16    # the caches' type

    def __post_init__(self):
        object.__setattr__(self, "state_dtype", jnp.dtype(self.state_dtype))
        for key, value in _IMPLEMENTED.items():
            if getattr(self, key) != value:
                raise ValueError(
                    f"{key}={getattr(self, key)!r} is not implemented "
                    f"(only {value!r})")
        if self.num_key_value_heads != self.num_attention_heads:
            raise ValueError(
                "EVA summarises a key/value head a query head: "
                "num_key_value_heads must equal num_attention_heads")
        if self.hidden_size % self.num_attention_heads:
            raise ValueError("num_attention_heads must divide hidden_size")
        if self.window_size % self.chunk_size \
                or self.kv_positions % self.window_size:
            raise ValueError(
                "a block is whole chunks and kv_positions whole blocks: "
                f"{self.chunk_size} / {self.window_size} / "
                f"{self.kv_positions}")

    @classmethod
    def from_dict(cls, model: Mapping, **extra) -> "EvaByteConfig":
        """From a published ``config.json``'s keys."""
        return config_from_dict(cls, model, **extra)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def ring_positions(self) -> int:
        """Slots of a layer's block cache (``WindowedCaches`` caps its
        ``window_positions`` here)."""
        return self.window_size

    @property
    def sliding_layers(self):
        return (True,) * self.num_hidden_layers


def _unit_offset(w):
    """A norm weight as ``rms_norm`` multiplies by it."""
    return 1.0 + w.astype(jnp.float32)


class EvaByteEncoder(WindowedCaches):
    """The encoder contract (`models/contract.py`) over EvaByte. The
    sizes a document's state is allocated at are
    `models/windowed_caches.py`'s (``cache_positions``: the document's
    length where one program holds it, else the smallest of
    ``kv_positions`` halved that does; ``window_positions``: that, capped
    at the block); what the state holds at those sizes is this
    encoder's own."""

    # what the cores met, a head a layer (every layer and head admits
    # the same pairs: layer 0's are counted, valid lanes only; summed over
    # a group's programs in int32, which a group of 16 rows of 32,768
    # positions fills to half: 1.04e9 pairs), the
    # summaries written a head a layer, and the layers whose core ran on
    # the Pallas kernel (``eva.core_is_kernel``'s answer for the
    # program's shapes: every layer or none; 0 on the CPU)
    counts = Counts(totals=("eva_singleton_pairs", "eva_summary_pairs",
                            "eva_summaries_written"),
                    sets=("eva_kernel_layers",))

    def __init__(self, config: EvaByteConfig, dtype=jnp.bfloat16):
        self.config = config
        self.dtype = jnp.dtype(dtype)  # of the weights it will be handed
        self._inv_freq = mla.yarn_inv_freq(config.head_dim, config.rope_theta)
        self._scale = config.head_dim ** -0.5

    # -- contract --------------------------------------------------------

    @property
    def out_dim(self) -> int:
        return self.config.hidden_size

    def _allocated(self, positions):
        """``(block slots, summary slots)`` of every layer."""
        cfg = self.config
        return (self.window_positions(positions),
                -(-self.cache_positions(positions) // cfg.chunk_size))

    def init_states(self, batch: int, positions=None):
        """Zeroed block and summary caches a layer (head-major, as
        ``ops/eva.py`` reads them), one position counter for the
        lock-step group, and the counts."""
        cfg = self.config
        block, summaries = self._allocated(positions)

        def caches(slots):
            return tuple(jnp.zeros(
                (batch, cfg.num_attention_heads, slots, cfg.head_dim),
                cfg.state_dtype) for _ in range(cfg.num_hidden_layers))

        return {"k": caches(block), "v": caches(block),
                "k_sum": caches(summaries), "v_sum": caches(summaries),
                "pos": jnp.zeros((), jnp.int32),
                "counts": self.counts.zeros()}

    def state_bytes_per_row(self, max_len=None) -> int:
        """Bytes of keys, values and their summaries one row holds for a
        document of ``max_len`` positions: the block stops at
        ``window_size``, the summaries grow at a ``chunk_size``-th of the
        document's rate."""
        cfg = self.config
        return cfg.num_hidden_layers * sum(self._allocated(max_len)) * 2 \
            * cfg.hidden_size * cfg.state_dtype.itemsize

    def state_counters(self, states):
        return states["counts"]

    def counter_attrs(self, counted) -> dict:
        if not counted:
            return {}
        return {name: self.counts.total(counted, name)
                for name in self.counts.totals} | {
            "eva_kernel_layers": self.counts.total(
                counted, "eva_kernel_layers") / len(counted)}

    def encode(self, params, tokens, states, lengths=None):
        """One chunk: ``tokens`` ``(B, T)`` with the carried ``states``
        in, ``(hidden (B, T, out_dim) float32, new states)`` out; every
        chunk of a document longer than one program is ``T`` long.
        ``lengths`` ``(B,)``, where the caller knows them, are each
        row's valid tokens in this chunk: the lanes after them are
        padding, which attention never lets reach a valid token (causal
        in the block; a summary weighs a padding lane 0)."""
        cfg = self.config
        dtype = params["embedding"].dtype
        eps = cfg.rms_norm_eps
        B, T = tokens.shape
        h = embed(params, tokens)
        pos = states["pos"]
        if lengths is None:
            lengths = jnp.full((B,), T, jnp.int32)
        valid = valid_lanes(lengths, T)
        new = {name: [] for name in ("k", "v", "k_sum", "v_sum")}
        for i in range(cfg.num_hidden_layers):
            p = params["layers"][f"layer_{i}"]
            with jax.named_scope(f"attention_{i}"):
                u = rms_norm(h, _unit_offset(p["norm"]), eps).astype(dtype)
                out, caches, admitted = self._attention(
                    p, u, {name: states[name][i] for name in new}, pos,
                    valid, dtype)
            for name in new:
                new[name].append(caches[name])
            if i == 0:  # every layer's masks admit the same pairs
                met = admitted
            h = h + out
            with jax.named_scope(f"mlp_{i}"):
                m = rms_norm(h, _unit_offset(p["mlp_norm"]), eps)
                h = h + moe.swiglu(m, p["w_in"], p["w_out"], dtype)
        with jax.named_scope("final_norm"):
            out = rms_norm(h, _unit_offset(params["final_norm"]), eps)
        lanes = valid.sum(axis=0, dtype=jnp.int32)  # rows valid a lane
        chunks = valid.reshape(B, T // cfg.chunk_size, cfg.chunk_size)
        zero = jnp.zeros((), jnp.int32)
        new_states = {name: tuple(leaves) for name, leaves in new.items()}
        new_states.update(
            pos=pos + T,
            counts=self.counts.update(
                states["counts"], zero, zero, zero,
                eva_singleton_pairs=lanes @ met[0],
                eva_summary_pairs=lanes @ met[1],
                eva_summaries_written=chunks.any(axis=-1).sum(
                    dtype=jnp.int32),
                eva_kernel_layers=cfg.num_hidden_layers * eva.core_is_kernel(
                    jax.default_backend(), dtype, T,
                    states["k"][0].shape[2], states["k_sum"][0].shape[2],
                    cfg.head_dim)))
        return out, new_states

    # -- layers ----------------------------------------------------------

    def _attention(self, p, u, caches, pos, valid, dtype):
        """``Attn`` of the module's docstring over the normed ``u``:
        ``(out (b, T, E) float32, the layer's four caches, met)``."""
        cfg = self.config
        b, T, _ = u.shape
        H, d = cfg.num_attention_heads, cfg.head_dim
        with jax.named_scope("qkv_proj"):
            q, k, v = split_heads(matmul(u, p["qkv"], dtype), H, H, d)
        q, k = rope_qk(q, k, pos, self._inv_freq)
        with jax.named_scope("eva_summaries"):
            ksum, vsum = eva.chunk_summaries(
                k, v, p["phi"], p["mu"], valid, self._scale, cfg.chunk_size)
            k_sum, v_sum = eva.write_summaries(
                caches["k_sum"], caches["v_sum"], ksum, vsum, pos,
                cfg.chunk_size)
        with jax.named_scope("eva_core"):
            out, k_block, v_block, met = eva.eva_cached(
                q, k, v, caches["k"], caches["v"], caches["k_sum"],
                caches["v_sum"], pos, self._scale, cfg.window_size,
                cfg.chunk_size, mxu_dtype=dtype)
        with jax.named_scope("o_proj"):
            out = matmul(out.reshape(b, T, H * d), p["o"])
        return out, {"k": k_block, "v": v_block, "k_sum": k_sum,
                     "v_sum": v_sum}, met
