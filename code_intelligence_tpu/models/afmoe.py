"""AFMoE style encoder (Arcee Trinity): grouped-query attention whose
layers are of two kinds, sliding-window and global, with per-head QK
norms and a sigmoid output gate; four norms a layer; a dense SwiGLU MLP
in the leading layers and, after them, sigmoid-routed experts with a
shared one, of which this chip holds a SHARE.

Published as ``model_type: afmoe``; the field names of
:class:`AfmoeConfig` are those of the model's ``config.json``. Equations
(``x`` the float32 residual, ``eps`` = ``rms_norm_eps``, no biases but
the router's; ``ops/attention.py`` and ``ops/moe.py`` hold the two
mechanisms, and a model with DeepSeek-V3's router shares the second):

    x = E[ids] * sqrt(hidden_size)                     (mup_enabled)
    every layer:  x += RMSNorm(Attn(RMSNorm(x)));  x += RMSNorm(FFN(RMSNorm(x)))
    out = RMSNorm(x)                                   # pooled; no LM head

    Attn(a): q = RMSNorm_d(a W_q), k = RMSNorm_d(a W_k), v = a W_v  (a head)
      a sliding layer: rotary on q and k (all ``head_dim`` dims, plain
        inverse frequencies, ``rotate_half`` pairs), and a query at t
        sees the keys t - sliding_window < j <= t; a full layer: no
        rotary, every key j <= t
      o = softmax(q.k / sqrt(head_dim)) v;  o = o * sigmoid(a W_gate)
      Attn = o W_o
    FFN, layers < num_dense_layers: SwiGLU of intermediate_size
    FFN, the others: sum_i w_i E_i(m) + E_shared(m), the experts SwiGLU
      of moe_intermediate_size, (i, w_i) from the router (ops/moe.route
      with one group: sigmoid scores, the bias moves the choice only,
      the chosen scores normalised and times route_scale)

**The share.** ``experts_held = (first, count)`` says which of the
``num_experts`` experts this chip holds, as ``models/deepseek_v3.py``
has it: the router keeps all its outputs and ``num_experts_per_tok``;
the sum runs over the chosen experts that are held, plus the shared one.

A plain class, not a Flax module: it owns no parameters. The tree it
reads (``benchmark/reference/afmoe.py::init_params`` makes one from a
seed), matrices as ``(in, out)``, a dict of leaves a layer:

    embedding (V, E), final_norm (E,)
    layers/layer_<i>, every layer: input_norm, post_attn_norm,
      pre_mlp_norm, post_mlp_norm (E,); qkv (E, (Hq + 2 Hkv) d):
      [q | k | v]; q_norm, k_norm (d,); gate (E, Hq d); o (Hq d, E)
    a dense layer besides: w_in (E, 2 F), w_out (F, E)  ([gate | up])
    an expert layer besides: router (E, num_experts), bias
      (num_experts,) float32, shared_in (E, 2 Fs), shared_out (Fs, E),
      experts_in (count, E, 2 Fe), experts_out (count, Fe, E): the HELD
      experts alone

The compute type is the type of the weights; RMSNorm statistics, rotary,
softmax, the gate's sigmoid and the router are float32 always.

**State carried between chunk programs, of two kinds** (``init_states``):
a full layer's keys and values GROW with the document and are allocated
for all of it (``cache_positions``); a sliding layer's live in a RING of
``sliding_window`` (in whole chunks) + ``chunk_positions`` slots that
later chunks overwrite (``window_positions``), so a row's state for such
a layer stops growing at the window. Beside them one position counter and the expert layers'
counts (``state_counters``).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, ClassVar, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp

from code_intelligence_tpu.models.blocks import (
    CarriedCounts, Counts, config_from_dict, embed, held_experts, matmul,
    rms_norm, rope_qk, split_heads, valid_lanes)
from code_intelligence_tpu.models.windowed_caches import (
    WindowedCaches, ring_positions)
from code_intelligence_tpu.ops import attention, mla, moe

SLIDING, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class AfmoeConfig:
    architecture: ClassVar[str] = "afmoe"

    vocab_size: int
    hidden_size: int = 3072
    intermediate_size: int = 12288
    moe_intermediate_size: int = 3072
    num_hidden_layers: int = 60
    num_dense_layers: int = 6
    layer_types: Tuple[str, ...] = ()
    num_attention_heads: int = 48
    num_key_value_heads: int = 8
    head_dim: int = 128
    sliding_window: int = 4096
    num_experts: int = 256             # the router's outputs
    num_shared_experts: int = 1
    num_experts_per_tok: int = 4
    n_group: int = 1
    topk_group: int = 1
    route_norm: bool = True
    route_scale: float = 2.448
    score_func: str = "sigmoid"
    mup_enabled: bool = True
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    rope_scaling: Any = None
    # the share: (first expert held, how many), None = all of them
    experts_held: Optional[Tuple[int, int]] = None
    # serving: positions one document's growing cache can hold, and the
    # longest chunk a program runs (a ring holds the window and one chunk)
    kv_positions: int = 16384
    chunk_positions: int = 512
    state_dtype: Any = jnp.bfloat16    # the caches' type

    def __post_init__(self):
        object.__setattr__(self, "experts_held", held_experts(
            self.experts_held, self.num_experts))
        object.__setattr__(self, "state_dtype", jnp.dtype(self.state_dtype))
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if len(self.layer_types) != self.num_hidden_layers or set(
                self.layer_types) - {SLIDING, FULL}:
            raise ValueError(
                f"layer_types must name {self.num_hidden_layers} layers, "
                f"each {SLIDING!r} or {FULL!r}: {self.layer_types}")
        if self.score_func != "sigmoid" or self.rope_scaling is not None:
            raise ValueError(
                "only score_func 'sigmoid' and plain rotary (rope_scaling "
                f"null) are implemented, not {self.score_func!r} / "
                f"{self.rope_scaling!r}")
        if self.num_experts % self.n_group:
            raise ValueError("n_group must divide num_experts")
        if not 0 <= self.num_dense_layers <= self.num_hidden_layers:
            raise ValueError("num_dense_layers exceeds the layers")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                "num_key_value_heads must divide num_attention_heads")

    @classmethod
    def from_dict(cls, model: Mapping, **extra) -> "AfmoeConfig":
        """From a published ``config.json``'s keys; of a share, its
        ``num_experts`` counts the experts HELD."""
        return config_from_dict(cls, model, "num_experts", **extra)

    @property
    def n_moe_layers(self) -> int:
        return self.num_hidden_layers - self.num_dense_layers

    @property
    def ring_positions(self) -> int:
        """Slots of a sliding layer's ring."""
        return ring_positions(self.sliding_window, self.chunk_positions)

    @property
    def sliding_layers(self) -> Tuple[bool, ...]:
        return tuple(kind == SLIDING for kind in self.layer_types)

    def count(self, kind: str) -> int:
        return sum(t == kind for t in self.layer_types)


class AfmoeEncoder(WindowedCaches, CarriedCounts):
    """The encoder contract (`models/contract.py`) over AFMoE; its two
    kinds of caches and their arithmetic (``cache_positions``,
    ``window_positions``, ``init_states``, ``state_bytes_per_row``) are
    `models/windowed_caches.py`'s, the reading of its counts
    `models/blocks.py`'s."""

    # the attention layers whose core the program ran on the Pallas
    # kernel, and the expert layers whose grouped matmuls it did
    counts = Counts(sets=("attention_kernel_layers",
                          "expert_kernel_layers"))

    def __init__(self, config: AfmoeConfig, dtype=jnp.bfloat16):
        self.config = config
        self.dtype = jnp.dtype(dtype)  # of the weights it will be handed
        self._inv_freq = mla.yarn_inv_freq(config.head_dim, config.rope_theta)
        self._scale = config.head_dim ** -0.5

    # -- contract --------------------------------------------------------

    @property
    def out_dim(self) -> int:
        return self.config.hidden_size

    def encode(self, params, tokens, states, lengths=None):
        """One chunk: ``tokens`` ``(B, T)`` with the carried ``states``
        in, ``(hidden (B, T, out_dim) float32, new states)`` out. Every
        chunk of a document must be ``T`` long once it is longer than a
        ring (``ops/attention.py``). ``lengths`` ``(B,)``, where the
        caller knows them, are each row's valid tokens in this chunk:
        the lanes after them are padding, which attention never lets
        reach a valid token (causal) and which is then not routed to any
        expert."""
        cfg = self.config
        dtype = params["embedding"].dtype
        eps = cfg.rms_norm_eps
        B, T = tokens.shape
        h = embed(params, tokens, math.sqrt(cfg.hidden_size)
                  if cfg.mup_enabled else None)
        pos = states["pos"]
        valid = None if lengths is None else \
            valid_lanes(lengths, T).reshape(-1)
        k_caches, v_caches = [], []
        rows = busiest = jnp.zeros((), jnp.int32)
        for i, kind in enumerate(cfg.layer_types):
            p = params["layers"][f"layer_{i}"]
            with jax.named_scope(f"attention_{i}"):
                out, kc, vc = self._attention(
                    p, h, states["k"][i], states["v"][i], pos, dtype,
                    sliding=kind == SLIDING)
                h = h + rms_norm(out, p["post_attn_norm"], eps)
            k_caches.append(kc)
            v_caches.append(vc)
            if i < cfg.num_dense_layers:
                with jax.named_scope(f"mlp_{i}"):
                    m = rms_norm(h, p["pre_mlp_norm"], eps)
                    f = moe.swiglu(m, p["w_in"], p["w_out"], dtype)
                    h = h + rms_norm(f, p["post_mlp_norm"], eps)
            else:
                with jax.named_scope(f"moe_{i}"):
                    m = rms_norm(h, p["pre_mlp_norm"], eps)
                    f, per_expert = moe.expert_layer(
                        p, m.reshape(B * T, -1), valid, dtype,
                        n_group=cfg.n_group, topk_group=cfg.topk_group,
                        top_k=cfg.num_experts_per_tok,
                        scaling=cfg.route_scale,
                        norm_topk_prob=cfg.route_norm,
                        first=cfg.experts_held[0],
                        shared=bool(cfg.num_shared_experts))
                    h = h + rms_norm(f.reshape(B, T, -1),
                                     p["post_mlp_norm"], eps)
                rows = rows + per_expert.sum()
                busiest = busiest + per_expert.max()
        with jax.named_scope("final_norm"):
            out = rms_norm(h, params["final_norm"], eps)
        ran = jnp.int32(1 if cfg.n_moe_layers else 0)
        on_kernel = sum(attention.core_is_kernel(
            jax.default_backend(), dtype, T, kc.shape[2],
            cfg.num_attention_heads // cfg.num_key_value_heads,
            cfg.head_dim) for kc in k_caches)
        new_states = {
            "k": tuple(k_caches), "v": tuple(v_caches), "pos": pos + T,
            "counts": self.counts.update(
                states["counts"], rows, busiest, ran,
                attention_kernel_layers=on_kernel,
                expert_kernel_layers=moe.kernel_layers(
                    params["layers"], B * T, cfg.num_experts_per_tok)),
        }
        return out, new_states

    # -- layers ----------------------------------------------------------

    def _attention(self, p, h, k_cache, v_cache, pos, dtype, sliding: bool):
        """``Attn`` of the module's docstring, before its post-norm."""
        cfg = self.config
        b, T, _ = h.shape
        Hq, Hkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                      cfg.head_dim)
        eps = cfg.rms_norm_eps
        a = rms_norm(h, p["input_norm"], eps).astype(dtype)
        with jax.named_scope("qkv_proj"):
            q, k, v = split_heads(matmul(a, p["qkv"], dtype), Hq, Hkv, d)
        with jax.named_scope("qk_norm"):
            q = rms_norm(q, p["q_norm"], eps)
            k = rms_norm(k, p["k_norm"], eps)
        if sliding:
            q, k = rope_qk(q, k, pos, self._inv_freq)
        with jax.named_scope("window_core" if sliding else "global_core"):
            out, k_cache, v_cache = attention.gqa_cached(
                q, k, v, k_cache, v_cache, pos, self._scale, mxu_dtype=dtype,
                window=cfg.sliding_window if sliding else None)
        with jax.named_scope("gate"):
            out = out.reshape(b, T, Hq * d) * jax.nn.sigmoid(
                matmul(a, p["gate"]))
        with jax.named_scope("o_proj"):
            out = matmul(out, p["o"])
        return out, k_cache, v_cache
