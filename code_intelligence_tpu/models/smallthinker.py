"""SmallThinker style encoder (PowerInfer SmallThinker-21BA3B): every
layer routes BEFORE it attends, then attends (grouped-query attention,
one NoPE global layer to three rotary sliding-window ones), then runs
the routed ReGLU experts that the early router chose; no shared expert,
no dense layer, two norms a layer.

Published as ``model_name: smallthinker_*``; the field names of
:class:`SmallThinkerConfig` are those of the model's ``config.json``.
Equations (``x`` the float32 residual, ``eps`` = ``rms_norm_eps``, no
biases anywhere; ``ops/attention.py`` and ``ops/moe.py`` hold the two
mechanisms):

    x = E[ids]                                         (no multiplier)
    layer i:
      z = x W_r                        the router reads the layer's INPUT
      (e, l) = top_k(z);  w = softmax(l)        over the chosen, float32
      a = RMSNorm_in(x);  q, k, v = a W_q, a W_k, a W_v        (a head)
      rope_layout[i] = 1: rotary on q and k (all ``head_dim`` dims, plain
        inverse frequencies, ``rotate_half`` pairs); 0: none (NoPE)
      sliding_window_layout[i] = 1: a query at t sees the keys
        t - sliding_window_size < j <= t; 0: every key j <= t
      x = x + softmax(q.k / sqrt(head_dim)) v W_o
      m = RMSNorm_post(x)
      x = x + sum_j w_j Down_{e_j}(relu(Gate_{e_j} m) * Up_{e_j} m)
    out = RMSNorm(x)                                   # pooled; no LM head

**Route, then attend, then apply.** A layer is three named parts in
program order: ``route_<i>`` (the router's matmul, the top-k, the
softmax and ``ops/moe.py::assign``'s sort by expert, all from the
layer's input), ``attention_<i>``, ``moe_<i>``
(``ops/moe.py::routed_experts`` on the post-attention stream). Nothing
of the first waits for the second.

**The share.** ``experts_held = (first, count)`` says which of the
``moe_num_primary_experts`` experts this chip holds, as
``models/deepseek_v3.py`` has it; the published deployment holds them
all.

A plain class, not a Flax module: it owns no parameters. The tree it
reads (``benchmark/reference/smallthinker.py::init_params`` makes one
from a seed), matrices as ``(in, out)``, a dict of leaves a layer:

    embedding (V, E), final_norm (E,)
    layers/layer_<i>: input_norm, post_norm (E,);
      qkv (E, (Hq + 2 Hkv) d): [q | k | v]; o (Hq d, E);
      router (E, moe_num_primary_experts);
      experts_in (count, E, 2 F): [gate | up], experts_out (count, F, E):
      the HELD experts alone

The compute type is the type of the weights; RMSNorm statistics, rotary,
softmax and the router are float32 always.

State carried between chunk programs (``init_states``): the two kinds of
caches of `models/windowed_caches.py` (a global layer's grows with the
document, a sliding layer's is a ring), one position counter, and the
counts of ``state_counters``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, ClassVar, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp

from code_intelligence_tpu.models.blocks import (
    CarriedCounts, Counts, config_from_dict, embed, held_experts, matmul,
    rms_norm, rope_qk, split_heads, valid_lanes)
from code_intelligence_tpu.models.windowed_caches import (
    WindowedCaches, ring_positions)
from code_intelligence_tpu.ops import attention, mla, moe

@dataclasses.dataclass(frozen=True)
class SmallThinkerConfig:
    architecture: ClassVar[str] = "smallthinker"

    vocab_size: int
    hidden_size: int = 2560
    num_hidden_layers: int = 52
    num_attention_heads: int = 28
    num_key_value_heads: int = 4
    head_dim: int = 128
    moe_ffn_hidden_size: int = 768
    moe_num_primary_experts: int = 64      # the router's outputs
    moe_num_active_primary_experts: int = 6
    moe_primary_router_apply_softmax: bool = True
    norm_topk_prob: bool = True
    rope_layout: Tuple[int, ...] = ()
    sliding_window_layout: Tuple[int, ...] = ()
    sliding_window_size: int = 4096
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1500000.0
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
            self.experts_held, self.moe_num_primary_experts))
        object.__setattr__(self, "state_dtype", jnp.dtype(self.state_dtype))
        for name in ("rope_layout", "sliding_window_layout"):
            layout = tuple(int(v) for v in getattr(self, name))
            object.__setattr__(self, name, layout)
            if len(layout) != self.num_hidden_layers or set(layout) - {0, 1}:
                raise ValueError(
                    f"{name} must say 0 or 1 for each of the "
                    f"{self.num_hidden_layers} layers: {layout}")
        if not self.moe_primary_router_apply_softmax:
            raise ValueError(
                "moe_primary_router_apply_softmax false (sigmoid scores "
                "of the chosen, normalised) is not implemented: only the "
                "softmax over the chosen logits is")
        if not self.norm_topk_prob:
            raise ValueError(
                "norm_topk_prob false is not implemented: a softmax over "
                "the chosen sums to 1")
        if self.rope_scaling is not None:
            raise ValueError(
                "only plain rotary (rope_scaling null) is implemented, "
                f"not rope_scaling {self.rope_scaling!r}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                "num_key_value_heads must divide num_attention_heads")

    @classmethod
    def from_dict(cls, model: Mapping, **extra) -> "SmallThinkerConfig":
        """From a published ``config.json``'s keys; of a share, its
        ``moe_num_primary_experts`` counts the experts HELD."""
        return config_from_dict(cls, model, "moe_num_primary_experts",
                                **extra)

    @property
    def n_moe_layers(self) -> int:
        return self.num_hidden_layers  # no dense layer

    @property
    def ring_positions(self) -> int:
        """Slots of a sliding layer's ring."""
        return ring_positions(self.sliding_window_size, self.chunk_positions)

    @property
    def sliding_layers(self) -> Tuple[bool, ...]:
        return tuple(bool(v) for v in self.sliding_window_layout)


class SmallThinkerEncoder(WindowedCaches, CarriedCounts):
    """The encoder contract (`models/contract.py`) over SmallThinker; its
    two kinds of caches, their arithmetic and ``init_states`` are
    `models/windowed_caches.py`'s, the reading of its counts
    `models/blocks.py`'s."""

    # the rounds of ``routed_experts``' loop (``expert_rounds_mean``: a
    # layer a program), the attention layers whose core the program ran
    # on the Pallas kernel, and the expert layers whose grouped matmuls
    # it did
    counts = Counts(sums=("expert_rounds",),
                    sets=("attention_kernel_layers", "expert_kernel_layers"))

    def __init__(self, config: SmallThinkerConfig, dtype=jnp.bfloat16):
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
        reach a valid token (causal) and which no expert is handed."""
        cfg = self.config
        dtype = params["embedding"].dtype
        eps = cfg.rms_norm_eps
        B, T = tokens.shape
        first, held = cfg.experts_held
        h = embed(params, tokens)
        pos = states["pos"]
        valid = None if lengths is None else \
            valid_lanes(lengths, T).reshape(-1)
        k_caches, v_caches = [], []
        rows = busiest = rounds = jnp.zeros((), jnp.int32)
        for i in range(cfg.num_hidden_layers):
            p = params["layers"][f"layer_{i}"]
            with jax.named_scope(f"route_{i}"):
                with jax.named_scope("router"):
                    experts, weights = moe.route(
                        h.reshape(B * T, -1), p["router"], None, 1, 1,
                        cfg.moe_num_active_primary_experts, 1.0,
                        score_func="softmax")
                with jax.named_scope("dispatch"):
                    assigned = moe.assign(experts, first, held, valid)
            with jax.named_scope(f"attention_{i}"):
                out, kc, vc = self._attention(
                    p, h, states["k"][i], states["v"][i], pos, dtype,
                    rotary=bool(cfg.rope_layout[i]),
                    sliding=cfg.sliding_layers[i])
                h = h + out
            k_caches.append(kc)
            v_caches.append(vc)
            with jax.named_scope(f"moe_{i}"):
                m = rms_norm(h, p["post_norm"], eps)
                f, per_expert = moe.routed_experts(
                    m.reshape(B * T, -1), experts, weights, p["experts_in"],
                    p["experts_out"], first, p["router"].shape[1], valid,
                    act="relu", assigned=assigned)
                h = h + f.reshape(B, T, -1)
            landed = per_expert.sum()
            rows = rows + landed
            busiest = busiest + per_expert.max()
            rounds = rounds + moe.rounds_run(
                landed, B * T, experts.shape[1], held, p["router"].shape[1])
        with jax.named_scope("final_norm"):
            out = rms_norm(h, params["final_norm"], eps)
        on_kernel = sum(attention.core_is_kernel(
            jax.default_backend(), dtype, T, kc.shape[2],
            cfg.num_attention_heads // cfg.num_key_value_heads,
            cfg.head_dim) for kc in k_caches)
        new_states = {
            "k": tuple(k_caches), "v": tuple(v_caches), "pos": pos + T,
            "counts": self.counts.update(
                states["counts"], rows, busiest, jnp.int32(1),
                expert_rounds=rounds, attention_kernel_layers=on_kernel,
                expert_kernel_layers=moe.kernel_layers(
                    params["layers"], B * T, experts.shape[1])),
        }
        return out, new_states

    # -- layers ----------------------------------------------------------

    def _attention(self, p, h, k_cache, v_cache, pos, dtype, rotary: bool,
                   sliding: bool):
        """The attention branch of one layer: ``softmax(q.k) v W_o`` of
        the normed input."""
        cfg = self.config
        b, T, _ = h.shape
        Hq, Hkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                      cfg.head_dim)
        a = rms_norm(h, p["input_norm"], cfg.rms_norm_eps).astype(dtype)
        with jax.named_scope("qkv_proj"):
            q, k, v = split_heads(matmul(a, p["qkv"], dtype), Hq, Hkv, d)
        if rotary:
            q, k = rope_qk(q, k, pos, self._inv_freq)
        with jax.named_scope("window_core" if sliding else "global_core"):
            out, k_cache, v_cache = attention.gqa_cached(
                q, k, v, k_cache, v_cache, pos, self._scale, mxu_dtype=dtype,
                window=cfg.sliding_window_size if sliding else None)
        with jax.named_scope("o_proj"):
            out = matmul(out.reshape(b, T, Hq * d), p["o"])
        return out, k_cache, v_cache
