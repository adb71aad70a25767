"""LongCat-Flash style encoder: a shortcut-connected mixture of experts.
A layer holds TWO latent-attention sublayers and TWO dense SwiGLU FFNs;
the routed experts read the stream after the first sublayer's attention
and their result rejoins it after the second sublayer's FFN. The router
is a softmax over ALL its outputs, a third of which are identity
(zero-compute) experts; of the experts with weights this chip holds a
SHARE.

Published as ``meituan-longcat/LongCat-Flash-Chat``; the field names of
:class:`LongcatFlashConfig` are those of the model's ``config.json``.
Equations (``x`` the float32 residual, ``eps`` = ``rms_norm_eps``, no
biases but the router's; ``ops/mla.py`` and ``ops/moe.py`` hold the two
mechanisms):

    x = E[ids]
    layer l:  x += MLA_{l,0}(RMSNorm(x));  u0 = RMSNorm(x)
              m  = MoE_l(u0)              # made HERE, added at the END
              x += SwiGLU_{l,0}(u0)
              x += MLA_{l,1}(RMSNorm(x));  u1 = RMSNorm(x)
              x += SwiGLU_{l,1}(u1);  x += m
    out = RMSNorm(x)                      # pooled; no LM head

    MLA(u): `models/blocks.py::latent_block` with a low-rank query, plain
      rotary (no YaRN), ``c_q`` times sqrt(hidden_size / q_lora_rank)
      (``mla_scale_q_lora``) and ``c_kv`` times sqrt(hidden_size /
      kv_lora_rank) (``mla_scale_kv_lora``) after their norms; the cache
      holds ``[c_kv (scaled) | k_pe (rotated)]``
    MoE(u): s = softmax(u W_r) over all n_routed_experts + zero_expert_num
      outputs, float32; e = top moe_topk of s + bias; w_j = 
      routed_scaling_factor * s[e_j], NOT renormalised;
      m = sum_{e_j < n_routed_experts} w_j SwiGLU_{e_j}(u)
        + (sum_{e_j >= n_routed_experts} w_j) u      # identity experts

**The share.** ``experts_held = (first, count)`` says which of the
``n_routed_experts`` experts with weights this chip holds; the router
keeps all its outputs and its ``moe_topk``; the first sum runs over the
chosen experts that are held, the identity part over every chosen
zero-compute expert (every token here is this chip's own), and that
partial ``m`` goes on (``models`` guide, section 4).

A plain class, not a Flax module: it owns no parameters. The tree it
reads (``benchmark/reference/longcat_flash.py::init_params`` makes one
from a seed), matrices as ``(in, out)``, a dict of leaves a layer (no
leaf is stacked over layers):

    embedding (V, E), final_norm (E,)
    layers/layer_<l>: attention_0, attention_1: norm (E,), q_a (E,
      q_rank), q_norm (q_rank,), q_b (q_rank, H * (nope + rope)), kv_a
      (E, kv_rank + rope), kv_norm (kv_rank,), kv_b (kv_rank, H * (nope
      + v)), o (H * v, E);  post_norm_0, post_norm_1 (E,);
      mlp_0, mlp_1: w_in (E, 2 * F), w_out (F, E) ([gate | up] fused);
      router (E, n_routed_experts + zero_expert_num), bias (the same,)
      float32; experts_in (count, E, 2 * Fe), experts_out (count, Fe,
      E): the HELD experts alone

The compute type is the type of the weights; RMSNorm statistics, rotary,
softmax and the router are float32 always.

State carried between chunk programs (``init_states``): ``2 *
num_layers`` latent caches ``(rows, positions, kv_rank + rope)`` (the
published ``layer_idx`` of a sublayer is ``2 l + s``), one position
counter, and the counts (``state_counters``).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, ClassVar, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp

from code_intelligence_tpu.models.blocks import (
    CarriedCounts, Counts, GrowingCache, config_from_dict, embed,
    held_experts, latent_block, rms_norm, valid_lanes)
from code_intelligence_tpu.ops import mla, moe


@dataclasses.dataclass(frozen=True)
class LongcatFlashConfig:
    architecture: ClassVar[str] = "longcat_flash"

    vocab_size: int
    hidden_size: int = 6144
    ffn_hidden_size: int = 12288
    expert_ffn_hidden_size: int = 2048
    num_layers: int = 28               # each of two sublayers
    num_attention_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    n_routed_experts: int = 512        # the router's outputs WITH weights
    zero_expert_num: int = 256         # its outputs without
    zero_expert_type: str = "identity"
    moe_topk: int = 12
    routed_scaling_factor: float = 6.0
    attention_method: str = "MLA"
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000000.0
    # the share: (first expert held, how many), None = all of them
    experts_held: Optional[Tuple[int, int]] = None
    # serving: positions one document's latent caches can hold
    kv_positions: int = 16384
    state_dtype: Any = jnp.bfloat16    # the latent caches' type

    def __post_init__(self):
        object.__setattr__(self, "experts_held", held_experts(
            self.experts_held, self.n_routed_experts))
        object.__setattr__(self, "state_dtype", jnp.dtype(self.state_dtype))
        if self.zero_expert_num and self.zero_expert_type != "identity":
            raise ValueError(
                "only zero_expert_type 'identity' is implemented, not "
                f"{self.zero_expert_type!r}")
        if self.attention_method != "MLA":
            raise ValueError(
                "only attention_method 'MLA' is implemented, not "
                f"{self.attention_method!r}")

    @classmethod
    def from_dict(cls, model: Mapping, **extra) -> "LongcatFlashConfig":
        """From a published ``config.json``'s keys; of a share, its
        ``n_routed_experts`` counts the experts HELD."""
        return config_from_dict(cls, model, "n_routed_experts", **extra)

    @property
    def q_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_dim(self) -> int:
        """What one token caches a sublayer: ``c_kv`` and the shared
        rotary key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def n_sublayers(self) -> int:
        return 2 * self.num_layers

    @property
    def n_moe_layers(self) -> int:
        return self.num_layers  # one shortcut branch a layer


class LongcatFlashEncoder(GrowingCache, CarriedCounts):
    """The encoder contract (`models/contract.py`) over LongCat-Flash;
    the sizes its latent caches are allocated at and the reading of its
    counts are `models/blocks.py`'s."""

    cache_kind = "latent"
    # the rounds of ``routed_experts``' loop (a layer a program); the
    # valid tokens' choices and those of them that fell on zero-compute
    # experts; the attention sublayers whose core the program ran on the
    # Pallas kernel, and the layers whose routed experts' grouped matmuls
    # it did
    counts = Counts(sums=("expert_rounds",),
                    totals=("zero_choices", "valid_choices"),
                    sets=("attention_kernel_layers", "expert_kernel_layers"))

    def __init__(self, config: LongcatFlashConfig, dtype=jnp.bfloat16):
        self.config = config
        self.dtype = jnp.dtype(dtype)  # of the weights it will be handed
        self._inv_freq = mla.yarn_inv_freq(
            config.qk_rope_head_dim, config.rope_theta, None)
        self._scale = mla.softmax_scale(config.q_head_dim, None)
        self._q_scale = math.sqrt(
            config.hidden_size / config.q_lora_rank) \
            if config.mla_scale_q_lora else None
        self._kv_scale = math.sqrt(
            config.hidden_size / config.kv_lora_rank) \
            if config.mla_scale_kv_lora else None

    # -- contract --------------------------------------------------------

    @property
    def out_dim(self) -> int:
        return self.config.hidden_size

    def init_states(self, batch: int, positions=None):
        cfg = self.config
        S = self.cache_positions(positions)
        return {
            "latent": tuple(
                jnp.zeros((batch, S, cfg.latent_dim), cfg.state_dtype)
                for _ in range(cfg.n_sublayers)),
            "pos": jnp.zeros((), jnp.int32),
            "counts": self.counts.zeros(),
        }

    def state_bytes_per_row(self, max_len=None) -> int:
        """Bytes of latent cache one row holds for a document of
        ``max_len`` tokens, two caches a layer; all of the state grows
        with the document."""
        cfg = self.config
        return cfg.n_sublayers * self.cache_positions(max_len) \
            * cfg.latent_dim * cfg.state_dtype.itemsize

    def encode(self, params, tokens, states, lengths=None):
        """One chunk: ``tokens`` ``(B, T)`` with the carried ``states``
        in, ``(hidden (B, T, out_dim) float32, new states)`` out.
        ``lengths`` ``(B,)``, where the caller knows them, are each
        row's valid tokens in this chunk: the lanes after them are
        padding, which attention never lets reach a valid token (causal)
        and which no expert, held or zero-compute, is given."""
        cfg = self.config
        dtype = params["embedding"].dtype
        eps = cfg.rms_norm_eps
        B, T = tokens.shape
        N = B * T
        h = embed(params, tokens)
        pos = states["pos"]
        valid = None if lengths is None else \
            valid_lanes(lengths, T).reshape(-1)
        latents = []
        rows = busiest = rounds = zero_choices = jnp.zeros((), jnp.int32)

        for i in range(cfg.num_layers):
            p = params["layers"][f"layer_{i}"]
            for s in (0, 1):
                at = 2 * i + s  # the sublayer's published ``layer_idx``
                with jax.named_scope(f"attention_{at}"):
                    out, cache = self._attention(
                        p[f"attention_{s}"], h, states["latent"][at], pos,
                        dtype)
                latents.append(cache)
                h = h + out
                u = rms_norm(h, p[f"post_norm_{s}"], eps)
                if s == 0:  # the shortcut leaves the stream here
                    with jax.named_scope(f"moe_{i}"):
                        m, per_expert, zeros = self._moe(
                            p, u.reshape(N, -1), valid)
                with jax.named_scope(f"mlp_{at}"):
                    h = h + moe.swiglu(u, p[f"mlp_{s}"]["w_in"],
                                       p[f"mlp_{s}"]["w_out"], dtype)
            h = h + m.reshape(B, T, -1)  # and rejoins it here
            landed = per_expert.sum()
            rows = rows + landed
            busiest = busiest + per_expert.max()
            rounds = rounds + moe.rounds_run(
                landed, N, cfg.moe_topk, cfg.experts_held[1],
                p["router"].shape[1])
            zero_choices = zero_choices + zeros
        with jax.named_scope("final_norm"):
            out = rms_norm(h, params["final_norm"], eps)
        chose = jnp.int32(N) if valid is None else \
            jnp.sum(valid, dtype=jnp.int32)
        on_kernel = sum(mla.core_is_kernel(
            jax.default_backend(), dtype, T, cache.shape[1],
            cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.v_head_dim,
            cfg.kv_lora_rank) for cache in latents)
        new_states = {
            "latent": tuple(latents),
            "pos": pos + T,
            "counts": self.counts.update(
                states["counts"], rows, busiest, jnp.int32(1),
                expert_rounds=rounds, zero_choices=zero_choices,
                valid_choices=chose * (cfg.moe_topk * cfg.num_layers),
                attention_kernel_layers=on_kernel,
                expert_kernel_layers=moe.kernel_layers(
                    params["layers"], N, cfg.moe_topk)),
        }
        return out, new_states

    # -- layers ----------------------------------------------------------

    def _attention(self, p, h, cache, pos, dtype):
        cfg = self.config
        return latent_block(
            p, h, cache, pos, dtype, heads=cfg.num_attention_heads,
            nope=cfg.qk_nope_head_dim, rope=cfg.qk_rope_head_dim,
            v_dim=cfg.v_head_dim, rank=cfg.kv_lora_rank,
            eps=cfg.rms_norm_eps, inv_freq=self._inv_freq, rope_factor=1.0,
            scale=self._scale, q_scale=self._q_scale,
            kv_scale=self._kv_scale)

    def _moe(self, p, u, valid):
        """The shortcut's branch over the flat tokens ``u``: ``(the held
        experts' part + the identity experts' (N, E) float32, rows each
        held expert ran, the valid tokens' choices of a zero-compute
        expert)``. Named scopes ``router``, ``routed_experts``'s three
        and ``zero_experts``."""
        cfg = self.config
        with jax.named_scope("router"):
            experts, weights = moe.route(
                u, p["router"], p["bias"], 1, 1, cfg.moe_topk,
                cfg.routed_scaling_factor, norm_topk_prob=False,
                score_func="softmax_all")
        y, per_expert = moe.routed_experts(
            u, experts, weights, p["experts_in"], p["experts_out"],
            cfg.experts_held[0], p["router"].shape[1], valid)
        z, zeros = moe.zero_experts(
            u, experts, weights, cfg.n_routed_experts, valid)
        return y + z, per_expert, zeros
