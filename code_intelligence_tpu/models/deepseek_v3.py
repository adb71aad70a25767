"""DeepSeek-V3 style encoder: multi-head latent attention in every layer,
a dense SwiGLU MLP in the leading layers and, after them, sigmoid-routed
experts with a shared one, of which this chip holds a SHARE.

Published as ``model_type: deepseek_v3``; the field names of
:class:`DeepseekV3Config` are those of the model's ``config.json``.
Equations (pre-norm residual blocks, ``eps`` = ``rms_norm_eps``, no
biases but the router's; ``ops/mla.py`` and ``ops/moe.py`` hold the two
mechanisms):

    h = E[ids]
    every layer:  h += MLA(RMSNorm(h));  h += FFN(RMSNorm(h))
    out = RMSNorm(h)                   # pooled; no LM head, no MTP module

    MLA(u): c_q = RMSNorm(u W_qa);  [q_nope | q_pe] = c_q W_qb   (a head)
      [c_kv | k_pe] = u W_kva;  c_kv = RMSNorm(c_kv)   (k_pe: ONE head)
      [k_nope | v] = c_kv W_kvb                         (a head)
      rotary (YaRN) on q_pe and k_pe; causal softmax in float32 of
      (q_nope.k_nope + q_pe.k_pe) * scale;  out = (P v) W_o
    FFN, layers < first_k_dense_replace: SwiGLU of intermediate_size
    FFN, the others: sum_i w_i E_i(u) + E_shared(u), the experts SwiGLU
      of moe_intermediate_size, (i, w_i) from the router (ops/moe.route)

**The share.** ``experts_held = (first, count)`` says which of the
``n_routed_experts`` experts this chip holds; the router keeps all its
outputs, its groups and its ``num_experts_per_tok``; the sum above runs
over the chosen experts that are held, plus the shared expert, and that
partial result goes on to the next layer (``models`` guide, §4).

A plain class, not a Flax module: it owns no parameters. The tree it
reads (``benchmark/reference/deepseek_v3.py::init_params`` makes one
from a seed), matrices as ``(in, out)``, a dict of leaves a layer (no
leaf is stacked over layers: a layer's slice of a stacked weight is a
copy in every program):

    embedding (V, E), final_norm (E,)
    layers/layer_<i>, every layer: norm, ffn_norm (E,);
      q_a (E, q_rank), q_norm (q_rank,), q_b (q_rank, H * (nope + rope));
      kv_a (E, kv_rank + rope), kv_norm (kv_rank,),
      kv_b (kv_rank, H * (nope + v)); o (H * v, E)
    a dense layer besides: w_in (E, 2 * F), w_out (F, E)
      ([gate | up] fused, as every SwiGLU here)
    an expert layer besides: router (E, n_routed_experts),
      bias (n_routed_experts,) float32, shared_in (E, 2 * Fs),
      shared_out (Fs, E), experts_in (count, E, 2 * Fe),
      experts_out (count, Fe, E): the HELD experts alone

The compute type is the type of the weights (bfloat16 weights: bfloat16
matmul inputs, float32 accumulation); RMSNorm statistics, rotary,
softmax and the router are float32 always.

State carried between chunk programs (``init_states``): per layer the
latent cache ``(rows, positions, kv_rank + rope)`` in the weights' type,
one position counter, three counts the expert layers keep and, last,
the attention layers whose core the program ran on the Pallas kernel
(``state_counters``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, ClassVar, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp

from code_intelligence_tpu.models.blocks import (
    CarriedCounts, Counts, GrowingCache, config_from_dict, embed,
    held_experts, latent_block, rms_norm, valid_lanes)
from code_intelligence_tpu.ops import mla, moe


@dataclasses.dataclass(frozen=True)
class DeepseekV3Config:
    architecture: ClassVar[str] = "deepseek_v3"

    vocab_size: int
    hidden_size: int = 7168
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 61
    first_k_dense_replace: int = 3
    num_attention_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 256        # the router's outputs
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_scaling: Any = None           # the published mapping, or None
    # the share: (first expert held, how many), None = all of them
    experts_held: Optional[Tuple[int, int]] = None
    # serving: positions one document's latent cache can hold
    kv_positions: int = 2048
    state_dtype: Any = jnp.bfloat16    # the latent cache's type

    def __post_init__(self):
        object.__setattr__(self, "experts_held", held_experts(
            self.experts_held, self.n_routed_experts))
        object.__setattr__(self, "state_dtype", jnp.dtype(self.state_dtype))
        if self.rope_scaling is not None:  # hashable, as a frozen field is
            object.__setattr__(self, "rope_scaling", tuple(sorted(
                dict(self.rope_scaling).items())))
        if self.scoring_func != "sigmoid" or self.topk_method != "noaux_tc":
            raise ValueError(
                "only scoring_func 'sigmoid' with topk_method 'noaux_tc' is "
                f"implemented, not {self.scoring_func!r} / "
                f"{self.topk_method!r}")
        if self.n_routed_experts % self.n_group:
            raise ValueError("n_group must divide n_routed_experts")
        if not 0 <= self.first_k_dense_replace <= self.num_hidden_layers:
            raise ValueError("first_k_dense_replace exceeds the layers")

    @classmethod
    def from_dict(cls, model: Mapping, **extra) -> "DeepseekV3Config":
        """From a published ``config.json``'s keys; of a share, its
        ``n_routed_experts`` counts the experts HELD."""
        return config_from_dict(cls, model, "n_routed_experts", **extra)

    @property
    def rope(self) -> Optional[dict]:
        return dict(self.rope_scaling) if self.rope_scaling else None

    @property
    def q_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_dim(self) -> int:
        """What one token caches a layer: ``c_kv`` and the shared
        rotary key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def n_moe_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace


class DeepseekV3Encoder(GrowingCache, CarriedCounts):
    """The encoder contract (`models/contract.py`) over DeepSeek-V3; the
    sizes its latent cache is allocated at and the reading of its counts
    are `models/blocks.py`'s."""

    cache_kind = "latent"
    # the attention layers whose core the program ran on the Pallas
    # kernel, and the expert layers whose grouped matmuls it did
    counts = Counts(sets=("attention_kernel_layers",
                          "expert_kernel_layers"))

    def __init__(self, config: DeepseekV3Config, dtype=jnp.bfloat16):
        self.config = config
        self.dtype = jnp.dtype(dtype)  # of the weights it will be handed
        self._inv_freq = mla.yarn_inv_freq(
            config.qk_rope_head_dim, config.rope_theta, config.rope)
        self._rope_factor = mla.rope_factor(config.rope)
        self._scale = mla.softmax_scale(config.q_head_dim, config.rope)

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
                for _ in range(cfg.num_hidden_layers)),
            "pos": jnp.zeros((), jnp.int32),
            "counts": self.counts.zeros(),
        }

    def state_bytes_per_row(self, max_len=None) -> int:
        """Bytes of latent cache one row holds for a document of
        ``max_len`` tokens; all of the state grows with the document."""
        cfg = self.config
        return cfg.num_hidden_layers * self.cache_positions(max_len) \
            * cfg.latent_dim * cfg.state_dtype.itemsize

    def encode(self, params, tokens, states, lengths=None):
        """One chunk: ``tokens`` ``(B, T)`` with the carried ``states``
        in, ``(hidden (B, T, out_dim) float32, new states)`` out.
        ``lengths`` ``(B,)``, where the caller knows them, are each
        row's valid tokens in this chunk: the lanes after them are
        padding, which attention never lets reach a valid token (causal)
        and which is then not routed to any expert."""
        cfg = self.config
        dtype = params["embedding"].dtype
        B, T = tokens.shape
        h = embed(params, tokens)
        pos = states["pos"]
        valid = None if lengths is None else \
            valid_lanes(lengths, T).reshape(-1)
        latents = []
        rows = busiest = jnp.zeros((), jnp.int32)
        for i in range(cfg.num_hidden_layers):
            p = params["layers"][f"layer_{i}"]
            with jax.named_scope(f"attention_{i}"):
                out, cache = self._attention(p, h, states["latent"][i], pos,
                                             dtype)
            h = h + out
            latents.append(cache)
            u = rms_norm(h, p["ffn_norm"], cfg.rms_norm_eps)
            if i < cfg.first_k_dense_replace:
                with jax.named_scope(f"mlp_{i}"):
                    h = h + moe.swiglu(u, p["w_in"], p["w_out"], dtype)
            else:
                with jax.named_scope(f"moe_{i}"):
                    out, per_expert = moe.expert_layer(
                        p, u.reshape(B * T, -1), valid, dtype,
                        n_group=cfg.n_group, topk_group=cfg.topk_group,
                        top_k=cfg.num_experts_per_tok,
                        scaling=cfg.routed_scaling_factor,
                        norm_topk_prob=cfg.norm_topk_prob,
                        first=cfg.experts_held[0],
                        shared=bool(cfg.n_shared_experts))
                h = h + out.reshape(B, T, -1)
                rows = rows + per_expert.sum()
                busiest = busiest + per_expert.max()
        with jax.named_scope("final_norm"):
            out = rms_norm(h, params["final_norm"], cfg.rms_norm_eps)
        ran = jnp.int32(1 if cfg.n_moe_layers else 0)
        on_kernel = sum(mla.core_is_kernel(
            jax.default_backend(), dtype, T, cache.shape[1],
            cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.v_head_dim,
            cfg.kv_lora_rank) for cache in latents)
        new_states = {
            "latent": tuple(latents),
            "pos": pos + T,
            "counts": self.counts.update(
                states["counts"], rows, busiest, ran,
                attention_kernel_layers=on_kernel,
                expert_kernel_layers=moe.kernel_layers(
                    params["layers"], B * T, cfg.num_experts_per_tok)),
        }
        return out, new_states

    # -- layers ----------------------------------------------------------

    def _attention(self, p, h, cache, pos, dtype):
        cfg = self.config
        return latent_block(
            p, h, cache, pos, dtype, heads=cfg.num_attention_heads,
            nope=cfg.qk_nope_head_dim, rope=cfg.qk_rope_head_dim,
            v_dim=cfg.v_head_dim, rank=cfg.kv_lora_rank,
            eps=cfg.rms_norm_eps, inv_freq=self._inv_freq,
            rope_factor=self._rope_factor, scale=self._scale)
