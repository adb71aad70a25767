"""GLM-5 style encoder (``model_type: glm_moe_dsa``): DeepSeek-V3's
layer (multi-head latent attention, a dense SwiGLU MLP in the leading
layers, sigmoid-routed experts with a shared one after them, of which
this chip holds a SHARE) whose every query attends only the
``index_topk`` cached positions a learned INDEXER picks for it
(``ops/dsa.py``). The field names of :class:`GlmMoeDsaConfig` are those
of the model's ``config.json``. Equations (pre-norm residual blocks,
``eps`` = ``rms_norm_eps``; `models/deepseek_v3.py`'s docstring has the
latent attention and the expert layer, which are `models/blocks.py`'s
``latent_block`` and ``ops/moe.py``'s ``expert_layer`` here too):

    h = E[ids]
    every layer:  h += DSA(RMSNorm(h));  h += FFN(RMSNorm(h))
    out = RMSNorm(h)                   # pooled; no LM head, no MTP module

    DSA(u): c_q, [q_nope | q_pe], [c_kv | k_pe], [k_nope | v] as MLA's
      (rotary plain, interleaved pairs, on the pe parts;
      scale = (nope + rope)^-0.5)
      indexer:  qI = c_q W_Iq                   (index_n_heads x index_head_dim)
                kI = LayerNorm(u W_Ik; g, b)    (ONE key a position, eps 1e-6)
                the first qk_rope_head_dim dims of every qI head and of
                kI turned at the position with the same frequencies
                w  = (u W_Iw) * index_n_heads^-0.5 * index_head_dim^-0.5
                I[t,s] = sum_j w[t,j] relu(qI[t,j] . kI[s])      s <= t
      S_t = the min(index_topk, t + 1) largest I[t, 0..t], ties to the
            lower position
      softmax over S_t alone of (q_nope.k_nope + q_pe.k_pe) * scale;
      out = (P v) W_o
    FFN: the dense SwiGLU in layers < first_k_dense_replace; in the
      others the router WITHOUT a group step (n_group 1, topk_group 1:
      ``ops/moe.route``'s ungrouped branch), choice on sigmoid + bias,
      weights the unbiased scores normalised and scaled, the held
      experts' part plus the shared expert

The placements the config's keys do not settle (the LayerNorm's eps,
which dims of an index head turn, ``w``'s two factors, the tie rule; no
Hadamard rotation and no FP8 index keys: the rotation is orthogonal and
FP8 is the release's kernel precision) are the family's published
description and are listed in the benchmark configuration's ``assumed``.

The tree it reads (``benchmark/reference/glm_moe_dsa.py::init_params``
makes one from a seed) is `models/deepseek_v3.py`'s, every layer
besides: ``index_q (q_rank, Hi * d)``, ``index_k (E, d)``,
``index_k_norm`` and ``index_k_bias (d,)``, ``index_w (E, Hi)``.

State carried between chunk programs (``init_states``): per layer the
latent cache ``(rows, positions, kv_rank + rope)`` AND the index-key
cache ``(rows, positions, index_head_dim)``, both in ``state_dtype``
and of one length (`models/contract.py`: ``cache_positions`` is the
length of both), one position counter and the counts: the expert
layers', and what the selection met, summed over layers and valid lanes:
``dsa_pairs_scored`` (a valid query times the positions ``<=`` it),
``dsa_pairs_selected`` (the pairs the core admits: ``min(reached,
index_topk)`` a query, where the engine's ``cache_steps_run`` counts the
positions reached) and ``dsa_threshold_ties``. A group of 8 rows of
32,768 positions scores 4.3e9 pairs a layer, more than an int32 holds:
the two pair counts are carried as ``n % 65536`` and ``n // 65536`` of
each program's own count (``_x65536``) and put together on the host.
"""

from __future__ import annotations

import dataclasses
from typing import Any, ClassVar, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from code_intelligence_tpu.models.blocks import (
    CarriedCounts, Counts, GrowingCache, config_from_dict, embed,
    held_experts, latent_block, matmul, rms_norm, valid_lanes)
from code_intelligence_tpu.ops import dsa, mla, moe

# the pair counts that pass an int32 over a group, carried in two slots
_WIDE = ("dsa_pairs_scored", "dsa_pairs_selected")


def layer_norm(x, w, bias, eps):
    """LayerNorm with mean and bias over the last axis, float32."""
    xf = x.astype(jnp.float32)
    xc = xf - jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    return xc * lax.rsqrt(var + eps) * w.astype(jnp.float32) \
        + bias.astype(jnp.float32)


def index_rope(x, positions, inv_freq, width: int):
    """The indexer's rotary: the first ``width`` dims of the last axis
    turned at ``positions`` (interleaved pairs, as the attention's), the
    dims after them as they are; float32."""
    return jnp.concatenate(
        [mla.apply_rope(x[..., :width], positions, inv_freq),
         x[..., width:].astype(jnp.float32)], axis=-1)


@dataclasses.dataclass(frozen=True)
class GlmMoeDsaConfig:
    architecture: ClassVar[str] = "glm_moe_dsa"

    vocab_size: int
    hidden_size: int = 6144
    intermediate_size: int = 12288
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 78
    first_k_dense_replace: int = 3
    num_attention_heads: int = 64
    q_lora_rank: int = 2048
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    index_n_heads: int = 32
    index_head_dim: int = 128
    index_topk: int = 2048
    rope_interleave: bool = True
    indexer_rope_interleave: bool = True
    n_routed_experts: int = 256        # the router's outputs
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    rms_norm_eps: float = 1e-5
    rope_parameters: Any = None        # the published mapping
    # the indexer's LayerNorm: not a config key (the file's `assumed`)
    index_norm_eps: float = 1e-6
    # the share: (first expert held, how many), None = all of them
    experts_held: Optional[Tuple[int, int]] = None
    # serving: positions one document's two caches can hold
    kv_positions: int = 32768
    state_dtype: Any = jnp.bfloat16    # both caches' type

    def __post_init__(self):
        object.__setattr__(self, "experts_held", held_experts(
            self.experts_held, self.n_routed_experts))
        object.__setattr__(self, "state_dtype", jnp.dtype(self.state_dtype))
        rope = dict(self.rope_parameters or {"rope_theta": 10000.0})
        object.__setattr__(self, "rope_parameters",
                           tuple(sorted(rope.items())))
        if rope.get("rope_type", "default") != "default":
            raise ValueError(
                f"rope_type {rope['rope_type']!r} is not implemented "
                "(only 'default': plain rotary)")
        if not (self.rope_interleave and self.indexer_rope_interleave):
            raise ValueError(
                "only interleaved rotary pairs are implemented "
                "(rope_interleave and indexer_rope_interleave true)")
        if self.scoring_func != "sigmoid" or self.topk_method != "noaux_tc":
            raise ValueError(
                "only scoring_func 'sigmoid' with topk_method 'noaux_tc' is "
                f"implemented, not {self.scoring_func!r} / "
                f"{self.topk_method!r}")
        if self.n_routed_experts % self.n_group:
            raise ValueError("n_group must divide n_routed_experts")
        if not 0 <= self.first_k_dense_replace <= self.num_hidden_layers:
            raise ValueError("first_k_dense_replace exceeds the layers")
        if self.index_head_dim < self.qk_rope_head_dim:
            raise ValueError(
                "an index head turns its first qk_rope_head_dim dims: "
                f"index_head_dim {self.index_head_dim} is narrower")

    @classmethod
    def from_dict(cls, model: Mapping, **extra) -> "GlmMoeDsaConfig":
        """From a published ``config.json``'s keys; of a share, its
        ``n_routed_experts`` counts the experts HELD."""
        return config_from_dict(cls, model, "n_routed_experts", **extra)

    @property
    def rope_theta(self) -> float:
        return float(dict(self.rope_parameters)["rope_theta"])

    @property
    def q_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_dim(self) -> int:
        """What one token caches a layer beside its index key: ``c_kv``
        and the shared rotary key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def n_moe_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace


class GlmMoeDsaEncoder(GrowingCache, CarriedCounts):
    """The encoder contract (`models/contract.py`) over GLM-5; the sizes
    its two caches are allocated at and the reading of its expert counts
    are `models/blocks.py`'s."""

    cache_kind = "latent and index-key"
    counts = Counts(
        sums=("expert_rounds",),
        totals=tuple(name + part for name in _WIDE
                     for part in ("", "_x65536")) + ("dsa_threshold_ties",),
        # the expert layers whose grouped matmuls ran on the Pallas
        # kernels, and the attention layers whose selection and core did
        sets=("expert_kernel_layers", "dsa_kernel_layers"))

    def __init__(self, config: GlmMoeDsaConfig, dtype=jnp.bfloat16):
        self.config = config
        self.dtype = jnp.dtype(dtype)  # of the weights it will be handed
        self._inv_freq = mla.yarn_inv_freq(
            config.qk_rope_head_dim, config.rope_theta)
        self._scale = mla.softmax_scale(config.q_head_dim, None)

    # -- contract --------------------------------------------------------

    @property
    def out_dim(self) -> int:
        return self.config.hidden_size

    def init_states(self, batch: int, positions=None):
        cfg = self.config
        S = self.cache_positions(positions)

        def caches(width):
            return tuple(jnp.zeros((batch, S, width), cfg.state_dtype)
                         for _ in range(cfg.num_hidden_layers))

        return {"latent": caches(cfg.latent_dim),
                "index": caches(cfg.index_head_dim),
                "pos": jnp.zeros((), jnp.int32),
                "counts": self.counts.zeros()}

    def state_bytes_per_row(self, max_len=None) -> int:
        """Bytes of latent and index-key cache one row holds for a
        document of ``max_len`` tokens; all of the state grows with the
        document."""
        cfg = self.config
        return cfg.num_hidden_layers * self.cache_positions(max_len) \
            * (cfg.latent_dim + cfg.index_head_dim) \
            * cfg.state_dtype.itemsize

    def counter_attrs(self, counted) -> dict:
        attrs = super().counter_attrs(counted)
        for name in _WIDE:
            if name in attrs:
                attrs[name] += 65536 * attrs.pop(name + "_x65536")
        return attrs

    def encode(self, params, tokens, states, lengths=None):
        """One chunk: ``tokens`` ``(B, T)`` with the carried ``states``
        in, ``(hidden (B, T, out_dim) float32, new states)`` out.
        ``lengths`` ``(B,)``, where the caller knows them, are each
        row's valid tokens in this chunk: the lanes after them are
        padding, which no valid query's scores, selection or attention
        reach (all three are causal), which is not routed to any expert
        and which is left out of the counts."""
        cfg = self.config
        dtype = params["embedding"].dtype
        B, T = tokens.shape
        h = embed(params, tokens)
        pos = states["pos"]
        valid = None if lengths is None else valid_lanes(lengths, T)
        flat = None if valid is None else valid.reshape(-1)
        latents, index_keys = [], []
        rows = busiest = rounds = jnp.zeros((), jnp.int32)
        met = jnp.zeros((3,), jnp.int32)
        for i in range(cfg.num_hidden_layers):
            p = params["layers"][f"layer_{i}"]
            with jax.named_scope(f"attention_{i}"):
                out, (latent, index), counted = self._attention(
                    p, h, (states["latent"][i], states["index"][i]), pos,
                    valid, dtype)
            h = h + out
            latents.append(latent)
            index_keys.append(index)
            met = met + counted
            u = rms_norm(h, p["ffn_norm"], cfg.rms_norm_eps)
            if i < cfg.first_k_dense_replace:
                with jax.named_scope(f"mlp_{i}"):
                    h = h + moe.swiglu(u, p["w_in"], p["w_out"], dtype)
            else:
                with jax.named_scope(f"moe_{i}"):
                    out, per_expert = moe.expert_layer(
                        p, u.reshape(B * T, -1), flat, dtype,
                        n_group=cfg.n_group, topk_group=cfg.topk_group,
                        top_k=cfg.num_experts_per_tok,
                        scaling=cfg.routed_scaling_factor,
                        norm_topk_prob=cfg.norm_topk_prob,
                        first=cfg.experts_held[0],
                        shared=bool(cfg.n_shared_experts))
                h = h + out.reshape(B, T, -1)
                landed = per_expert.sum()
                rows = rows + landed
                busiest = busiest + per_expert.max()
                rounds = rounds + moe.rounds_run(
                    landed, B * T, cfg.num_experts_per_tok,
                    cfg.experts_held[1], cfg.n_routed_experts)
        with jax.named_scope("final_norm"):
            out = rms_norm(h, params["final_norm"], cfg.rms_norm_eps)
        ran = jnp.int32(1 if cfg.n_moe_layers else 0)
        wide = {name + part: value for name, n in zip(_WIDE, met)
                for part, value in (("", n % 65536), ("_x65536", n // 65536))}
        new_states = {
            "latent": tuple(latents),
            "index": tuple(index_keys),
            "pos": pos + T,
            "counts": self.counts.update(
                states["counts"], rows, busiest, ran, expert_rounds=rounds,
                dsa_threshold_ties=met[2], **wide,
                expert_kernel_layers=moe.kernel_layers(
                    params["layers"], B * T, cfg.num_experts_per_tok),
                # every step of ``ops/dsa.py`` is XLA's: no Pallas core yet
                dsa_kernel_layers=0),
        }
        return out, new_states

    # -- layers ----------------------------------------------------------

    def _attention(self, p, h, caches, pos, valid, dtype):
        """``DSA(RMSNorm(h))`` of the module's docstring over one chunk:
        ``(out (b, T, E) float32, the layer's two caches, counts (3,))``."""
        cfg = self.config
        b, T, _ = h.shape
        Hi, d, turned = (cfg.index_n_heads, cfg.index_head_dim,
                         cfg.qk_rope_head_dim)
        counted = []

        def attend(u, c_q, q_nope, q_pe, latent, caches):
            cache, idx_cache = caches
            with jax.named_scope("dsa_indexer"):
                positions = pos + jnp.arange(T)
                q_idx = index_rope(
                    matmul(c_q, p["index_q"], dtype).reshape(b, T, Hi, d),
                    positions, self._inv_freq, turned)
                k_idx = index_rope(
                    layer_norm(matmul(u, p["index_k"]), p["index_k_norm"],
                               p["index_k_bias"], cfg.index_norm_eps),
                    positions, self._inv_freq, turned)
                w_idx = matmul(u, p["index_w"]) * (Hi ** -0.5 * d ** -0.5)
                idx_cache = lax.dynamic_update_slice_in_dim(
                    idx_cache, k_idx.astype(idx_cache.dtype), pos, axis=1)
            with jax.named_scope("mla_core"):
                cache = lax.dynamic_update_slice_in_dim(
                    cache, latent.astype(cache.dtype), pos, axis=1)
            out, counts = dsa.sparse_attention(
                q_nope, q_pe, cache, q_idx, w_idx, idx_cache, pos, p["kv_b"],
                self._scale, cfg.v_head_dim, cfg.index_topk, valid,
                mxu_dtype=dtype)
            counted.append(counts)
            return out, (cache, idx_cache)

        out, caches = latent_block(
            p, h, caches, pos, dtype, heads=cfg.num_attention_heads,
            nope=cfg.qk_nope_head_dim, rope=cfg.qk_rope_head_dim,
            v_dim=cfg.v_head_dim, rank=cfg.kv_lora_rank,
            eps=cfg.rms_norm_eps, inv_freq=self._inv_freq,
            rope_factor=1.0, scale=self._scale, attend=attend)
        return out, caches, counted[0]
