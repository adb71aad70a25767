"""Bailing-hybrid style encoder (Ling 3.0): delta-rule linear attention
with a decay per channel in five layers of six and multi-head latent
attention in the sixth, a dense SwiGLU MLP in the leading layers and,
after them, sigmoid-routed experts with a shared one, of which this chip
holds a SHARE.

Published as ``model_type: bailing_hybrid``; the field names of
:class:`BailingHybridConfig` are those of the model's ``config.json``.
Equations (pre-norm residual blocks, ``eps`` = ``rms_norm_eps``, no
biases but the router's and the decay gate's; ``ops/kda.py``,
``ops/mla.py`` and ``ops/moe.py`` hold the three mechanisms):

    h = E[ids]
    layer i:  h += mixer_i(RMSNorm(h));  h += FFN_i(RMSNorm(h))
      mixer_i = MLA where (i + 1) % layer_group_size == 0, else KDA
    out = RMSNorm(h)                   # pooled; no LM head, no MTP module

    KDA(x), H heads of d:
      q, k, v = silu(conv_K(x W_q)), silu(conv_K(x W_k)), silu(conv_K(x W_v))
        (depthwise causal conv over time, K = short_conv_kernel_size)
      q_h = l2norm(q_h) / sqrt(d);  k_h = l2norm(k_h)
      g = kda_lower_bound * sigmoid(exp(A_log_h) * (x W_f + dt_bias))
        (H, d): a log-decay per channel, in (kda_lower_bound, 0)
      b = sigmoid(x W_b)                                   (H,)
      S' = diag(exp(g)) S;  S = S' + b k (v - k^T S')^T;  o = S^T q
      KDA = [RMSNorm_d(o_h) * sigmoid(x W_g)] W_o
    MLA(x): ``models/blocks.py::latent_block`` with one query
      matrix (``q_lora_rank: null``), plain rotary and a head-wise
      output gate ``o_h * sigmoid(x w_h)``
    FFN, layers < first_k_dense_replace: SwiGLU of intermediate_size
    FFN, the others: sum_i w_i E_i(u) + E_shared(u), DeepSeek-V3's
      router (ops/moe.route: groups, the bias for the choice only)

What the published config does not settle is listed in the benchmark
configuration's ``assumed`` (which norm ``use_qk_norm`` names, no rotary
in the KDA layers, where the two output gates sit, the shapes of
``A_log`` / ``dt_bias``, which layers are latent).

**The share.** ``experts_held = (first, count)``, as
``models/deepseek_v3.py`` has it.

A plain class, not a Flax module: it owns no parameters. The tree it
reads (``benchmark/reference/bailing_hybrid.py::init_params`` makes one
from a seed), matrices as ``(in, out)``, a dict of leaves a layer:

    embedding (V, E), final_norm (E,)
    layers/layer_<i>, every layer: norm, ffn_norm (E,)
    a KDA layer besides: qkv (E, 3 H d): [q | k | v]; conv_w (3 H d, K);
      gates (E, 2 H d + H): [f | g | b] (decay gate, output gate, beta);
      A_log (H,), dt_bias (H d,) float32; o_norm (d,); o (H d, E)
    an MLA layer besides: q (E, H (nope + rope)); kv_a (E, rank + rope),
      kv_norm (rank,), kv_b (rank, H (nope + v)); gate (E, H);
      o (H v, E)
    a dense layer besides: w_in (E, 2 F), w_out (F, E)  ([gate | up])
    an expert layer besides: router (E, num_experts), bias
      (num_experts,) float32, shared_in (E, 2 Fs), shared_out (Fs, E),
      experts_in (count, E, 2 Fe), experts_out (count, Fe, E): the HELD
      experts alone

The compute type is the type of the weights; RMSNorm and L2-norm
statistics, both gates, the decays, rotary, softmax and the router are
float32 always.

**State carried between chunk programs, of two kinds in one row**
(``init_states``): a KDA layer's matrix state ``(rows, H, d, d)`` in
float32 and its conv tail ``(rows, K - 1, 3 H
d)`` are of FIXED size; an MLA layer's latent cache ``(rows, positions,
rank + rope)`` GROWS with the document (``cache_positions``). Beside
them one position counter and the counts (``state_counters``). A
padding lane (``lengths``) neither decays nor writes the matrix state
and is not taken into the conv tail.
"""

from __future__ import annotations

import dataclasses
from typing import Any, ClassVar, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp

from code_intelligence_tpu.models.blocks import (
    CarriedCounts, Counts, config_from_dict, embed, held_experts, l2_norm,
    latent_block, matmul, rms_norm, valid_lanes)
from code_intelligence_tpu.ops import kda, mla, moe, ssd

# published switches the encoder implements one value of: a configuration
# that states another is refused, not guessed
_IMPLEMENTED = {
    "kda_safe_gate": True, "no_kda_lora": True, "use_kda_lora": False,
    "linear_silu": True, "use_qk_norm": True, "group_norm_size": 1,
    "gated_attention_proj_granularity_type": "head_wise",
    "rope_interleave": True, "rope_scaling": None, "q_lora_rank": None,
    "scale_router_input": False, "use_bias": False, "use_qkv_bias": False,
    "value_norm": False, "up_proj_norm": False, "use_nGPT": False,
    "use_mla_nope": False, "score_function": "sigmoid",
    "topk_method": "noaux_tc", "hidden_act": "silu",
}
_SWIGLU_LIMITS = ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list")

# tokens a chunk of the recurrence: ops/kda.py's sub-blocks of 16 hold
# the decays of a gate bounded at -5 a token at any chunk; 64 is the
# published kernels' chunk
_KDA_CHUNK = 64
_KDA_SUB = 16


@dataclasses.dataclass(frozen=True)
class BailingHybridConfig:
    architecture: ClassVar[str] = "bailing_hybrid"

    vocab_size: int
    hidden_size: int = 2560
    intermediate_size: int = 6144
    moe_intermediate_size: int = 768
    moe_shared_expert_intermediate_size: int = 768
    num_hidden_layers: int = 42
    first_k_dense_replace: int = 2
    layer_group_size: int = 6
    num_attention_heads: int = 32
    head_dim: int = 128
    short_conv_kernel_size: int = 4
    kda_lower_bound: float = -5.0
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    num_experts: int = 512             # the router's outputs
    num_shared_experts: int = 1
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    rms_norm_eps: float = 1e-6
    rope_theta: float = 6000000.0
    # a SwiGLU clamp a layer; every held layer's must be 0
    expert_swiglu_limit_list: Tuple[float, ...] = ()
    share_expert_swiglu_limit_list: Tuple[float, ...] = ()
    # the share: (first expert held, how many), None = all of them
    experts_held: Optional[Tuple[int, int]] = None
    # serving: positions one document's latent cache can hold
    kv_positions: int = 16384
    state_dtype: Any = jnp.bfloat16    # the latent cache's, the conv tails'

    def __post_init__(self):
        object.__setattr__(self, "experts_held", held_experts(
            self.experts_held, self.num_experts))
        object.__setattr__(self, "state_dtype", jnp.dtype(self.state_dtype))
        for name in _SWIGLU_LIMITS:
            limits = tuple(getattr(self, name))[:self.num_hidden_layers]
            object.__setattr__(self, name, limits)
            if any(limits):
                raise NotImplementedError(
                    f"{name} is non-zero in a layer held ({limits}): the "
                    "config does not say where the clamp sits, and it is "
                    "not guessed")
        if self.num_experts % self.n_group:
            raise ValueError("n_group must divide num_experts")
        if not 0 <= self.first_k_dense_replace <= self.num_hidden_layers:
            raise ValueError("first_k_dense_replace exceeds the layers")
        if not -10 <= self.kda_lower_bound < 0:
            raise ValueError(
                f"kda_lower_bound {self.kda_lower_bound}: ops/kda.py holds "
                "a gate bounded in [-10, 0) a token")

    @classmethod
    def from_dict(cls, model: Mapping, **extra) -> "BailingHybridConfig":
        """From a published ``config.json``'s keys; a switch the encoder
        implements one value of (``_IMPLEMENTED``) is refused at any
        other. Of a share, its ``num_experts`` counts the experts
        HELD."""
        for key, value in _IMPLEMENTED.items():
            if key in model and model[key] != value:
                raise ValueError(
                    f"{key}={model[key]!r} is not implemented (only "
                    f"{value!r})")
        return config_from_dict(cls, model, "num_experts", **extra)

    def is_latent(self, layer: int) -> bool:
        return (layer + 1) % self.layer_group_size == 0

    @property
    def latent_layers(self) -> Tuple[int, ...]:
        return tuple(i for i in range(self.num_hidden_layers)
                     if self.is_latent(i))

    @property
    def kda_layers(self) -> Tuple[int, ...]:
        return tuple(i for i in range(self.num_hidden_layers)
                     if not self.is_latent(i))

    @property
    def kda_dim(self) -> int:
        return self.num_attention_heads * self.head_dim

    @property
    def latent_dim(self) -> int:
        """What one token caches a latent layer: ``c_kv`` and the shared
        rotary key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def n_moe_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace


class BailingHybridEncoder(CarriedCounts):
    """The encoder contract (`models/contract.py`) over the hybrid."""

    # the KDA and the latent layers whose core the program ran on a
    # Pallas kernel, as each op's ``core_is_kernel`` said, and the expert
    # layers whose grouped matmuls it did (``gmm_is_kernel``)
    counts = Counts(sets=("kda_kernel_layers", "attention_kernel_layers",
                          "expert_kernel_layers"))

    def __init__(self, config: BailingHybridConfig, dtype=jnp.bfloat16):
        self.config = config
        self.dtype = jnp.dtype(dtype)  # of the weights it will be handed
        self._inv_freq = mla.yarn_inv_freq(
            config.qk_rope_head_dim, config.rope_theta)
        self._scale = mla.softmax_scale(
            config.qk_nope_head_dim + config.qk_rope_head_dim, None)

    # -- contract --------------------------------------------------------

    @property
    def out_dim(self) -> int:
        return self.config.hidden_size

    def cache_positions(self, positions=None) -> int:
        """Positions a latent layer's cache is allocated at for
        documents of up to ``positions`` tokens: the smallest of
        ``kv_positions`` halved that holds them (a single-chunk group's
        bucket is its own), so that the groups of a call compile a few
        cache sizes and not one a length. 0 without a latent layer."""
        cfg = self.config
        if not cfg.latent_layers:
            return 0
        if positions is None:
            return cfg.kv_positions
        if positions > cfg.kv_positions:
            raise ValueError(
                f"a document of {positions} positions does not fit the "
                f"latent cache of kv_positions={cfg.kv_positions}")
        size = cfg.kv_positions
        while size % 2 == 0 and size // 2 >= positions:
            size //= 2
        return size

    def window_positions(self, positions=None) -> int:
        return 0  # no layer attends under a window: no ring

    def init_states(self, batch: int, positions=None):
        cfg = self.config
        H, d = cfg.num_attention_heads, cfg.head_dim
        S = self.cache_positions(positions)
        return {
            "kda": tuple(jnp.zeros((batch, H, d, d), jnp.float32)
                         for _ in cfg.kda_layers),
            "conv": tuple(jnp.zeros(
                (batch, cfg.short_conv_kernel_size - 1, 3 * cfg.kda_dim),
                cfg.state_dtype) for _ in cfg.kda_layers),
            "latent": tuple(
                jnp.zeros((batch, S, cfg.latent_dim), cfg.state_dtype)
                for _ in cfg.latent_layers),
            "pos": jnp.zeros((), jnp.int32),
            "counts": self.counts.zeros(),
        }

    def state_bytes_per_row(self, max_len=None) -> int:
        """Bytes of carried state one row holds for a document of
        ``max_len`` tokens: the fixed part (matrix states, conv tails)
        plus the part that grows with the document (latent caches)."""
        cfg = self.config
        fixed = len(cfg.kda_layers) * (
            cfg.kda_dim * cfg.head_dim * 4
            + (cfg.short_conv_kernel_size - 1) * 3 * cfg.kda_dim
            * cfg.state_dtype.itemsize)
        grows = len(cfg.latent_layers) * self.cache_positions(max_len) \
            * cfg.latent_dim * cfg.state_dtype.itemsize
        return fixed + grows

    def counter_attrs(self, counted) -> dict:
        """`models/blocks.py::CarriedCounts`' and ``kda_layers``, the
        configuration's."""
        attrs = super().counter_attrs(counted)
        if counted:
            attrs["kda_layers"] = len(self.config.kda_layers)
        return attrs

    def encode(self, params, tokens, states, lengths=None):
        """One chunk: ``tokens`` ``(B, T)`` with the carried ``states``
        in, ``(hidden (B, T, out_dim) float32, new states)`` out.
        ``lengths`` ``(B,)``, where the caller knows them, are each
        row's valid tokens in this chunk: the lanes after them are
        padding, which leaves the matrix states and the conv tails as
        they were, which latent attention never lets reach a valid token
        (causal) and which is not routed to any expert."""
        cfg = self.config
        dtype = params["embedding"].dtype
        B, T = tokens.shape
        h = embed(params, tokens)
        pos = states["pos"]
        if lengths is None:
            lengths = jnp.full((B,), T, jnp.int32)
        valid = valid_lanes(lengths, T)
        kda_states, tails, latents = [], [], []
        rows = busiest = jnp.zeros((), jnp.int32)
        for i in range(cfg.num_hidden_layers):
            p = params["layers"][f"layer_{i}"]
            if cfg.is_latent(i):
                with jax.named_scope(f"attention_{i}"):
                    out, cache = latent_block(
                        p, h, states["latent"][len(latents)], pos, dtype,
                        heads=cfg.num_attention_heads,
                        nope=cfg.qk_nope_head_dim, rope=cfg.qk_rope_head_dim,
                        v_dim=cfg.v_head_dim, rank=cfg.kv_lora_rank,
                        eps=cfg.rms_norm_eps, inv_freq=self._inv_freq,
                        rope_factor=1.0, scale=self._scale,
                        q_low_rank=False, head_gate=True)
                latents.append(cache)
            else:
                n = len(kda_states)
                with jax.named_scope(f"kda_{i}"):
                    out, S, tail = self._kda(
                        p, h, states["kda"][n], states["conv"][n], valid,
                        lengths, dtype)
                kda_states.append(S)
                tails.append(tail)
            h = h + out
            u = rms_norm(h, p["ffn_norm"], cfg.rms_norm_eps)
            if i < cfg.first_k_dense_replace:
                with jax.named_scope(f"mlp_{i}"):
                    h = h + moe.swiglu(u, p["w_in"], p["w_out"], dtype)
            else:
                with jax.named_scope(f"moe_{i}"):
                    out, per_expert = moe.expert_layer(
                        p, u.reshape(B * T, -1), valid.reshape(-1), dtype,
                        n_group=cfg.n_group, topk_group=cfg.topk_group,
                        top_k=cfg.num_experts_per_tok,
                        scaling=cfg.routed_scaling_factor,
                        norm_topk_prob=cfg.norm_topk_prob,
                        first=cfg.experts_held[0],
                        shared=bool(cfg.num_shared_experts))
                h = h + out.reshape(B, T, -1)
                rows = rows + per_expert.sum()
                busiest = busiest + per_expert.max()
        with jax.named_scope("final_norm"):
            out = rms_norm(h, params["final_norm"], cfg.rms_norm_eps)
        ran = jnp.int32(1 if cfg.n_moe_layers else 0)
        backend = jax.default_backend()
        on_kernel = sum(mla.core_is_kernel(
            backend, dtype, T, cache.shape[1], cfg.num_attention_heads,
            cfg.qk_nope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank)
            for cache in latents)
        kda_on_kernel = len(kda_states) * kda.core_is_kernel(
            backend, dtype, T, cfg.num_attention_heads, cfg.head_dim,
            cfg.head_dim, _KDA_CHUNK, _KDA_SUB)
        new_states = {
            "kda": tuple(kda_states), "conv": tuple(tails),
            "latent": tuple(latents), "pos": pos + T,
            "counts": self.counts.update(
                states["counts"], rows, busiest, ran,
                kda_kernel_layers=kda_on_kernel,
                attention_kernel_layers=on_kernel,
                expert_kernel_layers=moe.kernel_layers(
                    params["layers"], B * T, cfg.num_experts_per_tok)),
        }
        return out, new_states

    # -- layers ----------------------------------------------------------

    def _kda(self, p, h, S, tail, valid, lengths, dtype):
        """``KDA(RMSNorm(h))`` of the module's docstring over one chunk:
        ``(out (b, T, E) float32, matrix state, conv tail)``."""
        cfg = self.config
        b, T, _ = h.shape
        H, d, D = cfg.num_attention_heads, cfg.head_dim, cfg.kda_dim
        u = rms_norm(h, p["norm"], cfg.rms_norm_eps).astype(dtype)
        with jax.named_scope("qkv_proj"):
            qkv = matmul(u, p["qkv"], dtype)
            # kept in float32: the decay gate feeds an exp of a running sum
            fgb = matmul(u, p["gates"])
        with jax.named_scope("conv1d"):
            zero = jnp.zeros((3 * D,), jnp.float32)     # use_bias: false
            qkv, new_tail = ssd.causal_conv1d(qkv, p["conv_w"], zero, tail,
                                              lengths=lengths)
            qkv = jax.nn.silu(qkv)
        with jax.named_scope("gates"):
            q, k, v = (qkv[..., j * D:(j + 1) * D].reshape(b, T, H, d)
                       for j in range(3))
            q = l2_norm(q) * d ** -0.5
            k = l2_norm(k)
            rate = jnp.exp(p["A_log"].astype(jnp.float32))[:, None]
            g = cfg.kda_lower_bound * jax.nn.sigmoid(rate * (
                fgb[..., :D] + p["dt_bias"].astype(jnp.float32)
            ).reshape(b, T, H, d))
            beta = jax.nn.sigmoid(fgb[..., 2 * D:])
            # a padding lane decays nothing and writes nothing
            g = jnp.where(valid[..., None, None], g, 0.0)
            beta = jnp.where(valid[..., None], beta, 0.0)
        with jax.named_scope("kda_core"):
            o, S_new = kda.kda_scan(
                q, k, v, g, beta, S, _KDA_CHUNK, mxu_dtype=dtype,
                sub=_KDA_SUB)
        with jax.named_scope("gated_norm"):
            o = rms_norm(o, p["o_norm"], cfg.rms_norm_eps) \
                * jax.nn.sigmoid(fgb[..., D:2 * D]).reshape(b, T, H, d)
        with jax.named_scope("o_proj"):
            out = matmul(o.reshape(b, T, D), p["o"])
        return out, S_new, new_tail.astype(tail.dtype)
