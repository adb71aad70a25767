"""Qwen3-Next style encoder: Gated DeltaNet (a delta rule with ONE decay
a head, two value heads a key head) in three layers of four and gated
softmax attention (a quarter of the head rotary, the output gate taken
out of a doubled ``q_proj``) in the fourth; every layer an expert layer
with a sigmoid-gated shared expert, of which this chip holds a SHARE.

Published as ``model_type: qwen3_next``; the field names of
:class:`Qwen3NextConfig` are those of the model's ``config.json``.
Equations (pre-norm residual blocks, ``eps`` = ``rms_norm_eps``, no
biases; ``ops/gdn.py``, ``ops/attention.py`` and ``ops/moe.py`` hold the
three mechanisms):

    norm(x; w) = x * rsqrt(mean(x^2) + eps) * (1 + w)     (zero-centred)
    h = E[ids]
    layer i:  h += mixer_i(norm(h; w1));  h += MoE_i(norm(h; w2))
      mixer_i = Attn where (i + 1) % full_attention_interval == 0, else GDN
    out = norm(h; w_f)                 # pooled; no LM head, no MTP module

    GDN(u), Hk key heads and Hv value heads of dk | dv:
      [q | k | v] = silu(conv_K(u W_qkv))   (depthwise causal conv over
        time, K = linear_conv_kernel_dim, no bias)
      z = u W_z;  [b | a] = u W_ba
      q_h = l2norm(q_h) / sqrt(dk);  k_h = l2norm(k_h)
      beta = sigmoid(b);  g = -exp(A_log) * softplus(a + dt_bias)   (Hv,)
      value head j reads key head j // (Hv / Hk):
        S' = exp(g) S;  S = S' + beta k (v - S'^T k)^T;  o = S^T q
      GDN = [rmsnorm_dv(o_j) * w_o * silu(z_j)] W_out     (plain w_o)
    Attn(u), Hq query heads on Hkv key/value heads of d:
      [q_h | gate_h] = u W_q a head;  k = u W_k;  v = u W_v
      q = norm_d(q; w_q), k = norm_d(k; w_k) a head (zero-centred)
      rotary (``rotate_half`` pairs) on the first partial_rotary_factor *
        d dims; causal softmax at scale d^-0.5 over the growing cache
      Attn = [o * sigmoid(gate)] W_o
    MoE(u) = sum_i w_i E_i(u) + sigmoid(u w_sg) * E_shared(u): softmax
      over the router's logits, top num_experts_per_tok, renormalised
      (``ops/moe.route(score_func="softmax")``), every expert SwiGLU

What the published config does not settle is listed in the benchmark
configuration's ``assumed`` (the placements the family's public
modelling code has: the zero-centred norms, the plain gated norm, the
l2 norm's eps, which value head reads which key head, the shared
expert's gate; the columns of ``W_qkv`` / ``W_z`` / ``W_ba`` head-major
and not interleaved by key-head group).

**The share.** ``experts_held = (first, count)``, as
``models/deepseek_v3.py`` has it.

A plain class, not a Flax module: it owns no parameters. The tree it
reads (``benchmark/reference/qwen3_next.py::init_params`` makes one from
a seed), matrices as ``(in, out)``, a dict of leaves a layer:

    embedding (V, E), final_norm (E,)
    layers/layer_<i>, every layer: norm, ffn_norm (E,); router (E,
      num_experts); shared_in (E, 2 Fs), shared_out (Fs, E), shared_gate
      (E, 1); experts_in (count, E, 2 Fe), experts_out (count, Fe, E):
      the HELD experts alone  ([gate | up])
    a GDN layer besides: qkv (E, 2 Hk dk + Hv dv): [q | k | v]; conv_w
      (2 Hk dk + Hv dv, K); z (E, Hv dv); ba (E, 2 Hv): [b | a]; A_log,
      dt_bias (Hv,) float32; o_norm (dv,); o (Hv dv, E)
    an attention layer besides: qkv (E, 2 Hq d + 2 Hkv d): a head's
      [q | gate], all heads, then [k | v]; q_norm, k_norm (d,);
      o (Hq d, E)

The compute type is the type of the weights; norm and l2-norm
statistics, both gates, the decays, rotary, softmax and the router are
float32 always.

**State carried between chunk programs, of two kinds in one row**
(``init_states``): a GDN layer's matrix state ``(rows, Hv, dk, dv)`` in
float32 and its conv tail ``(rows, K - 1, 2 Hk dk + Hv dv)`` are of
FIXED size; an attention layer's keys and values ``(rows, Hkv,
positions, d)`` GROW with the document (``cache_positions``). Beside
them one position counter and the counts (``state_counters``). A padding
lane (``lengths``) neither decays nor writes the matrix state and is not
taken into the conv tail.
"""

from __future__ import annotations

import dataclasses
from typing import Any, ClassVar, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp

from code_intelligence_tpu.models.blocks import (
    CarriedCounts, Counts, GrowingCache, config_from_dict, embed,
    held_experts, l2_norm, matmul, rms_norm, rope_qk, valid_lanes)
from code_intelligence_tpu.ops import attention, gdn, mla, moe, ssd

# published switches the encoder implements one value of: a configuration
# that states another is refused, not guessed
_IMPLEMENTED = {
    "hidden_act": "silu", "use_sliding_window": False, "rope_scaling": None,
    "decoder_sparse_step": 1, "mlp_only_layers": [],
}

# tokens a chunk of the recurrence: the published kernels' chunk
_GDN_CHUNK = 64


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
    architecture: ClassVar[str] = "qwen3_next"

    vocab_size: int
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    full_attention_interval: int = 4
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 10000000.0
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    num_experts: int = 512             # the router's outputs
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    # the share: (first expert held, how many), None = all of them
    experts_held: Optional[Tuple[int, int]] = None
    # serving: positions one document's key/value cache can hold
    kv_positions: int = 16384
    state_dtype: Any = jnp.bfloat16    # the caches', the conv tails'

    def __post_init__(self):
        object.__setattr__(self, "experts_held", held_experts(
            self.experts_held, self.num_experts))
        object.__setattr__(self, "state_dtype", jnp.dtype(self.state_dtype))
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                "num_key_value_heads must divide num_attention_heads")
        if self.linear_num_value_heads % self.linear_num_key_heads:
            raise ValueError(
                "linear_num_key_heads must divide linear_num_value_heads")
        if self.rotary_dim % 2 or not 0 < self.rotary_dim <= self.head_dim:
            raise ValueError(
                f"partial_rotary_factor {self.partial_rotary_factor} of a "
                f"head of {self.head_dim} is no even number of dims")
        if not self.norm_topk_prob:
            raise ValueError(
                "norm_topk_prob false is not implemented: the softmax "
                "router's weights are those of the chosen, renormalised")

    @classmethod
    def from_dict(cls, model: Mapping, **extra) -> "Qwen3NextConfig":
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

    def is_attention(self, layer: int) -> bool:
        return (layer + 1) % self.full_attention_interval == 0

    @property
    def attention_layers(self) -> Tuple[int, ...]:
        return tuple(i for i in range(self.num_hidden_layers)
                     if self.is_attention(i))

    @property
    def gdn_layers(self) -> Tuple[int, ...]:
        return tuple(i for i in range(self.num_hidden_layers)
                     if not self.is_attention(i))

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def key_dim(self) -> int:
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def value_dim(self) -> int:
        return self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def conv_dim(self) -> int:
        """Channels of ``[q | k | v]``, which the short conv runs over."""
        return 2 * self.key_dim + self.value_dim

    @property
    def n_moe_layers(self) -> int:
        return self.num_hidden_layers  # every layer is an expert layer


def _centred(w):
    """A zero-centred norm weight as ``rms_norm`` multiplies by it."""
    return 1.0 + w.astype(jnp.float32)


class Qwen3NextEncoder(GrowingCache, CarriedCounts):
    """The encoder contract (`models/contract.py`) over the hybrid; the
    sizes its key/value caches are allocated at and the reading of its
    counts are `models/blocks.py`'s."""

    cache_kind = "key/value"
    # the rounds of ``routed_experts``' loop (a layer a program); the
    # rows still going whose matrix states and conv tails a chunk program
    # was handed by the one before it; the linear and the attention
    # layers whose core the program ran on a Pallas kernel, as each op's
    # ``core_is_kernel`` said, and the expert layers whose grouped
    # matmuls it did (``gmm_is_kernel``)
    counts = Counts(sums=("expert_rounds",),
                    totals=("gdn_state_handovers",),
                    sets=("gdn_kernel_layers", "attention_kernel_layers",
                          "expert_kernel_layers"))

    def __init__(self, config: Qwen3NextConfig, dtype=jnp.bfloat16):
        self.config = config
        self.dtype = jnp.dtype(dtype)  # of the weights it will be handed
        self._inv_freq = mla.yarn_inv_freq(
            config.rotary_dim, config.rope_theta)
        self._scale = config.head_dim ** -0.5

    # -- contract --------------------------------------------------------

    @property
    def out_dim(self) -> int:
        return self.config.hidden_size

    def cache_positions(self, positions=None) -> int:
        if not self.config.attention_layers:
            return 0
        return super().cache_positions(positions)

    def init_states(self, batch: int, positions=None):
        cfg = self.config
        S = self.cache_positions(positions)

        def caches():
            return tuple(jnp.zeros(
                (batch, cfg.num_key_value_heads, S, cfg.head_dim),
                cfg.state_dtype) for _ in cfg.attention_layers)

        return {
            "gdn": tuple(jnp.zeros(
                (batch, cfg.linear_num_value_heads, cfg.linear_key_head_dim,
                 cfg.linear_value_head_dim), jnp.float32)
                for _ in cfg.gdn_layers),
            "conv": tuple(jnp.zeros(
                (batch, cfg.linear_conv_kernel_dim - 1, cfg.conv_dim),
                cfg.state_dtype) for _ in cfg.gdn_layers),
            "k": caches(), "v": caches(),
            "pos": jnp.zeros((), jnp.int32),
            "counts": self.counts.zeros(),
        }

    def state_bytes_per_row(self, max_len=None) -> int:
        """Bytes of carried state one row holds for a document of
        ``max_len`` tokens: the fixed part (matrix states, conv tails)
        plus the part that grows with the document (keys and values)."""
        cfg = self.config
        fixed = len(cfg.gdn_layers) * (
            cfg.value_dim * cfg.linear_key_head_dim * 4
            + (cfg.linear_conv_kernel_dim - 1) * cfg.conv_dim
            * cfg.state_dtype.itemsize)
        grows = len(cfg.attention_layers) * self.cache_positions(max_len) \
            * 2 * cfg.num_key_value_heads * cfg.head_dim \
            * cfg.state_dtype.itemsize
        return fixed + grows

    def encode(self, params, tokens, states, lengths=None):
        """One chunk: ``tokens`` ``(B, T)`` with the carried ``states``
        in, ``(hidden (B, T, out_dim) float32, new states)`` out.
        ``lengths`` ``(B,)``, where the caller knows them, are each
        row's valid tokens in this chunk: the lanes after them are
        padding, which leaves the matrix states and the conv tails as
        they were, which attention never lets reach a valid token
        (causal) and which is not routed to any expert."""
        cfg = self.config
        dtype = params["embedding"].dtype
        eps = cfg.rms_norm_eps
        B, T = tokens.shape
        N = B * T
        h = embed(params, tokens)
        pos = states["pos"]
        if lengths is None:
            lengths = jnp.full((B,), T, jnp.int32)
        valid = valid_lanes(lengths, T)
        gdn_states, tails, k_caches, v_caches = [], [], [], []
        rows = busiest = rounds = jnp.zeros((), jnp.int32)
        for i in range(cfg.num_hidden_layers):
            p = params["layers"][f"layer_{i}"]
            if cfg.is_attention(i):
                n = len(k_caches)
                with jax.named_scope(f"attention_{i}"):
                    u = rms_norm(h, _centred(p["norm"]), eps).astype(dtype)
                    out, kc, vc = self._attention(
                        p, u, states["k"][n], states["v"][n], pos, dtype)
                k_caches.append(kc)
                v_caches.append(vc)
            else:
                n = len(gdn_states)
                with jax.named_scope(f"gdn_{i}"):
                    u = rms_norm(h, _centred(p["norm"]), eps).astype(dtype)
                    out, S, tail = self._gdn(
                        p, u, states["gdn"][n], states["conv"][n], valid,
                        lengths, dtype)
                gdn_states.append(S)
                tails.append(tail)
            h = h + out
            with jax.named_scope(f"moe_{i}"):
                m = rms_norm(h, _centred(p["ffn_norm"]), eps)
                out, per_expert = moe.expert_layer(
                    p, m.reshape(N, -1), valid.reshape(-1), dtype,
                    n_group=1, topk_group=1, top_k=cfg.num_experts_per_tok,
                    scaling=1.0, norm_topk_prob=cfg.norm_topk_prob,
                    first=cfg.experts_held[0], shared=True,
                    score_func="softmax")
            h = h + out.reshape(B, T, -1)
            landed = per_expert.sum()
            rows = rows + landed
            busiest = busiest + per_expert.max()
            rounds = rounds + moe.rounds_run(
                landed, N, cfg.num_experts_per_tok, cfg.experts_held[1],
                p["router"].shape[1])
        with jax.named_scope("final_norm"):
            out = rms_norm(h, _centred(params["final_norm"]), eps)
        backend = jax.default_backend()
        on_kernel = sum(attention.core_is_kernel(
            backend, dtype, T, kc.shape[2],
            cfg.num_attention_heads // cfg.num_key_value_heads, cfg.head_dim)
            for kc in k_caches)
        gdn_on_kernel = len(gdn_states) * gdn.core_is_kernel(
            backend, dtype, T, cfg.linear_num_key_heads,
            cfg.linear_num_value_heads, cfg.linear_key_head_dim,
            cfg.linear_value_head_dim, _GDN_CHUNK)
        new_states = {
            "gdn": tuple(gdn_states), "conv": tuple(tails),
            "k": tuple(k_caches), "v": tuple(v_caches), "pos": pos + T,
            "counts": self.counts.update(
                states["counts"], rows, busiest, jnp.int32(1),
                expert_rounds=rounds,
                # a row still going, in a program with positions behind
                # it, was handed its states (a finished row's and a
                # padding row's lengths are 0)
                gdn_state_handovers=jnp.where(
                    pos > 0, jnp.sum(lengths > 0), 0).astype(jnp.int32),
                gdn_kernel_layers=gdn_on_kernel,
                attention_kernel_layers=on_kernel,
                expert_kernel_layers=moe.kernel_layers(
                    params["layers"], N, cfg.num_experts_per_tok)),
        }
        return out, new_states

    # -- layers ----------------------------------------------------------

    def _gdn(self, p, u, S, tail, valid, lengths, dtype):
        """``GDN(u)`` of the module's docstring over one chunk: ``(out
        (b, T, E) float32, matrix state, conv tail)``."""
        cfg = self.config
        b, T, _ = u.shape
        Hk, Hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
        dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
        with jax.named_scope("qkv_proj"):
            qkv = matmul(u, p["qkv"], dtype)
            z = matmul(u, p["z"])
            # kept in float32: the decay gate feeds an exp of a running sum
            ba = matmul(u, p["ba"])
        with jax.named_scope("conv1d"):
            zero = jnp.zeros((cfg.conv_dim,), jnp.float32)      # no bias
            qkv, new_tail = ssd.causal_conv1d(qkv, p["conv_w"], zero, tail,
                                              lengths=lengths)
            qkv = jax.nn.silu(qkv)
        with jax.named_scope("gates"):
            q = qkv[..., :cfg.key_dim].reshape(b, T, Hk, dk)
            k = qkv[..., cfg.key_dim:2 * cfg.key_dim].reshape(b, T, Hk, dk)
            v = qkv[..., 2 * cfg.key_dim:].reshape(b, T, Hv, dv)
            q = l2_norm(q) * dk ** -0.5
            k = l2_norm(k)
            beta = jax.nn.sigmoid(ba[..., :Hv])
            g = -jnp.exp(p["A_log"].astype(jnp.float32)) * jax.nn.softplus(
                ba[..., Hv:] + p["dt_bias"].astype(jnp.float32))
            # a padding lane decays nothing and writes nothing
            g = jnp.where(valid[..., None], g, 0.0)
            beta = jnp.where(valid[..., None], beta, 0.0)
        with jax.named_scope("gdn_core"):
            o, S_new = gdn.gdn_scan(q, k, v, g, beta, S, _GDN_CHUNK,
                                    mxu_dtype=dtype)
        with jax.named_scope("gated_norm"):
            o = rms_norm(o, p["o_norm"], cfg.rms_norm_eps) \
                * jax.nn.silu(z).reshape(b, T, Hv, dv)
        with jax.named_scope("o_proj"):
            out = matmul(o.reshape(b, T, cfg.value_dim), p["o"])
        return out, S_new, new_tail.astype(tail.dtype)

    def _qk_norm(self, p, q, k):
        """The zero-centred RMSNorm a head of the queries and the keys,
        float32 out."""
        eps = self.config.rms_norm_eps
        with jax.named_scope("qk_norm"):
            return (rms_norm(q, _centred(p["q_norm"]), eps),
                    rms_norm(k, _centred(p["k_norm"]), eps))

    def _attention(self, p, u, k_cache, v_cache, pos, dtype):
        """``Attn(u)`` of the module's docstring over one chunk through
        the growing cache: ``(out (b, T, E) float32, keys, values)``."""
        cfg = self.config
        b, T, _ = u.shape
        Hq, Hkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                      cfg.head_dim)
        with jax.named_scope("qkv_proj"):
            qkv = matmul(u, p["qkv"], dtype)
            qg = qkv[..., :2 * Hq * d].reshape(b, T, Hq, 2 * d)
            q, gate = qg[..., :d], qg[..., d:]
            k = qkv[..., 2 * Hq * d:(2 * Hq + Hkv) * d].reshape(b, T, Hkv, d)
            v = qkv[..., (2 * Hq + Hkv) * d:].reshape(b, T, Hkv, d)
        q, k = self._qk_norm(p, q, k)
        q, k = rope_qk(q, k, pos, self._inv_freq, width=cfg.rotary_dim)
        with jax.named_scope("global_core"):
            out, k_cache, v_cache = attention.gqa_cached(
                q, k, v, k_cache, v_cache, pos, self._scale, mxu_dtype=dtype)
        with jax.named_scope("out_gate"):
            out = out * jax.nn.sigmoid(gate.astype(jnp.float32))
        with jax.named_scope("o_proj"):
            out = matmul(out.reshape(b, T, Hq * d), p["o"])
        return out, k_cache, v_cache
