"""Granite 4.0-H style hybrid encoder: Mamba-2 layers with a few GQA
attention layers between them, every layer followed by a gated MLP.

Published as ``model_type: granitemoehybrid`` (dense when
``num_local_experts`` is 0); the field names of
:class:`GraniteHybridConfig` are those of the model's ``config.json``.
Equations (``eps`` = ``rms_norm_eps``, no biases but the conv's):

    h = embedding_multiplier * E[ids]
    every layer:  h += residual_multiplier * mixer(RMSNorm(h))
                  h += residual_multiplier * mlp(RMSNorm(h))
    mlp(u) = (silu(g) * v) @ W_out,  [g, v] = split(u @ W_in)
    out = RMSNorm(h)                     # pooled; the tied LM head is not run

    Mamba-2 mixer: [z, xBC, dt] = split(u @ W_in_proj);
      xBC = silu(causal_conv1d(xBC)); [x, B, C] = split(xBC);
      dt = softplus(dt + dt_bias); A = -exp(A_log);
      y = ssd_scan(x, dt, A, B, C, D)                   (ops/ssd.py)
      mixer = (RMSNorm(y * silu(z)) * w) @ W_out_proj
    attention mixer: causal GQA, no rotary, scale attention_multiplier,
      over the cached and the current positions       (ops/attention.py)

A plain class, not a Flax module: it owns no parameters. The tree it
reads (``benchmark/reference/granite_hybrid.py::init_params`` makes one
from a seed) holds ``embedding``, ``final_norm`` and two groups,
``mamba`` (``norm``, ``in_proj``, ``conv_w``, ``conv_b``, ``dt_bias``,
``A_log``, ``D``, ``gated_norm``, ``out_proj``, ``mlp_norm``, ``mlp_in``,
``mlp_out``) and ``attention`` (``norm``, ``q``, ``k``, ``v``, ``o`` and
the same MLP), matrices as ``(in, out)``: the Mamba layers' leaves stacked on a leading axis of
36, the attention layers' on one of 4, so that a run of Mamba layers is
ONE ``lax.scan`` body whatever its length. The compute type is the type
of the weights it is handed (bfloat16 weights: bfloat16 matmul inputs,
float32 accumulation); RMSNorm statistics, softmax, the decay and the
SSM state are float32 always.

State carried between chunk programs (``init_states``): per Mamba layer
the ``(heads, d_head, d_state)`` matrix state and the conv's last
``d_conv - 1`` inputs, both of fixed size; per attention layer a
key/value cache that grows with the document, allocated at a fixed
number of positions; one position counter.
"""

from __future__ import annotations

import dataclasses
from typing import Any, ClassVar, List, Mapping, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from code_intelligence_tpu.models.blocks import (
    GrowingCache, config_from_dict, embed, matmul, rms_norm)
from code_intelligence_tpu.ops.attention import gqa_cached
from code_intelligence_tpu.ops.ssd import causal_conv1d, ssd_scan


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig:
    architecture: ClassVar[str] = "granite_hybrid"

    vocab_size: int
    hidden_size: int = 2048
    num_hidden_layers: int = 40
    layer_types: Tuple[str, ...] = ()
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    shared_intermediate_size: int = 8192
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_d_conv: int = 4
    mamba_n_groups: int = 1
    mamba_expand: int = 2
    mamba_chunk_size: int = 256
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    rms_norm_eps: float = 1e-5
    # serving: positions one document's key/value cache can hold
    kv_positions: int = 2048
    state_dtype: Any = jnp.float32

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        object.__setattr__(self, "state_dtype", jnp.dtype(self.state_dtype))
        if len(self.layer_types) != self.num_hidden_layers or set(
                self.layer_types) - {"mamba", "attention"}:
            raise ValueError(
                f"layer_types must name {self.num_hidden_layers} layers, "
                f"each 'mamba' or 'attention': {self.layer_types}")
        if self.mamba_n_groups != 1:
            raise ValueError("only mamba_n_groups == 1 is implemented")
        if self.d_inner != self.mamba_expand * self.hidden_size:
            raise ValueError(
                "mamba_n_heads * mamba_d_head must equal "
                "mamba_expand * hidden_size")

    @classmethod
    def from_dict(cls, model: Mapping, **extra) -> "GraniteHybridConfig":
        """From a published ``config.json``'s keys (``logits_scaling``,
        ``rope_theta``, ... do not shape the encoder)."""
        return config_from_dict(cls, model, **extra)

    @property
    def d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    def runs(self) -> List[Tuple[str, int, int]]:
        """``(kind, first layer, count)`` of each run of like layers."""
        out: List[Tuple[str, int, int]] = []
        for i, kind in enumerate(self.layer_types):
            if out and out[-1][0] == kind:
                out[-1] = (kind, out[-1][1], out[-1][2] + 1)
            else:
                out.append((kind, i, 1))
        return out

    def count(self, kind: str) -> int:
        return sum(t == kind for t in self.layer_types)


class GraniteHybridEncoder(GrowingCache):
    """The encoder contract (`models/contract.py`) over the hybrid; the
    sizes its key/value cache is allocated at are
    `models/blocks.py::GrowingCache`'s."""

    cache_kind = "key/value"

    def __init__(self, config: GraniteHybridConfig, dtype=jnp.bfloat16):
        self.config = config
        # the type of the weights it will be handed: the conv tail and the
        # key/value cache are carried in it
        self.dtype = jnp.dtype(dtype)

    # -- contract --------------------------------------------------------

    @property
    def out_dim(self) -> int:
        return self.config.hidden_size

    def init_states(self, batch: int, positions=None):
        cfg, dtype = self.config, self.dtype
        S = self.cache_positions(positions)
        # head-major, as ``ops/attention.py`` reads them
        kv = (cfg.count("attention"), batch, cfg.num_key_value_heads, S,
              cfg.head_dim)
        runs = [n for kind, _, n in cfg.runs() if kind == "mamba"]
        return {
            "ssm": tuple(jnp.zeros(
                (n, batch, cfg.mamba_n_heads, cfg.mamba_d_head,
                 cfg.mamba_d_state), cfg.state_dtype) for n in runs),
            "conv": tuple(jnp.zeros(
                (n, batch, cfg.mamba_d_conv - 1, cfg.conv_dim), dtype)
                for n in runs),
            "k": jnp.zeros(kv, dtype), "v": jnp.zeros(kv, dtype),
            "pos": jnp.zeros((), jnp.int32),
        }

    def state_bytes_per_row(self, max_len=None) -> int:
        """Bytes of carried state one row holds for a document of
        ``max_len`` tokens: the fixed part (SSM state, conv tail) plus
        the part that grows with the document (keys and values)."""
        cfg = self.config
        item = self.dtype.itemsize
        fixed = cfg.count("mamba") * (
            cfg.mamba_n_heads * cfg.mamba_d_head * cfg.mamba_d_state
            * cfg.state_dtype.itemsize
            + (cfg.mamba_d_conv - 1) * cfg.conv_dim * item)
        grows = cfg.count("attention") * self.cache_positions(max_len) \
            * 2 * cfg.num_key_value_heads * cfg.head_dim * item
        return fixed + grows

    def state_counters(self, states):
        return None  # the state holds no counts

    def counter_attrs(self, counted) -> dict:
        return {}

    def encode(self, params, tokens, states, lengths=None):
        """One chunk: ``tokens`` ``(B, T)`` with the carried ``states``
        in, ``(hidden (B, T, out_dim) float32, new states)`` out.
        ``lengths`` are not read: scan and attention run every lane."""
        cfg = self.config
        dtype = params["embedding"].dtype
        res = cfg.residual_multiplier
        h = embed(params, tokens, cfg.embedding_multiplier)
        pos = states["pos"]
        ssm, conv, k_cache, v_cache = [], [], [], []
        mamba_at = attn_at = 0
        for kind, first, n in cfg.runs():
            if kind == "mamba":
                run = len(ssm)

                def body(h, xs, first=first):
                    i, S, tail = xs
                    p = jax.tree.map(lambda a: a[i], params["mamba"])
                    with jax.named_scope(f"mamba_{first}"):
                        out, S, tail = self._mamba(p, h, S, tail, dtype)
                    h = h + res * out
                    with jax.named_scope(f"mlp_{first}"):
                        h = h + res * self._mlp(p, h, dtype)
                    return h, (S, tail)

                h, (S, tail) = lax.scan(
                    body, h, (jnp.arange(mamba_at, mamba_at + n),
                              states["ssm"][run], states["conv"][run]))
                ssm.append(S)
                conv.append(tail)
                mamba_at += n
            else:
                for j in range(n):
                    a = attn_at + j
                    p = jax.tree.map(lambda w: w[a], params["attention"])
                    with jax.named_scope(f"attention_{first + j}"):
                        out, kc, vc = self._attention(
                            p, h, states["k"][a], states["v"][a], pos, dtype)
                    h = h + res * out
                    with jax.named_scope(f"mlp_{first + j}"):
                        h = h + res * self._mlp(p, h, dtype)
                    k_cache.append(kc)
                    v_cache.append(vc)
                attn_at += n
        with jax.named_scope("final_norm"):
            out = rms_norm(h, params["final_norm"], cfg.rms_norm_eps)
        new_states = {
            "ssm": tuple(ssm), "conv": tuple(conv),
            "k": jnp.stack(k_cache), "v": jnp.stack(v_cache),
            "pos": pos + tokens.shape[1],
        }
        return out, new_states

    # -- layers ----------------------------------------------------------

    def _mlp(self, p, h, dtype):
        cfg = self.config
        u = rms_norm(h, p["mlp_norm"], cfg.rms_norm_eps)
        g, v = jnp.split(matmul(u, p["mlp_in"], dtype), 2, axis=-1)
        act = jax.nn.silu(g.astype(jnp.float32)) * v.astype(jnp.float32)
        return matmul(act, p["mlp_out"])

    def _mamba(self, p, h, S, tail, dtype):
        cfg = self.config
        b, T, _ = h.shape
        di, ds, H = cfg.d_inner, cfg.mamba_d_state, cfg.mamba_n_heads
        u = rms_norm(h, p["norm"], cfg.rms_norm_eps).astype(dtype)
        # one product, kept in float32: the step size feeds an exp and
        # keeps its accumulation; z and xBC go on in the compute type
        # (the conv's carried tail is xBC as the conv read it)
        zxd = matmul(u, p["in_proj"])
        z = zxd[..., :di].astype(dtype)
        xBC = zxd[..., di:di + cfg.conv_dim].astype(dtype)
        dt = jax.nn.softplus(zxd[..., di + cfg.conv_dim:]
                             + p["dt_bias"].astype(jnp.float32))
        with jax.named_scope("conv1d"):
            xBC, tail = causal_conv1d(xBC, p["conv_w"], p["conv_b"], tail)
            xBC = jax.nn.silu(xBC)
        x = xBC[..., :di].reshape(b, T, H, cfg.mamba_d_head)
        B, C = xBC[..., di:di + ds], xBC[..., di + ds:]
        with jax.named_scope("ssd_scan"):
            y, S_new = ssd_scan(
                x, dt, -jnp.exp(p["A_log"].astype(jnp.float32)), B, C,
                p["D"], S.astype(jnp.float32), cfg.mamba_chunk_size,
                mxu_dtype=dtype)
        with jax.named_scope("gated_norm"):
            y = y.reshape(b, T, di) * jax.nn.silu(z.astype(jnp.float32))
            y = rms_norm(y, p["gated_norm"], cfg.rms_norm_eps)
        return matmul(y, p["out_proj"]), S_new.astype(S.dtype), tail

    def _attention(self, p, h, k_cache, v_cache, pos, dtype):
        cfg = self.config
        b, T, _ = h.shape
        Hq, Hkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                      cfg.head_dim)
        u = rms_norm(h, p["norm"], cfg.rms_norm_eps).astype(dtype)
        q = matmul(u, p["q"], dtype).reshape(b, T, Hq, d)
        k = matmul(u, p["k"], dtype).reshape(b, T, Hkv, d)
        v = matmul(u, p["v"], dtype).reshape(b, T, Hkv, d)
        out, k_cache, v_cache = gqa_cached(
            q, k, v, k_cache, v_cache, pos, cfg.attention_multiplier,
            mxu_dtype=dtype)
        return matmul(out.reshape(b, T, Hq * d), p["o"]), k_cache, v_cache
