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

from code_intelligence_tpu.models.granite_hybrid import _matmul, _rms_norm
from code_intelligence_tpu.ops import mla, moe


def share_of(model: Mapping, count_key: str) -> dict:
    """What a configuration of a SHARE says to its dataclass: the file's
    ``experts_held: {"first", "count", "of"}`` beside a ``count_key``
    that counts the experts held becomes the router's width under
    ``count_key`` and ``experts_held = (first, count)``; nothing for a
    mapping without the block."""
    held = model.get("experts_held")
    if not isinstance(held, Mapping):
        return {}
    if model.get(count_key, held["count"]) != held["count"]:
        raise ValueError(
            f"{count_key} {model[count_key]} is not the count of "
            f"experts_held {dict(held)}")
    return {count_key: held["of"],
            "experts_held": (held["first"], held["count"])}


def latent_block(p, h, cache, pos, dtype, *, heads: int, nope: int,
                 rope: int, v_dim: int, rank: int, eps: float, inv_freq,
                 rope_factor: float, scale: float, q_low_rank: bool = True,
                 head_gate: bool = False):
    """``MLA(RMSNorm(h))`` of the module's docstring over one chunk,
    through the latent ``cache`` at ``pos``: ``(out (b, T, E) float32,
    cache)``. One copy for every model with latent attention; what such
    models differ in are two placements: a query made in two steps
    through a normed low-rank ``c_q`` (leaves ``q_a``, ``q_norm``,
    ``q_b``) or by one matrix ``q`` (``q_low_rank=False``), and
    ``head_gate``: each head's output times ``sigmoid(u w_h)`` (leaf
    ``gate`` ``(E, heads)``) before ``o``. Named scopes ``q_proj``,
    ``kv_latent``, ``rope``, ``mla_core``, ``gate`` (where gated),
    ``o_proj``."""
    b, T, _ = h.shape
    u = _rms_norm(h, p["norm"], eps).astype(dtype)
    with jax.named_scope("q_proj"):
        if q_low_rank:
            c_q = _rms_norm(_matmul(u, p["q_a"]), p["q_norm"], eps)
            q = _matmul(c_q, p["q_b"], dtype)
        else:
            q = _matmul(u, p["q"], dtype)
        q = q.reshape(b, T, heads, nope + rope)
    with jax.named_scope("kv_latent"):
        kv = _matmul(u, p["kv_a"])
        c_kv = _rms_norm(kv[..., :rank], p["kv_norm"], eps)
    with jax.named_scope("rope"):
        positions = pos + jnp.arange(T)
        q_pe = mla.apply_rope(q[..., nope:], positions, inv_freq,
                              rope_factor)
        k_pe = mla.apply_rope(kv[..., rank:], positions, inv_freq,
                              rope_factor)
    latent = jnp.concatenate([c_kv, k_pe], axis=-1)
    with jax.named_scope("mla_core"):
        out, cache = mla.mla_cached(
            q[..., :nope], q_pe, latent, cache, pos, p["kv_b"], scale,
            v_dim, mxu_dtype=dtype)
    if head_gate:
        with jax.named_scope("gate"):
            out = out * jax.nn.sigmoid(_matmul(u, p["gate"]))[..., None]
    with jax.named_scope("o_proj"):
        out = _matmul(out.reshape(b, T, heads * v_dim), p["o"])
    return out, cache


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
        held = self.experts_held or (0, self.n_routed_experts)
        object.__setattr__(self, "experts_held", tuple(int(v) for v in held))
        object.__setattr__(self, "state_dtype", jnp.dtype(self.state_dtype))
        if self.rope_scaling is not None:  # hashable, as a frozen field is
            object.__setattr__(self, "rope_scaling", tuple(sorted(
                dict(self.rope_scaling).items())))
        if self.scoring_func != "sigmoid" or self.topk_method != "noaux_tc":
            raise ValueError(
                "only scoring_func 'sigmoid' with topk_method 'noaux_tc' is "
                f"implemented, not {self.scoring_func!r} / "
                f"{self.topk_method!r}")
        first, count = self.experts_held
        if not (0 <= first and 0 < count
                and first + count <= self.n_routed_experts):
            raise ValueError(
                f"experts_held {self.experts_held} lies outside the "
                f"router's {self.n_routed_experts} experts")
        if self.n_routed_experts % self.n_group:
            raise ValueError("n_group must divide n_routed_experts")
        if not 0 <= self.first_k_dense_replace <= self.num_hidden_layers:
            raise ValueError("first_k_dense_replace exceeds the layers")

    @classmethod
    def from_dict(cls, model: Mapping, **extra) -> "DeepseekV3Config":
        """From a published ``config.json``'s keys; keys that do not
        shape the encoder are passed over. A configuration of a share
        carries ``experts_held: {"first", "count", "of"}``: its
        ``n_routed_experts`` then counts the experts HELD, and ``of`` is
        the router's width."""
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in model.items() if k in names}
        return cls(**{**kw, **share_of(model, "n_routed_experts"), **extra})

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


class DeepseekV3Encoder:
    """The encoder contract (`models/contract.py`) over DeepSeek-V3."""

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

    def cache_positions(self, positions=None) -> int:
        """Positions the latent cache is allocated at for documents of
        up to ``positions`` tokens: their own length for short ones (one
        chunk), the configured maximum for everything longer, so that
        every multi-chunk group runs one compiled shape."""
        cfg = self.config
        if positions is None:
            return cfg.kv_positions
        if positions > cfg.kv_positions:
            raise ValueError(
                f"a document of {positions} positions does not fit the "
                f"latent cache of kv_positions={cfg.kv_positions}")
        return positions if positions <= cfg.kv_positions // 4 \
            else cfg.kv_positions

    def window_positions(self, positions=None) -> int:
        return 0  # no layer attends under a window: no ring

    def init_states(self, batch: int, positions=None):
        cfg = self.config
        S = self.cache_positions(positions)
        return {
            "latent": tuple(
                jnp.zeros((batch, S, cfg.latent_dim), cfg.state_dtype)
                for _ in range(cfg.num_hidden_layers)),
            "pos": jnp.zeros((), jnp.int32),
            "counts": jnp.zeros((len(moe.COUNTERS) + 1,), jnp.int32),
        }

    def state_bytes_per_row(self, max_len=None) -> int:
        """Bytes of latent cache one row holds for a document of
        ``max_len`` tokens; all of the state grows with the document."""
        cfg = self.config
        return cfg.num_hidden_layers * self.cache_positions(max_len) \
            * cfg.latent_dim * cfg.state_dtype.itemsize

    def state_counters(self, states):
        """The counts the expert layers have kept since ``init_states``
        (``ops/moe.py::COUNTERS``) and, last, the attention layers whose
        core the group's programs ran on the Pallas kernel (a device
        array; ``counter_attrs`` names them)."""
        return states["counts"]

    def counter_attrs(self, counted) -> dict:
        """Span attributes from the fetched ``state_counters`` of a
        flush's groups: ``ops/moe.py::counter_attrs`` and
        ``attention_kernel_layers``, the attention layers on the Pallas
        core in a group's programs (``ops/mla.py::core_is_kernel``: all
        programs of a group run one chunk length against one cache
        size, so one answer a group), averaged over the groups."""
        attrs = moe.counter_attrs(counted, self.config.n_moe_layers,
                                  self.config.experts_held[1])
        if counted:
            attrs["attention_kernel_layers"] = \
                sum(int(c[-1]) for c in counted) / len(counted)
        return attrs

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
        with jax.named_scope("embedding"):
            h = jnp.take(params["embedding"], tokens, axis=0).astype(
                jnp.float32)
        pos = states["pos"]
        valid = None
        if lengths is not None:
            valid = (jnp.arange(T)[None, :] < lengths[:, None]).reshape(-1)
        latents = []
        rows = busiest = jnp.zeros((), jnp.int32)
        for i in range(cfg.num_hidden_layers):
            p = params["layers"][f"layer_{i}"]
            with jax.named_scope(f"attention_{i}"):
                out, cache = self._attention(p, h, states["latent"][i], pos,
                                             dtype)
            h = h + out
            latents.append(cache)
            u = _rms_norm(h, p["ffn_norm"], cfg.rms_norm_eps)
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
            out = _rms_norm(h, params["final_norm"], cfg.rms_norm_eps)
        ran = jnp.int32(1 if cfg.n_moe_layers else 0)
        on_kernel = sum(mla.core_is_kernel(
            jax.default_backend(), dtype, T, cache.shape[1],
            cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.v_head_dim,
            cfg.kv_lora_rank) for cache in latents)
        new_states = {
            "latent": tuple(latents),
            "pos": pos + T,
            # sums since init_states, then what this program's rule said
            "counts": states["counts"].at[:-1].add(
                jnp.stack([rows, busiest, ran])).at[-1].set(on_kernel),
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
