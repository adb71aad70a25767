"""What the large encoders share, one copy each: the layer beneath
``models/<architecture>.py``. A model's file imports this module,
`models/windowed_caches.py` and ``ops/``, never another model's file
(``tests/test_model_blocks.py`` holds that), so a change to one model
is a change to its own cells alone. ``ops/``'s functions are called
through their modules (``mla.apply_rope(...)``): the benchmark's
controls and the tests replace them there for a run.
"""

from __future__ import annotations

import dataclasses
from typing import Mapping, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from code_intelligence_tpu.ops import mla, moe


def rms_norm(x, w, eps):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return xf * lax.rsqrt(var + eps) * w.astype(jnp.float32)


def l2_norm(x, eps=1e-6):
    """``x / sqrt(sum x^2 + eps)`` over the last axis, float32: the
    delta-rule layers' norm of a head's ``q`` and ``k``."""
    xf = x.astype(jnp.float32)
    return xf * lax.rsqrt(jnp.sum(xf * xf, axis=-1, keepdims=True) + eps)


def matmul(x, w, out_dtype=jnp.float32):
    return jnp.dot(x.astype(w.dtype), w, preferred_element_type=jnp.float32
                   ).astype(out_dtype)


def embed(params, tokens, scale=None):
    """``E[ids]`` in float32, times the model's multiplier where it has
    one, under the named scope ``embedding``."""
    with jax.named_scope("embedding"):
        h = jnp.take(params["embedding"], tokens, axis=0).astype(
            jnp.float32)
        return h if scale is None else h * scale


def valid_lanes(lengths, T: int):
    """``(B, T)``: the lanes of a chunk that hold a row's valid tokens;
    those after ``lengths`` are padding."""
    return jnp.arange(T)[None, :] < lengths[:, None]


def split_heads(qkv, Hq: int, Hkv: int, d: int):
    """A fused ``[q | k | v]`` projection ``(b, T, (Hq + 2 Hkv) d)`` as
    heads: ``q (b, T, Hq, d)``, ``k`` and ``v (b, T, Hkv, d)``."""
    b, T, _ = qkv.shape
    q = qkv[..., :Hq * d].reshape(b, T, Hq, d)
    k = qkv[..., Hq * d:(Hq + Hkv) * d].reshape(b, T, Hkv, d)
    v = qkv[..., (Hq + Hkv) * d:].reshape(b, T, Hkv, d)
    return q, k, v


def rope_qk(q, k, pos, inv_freq, width=None):
    """Rotary on the leading ``width`` dims of each head of ``q`` and
    ``k`` (all ``head_dim`` of them by default; ``rotate_half`` pairs
    within the slice, ``inv_freq`` its ``width // 2`` frequencies; the
    dims after it pass as they are, in float32) at the chunk's positions
    ``pos + t``, under the named scope ``rope``."""
    with jax.named_scope("rope"):
        positions = pos + jnp.arange(q.shape[1])

        def turned(x):
            if width is None:
                return mla.apply_rope(x, positions, inv_freq,
                                      interleaved=False)
            return jnp.concatenate(
                [mla.apply_rope(x[..., :width], positions, inv_freq,
                                interleaved=False),
                 x[..., width:].astype(jnp.float32)], axis=-1)

        return turned(q), turned(k)


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


def held_experts(held, n_experts: int) -> Tuple[int, int]:
    """A configuration's ``experts_held`` as ``(first, count)`` (``None``:
    all ``n_experts`` the router has), within the router's or refused."""
    first, count = (int(v) for v in held or (0, n_experts))
    if not (0 <= first and 0 < count and first + count <= n_experts):
        raise ValueError(
            f"experts_held {(first, count)} lies outside the router's "
            f"{n_experts} experts")
    return first, count


def config_from_dict(cls, model: Mapping, count_key=None, **extra):
    """The dataclass ``cls`` from a published ``config.json``'s keys;
    keys that are not its fields do not shape the encoder and are passed
    over. A configuration of a share carries ``experts_held``: its
    ``count_key`` then counts the experts HELD (``share_of``). ``extra``
    (the serving fields) last."""
    names = {f.name for f in dataclasses.fields(cls)}
    kw = {k: v for k, v in model.items() if k in names}
    held = share_of(model, count_key) if count_key else {}
    return cls(**{**kw, **held, **extra})


def latent_block(p, h, cache, pos, dtype, *, heads: int, nope: int,
                 rope: int, v_dim: int, rank: int, eps: float, inv_freq,
                 rope_factor: float, scale: float, q_low_rank: bool = True,
                 head_gate: bool = False, q_scale: float = None,
                 kv_scale: float = None, attend=None):
    """``MLA(RMSNorm(h))`` of `models/deepseek_v3.py`'s docstring over
    one chunk, through the latent ``cache`` at ``pos``: ``(out (b, T, E)
    float32, cache)``. One copy for every model with latent attention;
    what such models differ in are placements: a query made in two
    steps through a normed low-rank ``c_q`` (leaves ``q_a``, ``q_norm``,
    ``q_b``) or by one matrix ``q`` (``q_low_rank=False``);
    ``head_gate``: each head's output times ``sigmoid(u w_h)`` (leaf
    ``gate`` ``(E, heads)``) before ``o``; and two fixed multipliers,
    ``q_scale`` on the normed ``c_q`` and ``kv_scale`` on the normed
    ``c_kv`` (the cache then holds the scaled ``c_kv``; ``k_pe`` is
    never scaled), none where they are ``None``. Named scopes ``q_proj``,
    ``kv_latent``, ``rope``, ``mla_core``, ``gate`` (where gated),
    ``o_proj``. A model that SELECTS what a query attends hands in
    ``attend(u, c_q, q_nope, q_pe, latent, cache) -> (out (b, T, heads,
    v_dim), cache)`` in place of the core: it reads the normed ``u`` and
    the normed ``c_q`` the queries are made of, and ``cache`` is then
    whatever that model carries a layer (the scopes inside are its
    own)."""
    b, T, _ = h.shape
    u = rms_norm(h, p["norm"], eps).astype(dtype)
    with jax.named_scope("q_proj"):
        if q_low_rank:
            c_q = rms_norm(matmul(u, p["q_a"]), p["q_norm"], eps)
            if q_scale is not None:
                c_q = c_q * q_scale
            q = matmul(c_q, p["q_b"], dtype)
        else:
            q = matmul(u, p["q"], dtype)
        q = q.reshape(b, T, heads, nope + rope)
    with jax.named_scope("kv_latent"):
        kv = matmul(u, p["kv_a"])
        c_kv = rms_norm(kv[..., :rank], p["kv_norm"], eps)
        if kv_scale is not None:
            c_kv = c_kv * kv_scale
    with jax.named_scope("rope"):
        positions = pos + jnp.arange(T)
        q_pe = mla.apply_rope(q[..., nope:], positions, inv_freq,
                              rope_factor)
        k_pe = mla.apply_rope(kv[..., rank:], positions, inv_freq,
                              rope_factor)
    latent = jnp.concatenate([c_kv, k_pe], axis=-1)
    if attend is not None:
        out, cache = attend(u, c_q, q[..., :nope], q_pe, latent, cache)
    else:
        with jax.named_scope("mla_core"):
            out, cache = mla.mla_cached(
                q[..., :nope], q_pe, latent, cache, pos, p["kv_b"], scale,
                v_dim, mxu_dtype=dtype)
    if head_gate:
        with jax.named_scope("gate"):
            out = out * jax.nn.sigmoid(matmul(u, p["gate"]))[..., None]
    with jax.named_scope("o_proj"):
        out = matmul(out.reshape(b, T, heads * v_dim), p["o"])
    return out, cache


class GrowingCache:
    """The part of the encoder contract that depends on a document's
    length, for an encoder whose one such state is a cache that grows
    with the document (``self.config.kv_positions`` positions at most)
    and that has no ring."""

    cache_kind: str  # what the cache holds, as its error names it

    def cache_positions(self, positions=None) -> int:
        """Positions the cache is allocated at for documents of up to
        ``positions`` tokens: their own length for short ones (one
        chunk), the configured maximum for everything longer, so that
        every multi-chunk group runs one compiled shape."""
        cfg = self.config
        if positions is None:
            return cfg.kv_positions
        if positions > cfg.kv_positions:
            raise ValueError(
                f"a document of {positions} positions does not fit the "
                f"{self.cache_kind} cache of kv_positions={cfg.kv_positions}")
        return positions if positions <= cfg.kv_positions // 4 \
            else cfg.kv_positions

    def window_positions(self, positions=None) -> int:
        return 0  # no layer attends under a window: no ring


class Counts:
    """The layout of the int32 vector an expert encoder carries under
    ``states["counts"]``, written once: an encoder names its own slots
    and reads and writes them by name. ``ops/moe.py::COUNTERS`` and the
    encoder's ``sums`` add up over layers and a group's programs since
    ``init_states`` (``sums`` reach the span as a mean a layer a
    program, ``totals`` as they are); ``sets``, after them, each program
    sets to what its trace knows (the layers an op's ``core_is_kernel``
    put on a Pallas kernel: all programs of a group run one chunk length
    against one cache size, so one answer a group)."""

    def __init__(self, sums: Sequence[str] = (), sets: Sequence[str] = (),
                 totals: Sequence[str] = ()):
        self.sums, self.totals, self.sets = (
            tuple(sums), tuple(totals), tuple(sets))
        self.names = moe.COUNTERS + self.sums + self.totals + self.sets

    def zeros(self):
        return jnp.zeros((len(self.names),), jnp.int32)

    def update(self, counts, rows, busiest, ran, **by_name):
        """``counts`` after one program: ``rows``, ``busiest`` and
        ``ran`` added to ``ops/moe.py::COUNTERS``' slots, the encoder's
        own slots added to or set, each ``by_name``."""
        first_set = len(self.names) - len(self.sets)
        counts = counts.at[:first_set].add(jnp.stack(
            [rows, busiest, ran,
             *(by_name[name] for name in self.sums + self.totals)]))
        values = [by_name[name] for name in self.sets]
        # one slot is a scalar update and several are one of a slice, as
        # the programs of the ledger's cells were lowered
        if len(values) == 1:
            return counts.at[first_set].set(values[0])
        return counts.at[first_set:].set(jnp.array(values, jnp.int32))

    def total(self, counted, name: str) -> int:
        """Slot ``name`` of the fetched vectors ``counted``, summed."""
        at = self.names.index(name)
        return sum(int(c[at]) for c in counted)

    def attrs(self, counted, n_moe_layers: int, held: int) -> dict:
        """Span attributes from the fetched vectors of a flush's groups:
        ``ops/moe.py::counter_attrs``, each of ``sums`` a layer a program
        as ``<name>_mean``, each of ``totals`` summed under its name,
        each of ``sets`` under its name, averaged over the groups."""
        attrs = moe.counter_attrs(counted, n_moe_layers, held)
        if attrs:
            layer_programs = attrs["moe_programs"] * n_moe_layers
            attrs.update({f"{name}_mean": self.total(counted, name)
                          / layer_programs for name in self.sums})
            attrs.update({name: self.total(counted, name)
                          for name in self.totals})
        if counted:
            attrs.update({name: self.total(counted, name) / len(counted)
                          for name in self.sets})
        return attrs


class CarriedCounts:
    """``state_counters`` and ``counter_attrs`` of the contract, for an
    encoder that carries ``counts = Counts(...)`` in its state and whose
    configuration says ``n_moe_layers`` and ``experts_held``."""

    counts: Counts

    def state_counters(self, states):
        return states["counts"]

    def counter_attrs(self, counted) -> dict:
        cfg = self.config
        return self.counts.attrs(counted, cfg.n_moe_layers,
                                 cfg.experts_held[1])
