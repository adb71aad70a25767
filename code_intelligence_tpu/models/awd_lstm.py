"""AWD-LSTM language model in Flax.

TPU-native rebuild of the model the reference constructs through fastai's
``language_model_learner(AWD_LSTM, config=awd_lstm_lm_config)``
(`Issue_Embeddings/train.py:68-73,88-92`): embedding with embedding-dropout →
N × LSTM with weight-drop (DropConnect) and variational ("locked") dropout →
tied-weight decoder. Default hyperparameters are the reference's
(emb_sz=800, n_hid=2500, n_layers=4; dropouts output_p=0.1, hidden_p=0.15,
input_p=0.25, embed_p=0.02, weight_p=0.2, tie_weights — `train.py:42-46,68-73`).

The full AWD regularization set is implemented with jit-safe RNG plumbing
(SURVEY.md §7 "hard parts"): every dropout mask is sampled once per call
(= per BPTT window) from the ``'dropout'`` RNG collection and held fixed
across the ``lax.scan`` timesteps, which is the variational-dropout /
per-window DropConnect semantics.

Hidden state is functional: callers pass states in and get new states out
(truncated-BPTT carry lives in the train state, sharded under pjit).
"""

from __future__ import annotations

import dataclasses
from typing import Any, ClassVar, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from code_intelligence_tpu.ops.lstm import LSTMState, lstm_layer
from code_intelligence_tpu.ops.pallas_lstm import (
    fits_resident,
    fits_resident_int8,
    lstm_layer_fused,
    lstm_layer_fused_ragged,
    lstm_layer_fused_ragged_int8,
)
from code_intelligence_tpu.ops.qrnn import qrnn_layer
from code_intelligence_tpu.ops.quantize import SCALE_SUFFIX


@dataclasses.dataclass(frozen=True)
class AWDLSTMConfig:
    """Hyperparameters, mirroring the reference's config-dict mutation of
    fastai's ``awd_lstm_lm_config`` (`train.py:42-46,68-73`)."""

    architecture: ClassVar[str] = "awd_lstm"  # models/contract.py

    vocab_size: int
    emb_sz: int = 800
    n_hid: int = 2500
    n_layers: int = 4
    pad_id: int = 1
    # Dropouts (reference values, train.py:68-70).
    output_p: float = 0.1
    hidden_p: float = 0.15
    input_p: float = 0.25
    embed_p: float = 0.02
    weight_p: float = 0.2
    tie_weights: bool = True
    out_bias: bool = True
    qrnn: bool = False  # QRNN fast path (train.py:53-54,73)
    qrnn_use_pallas: bool = False  # Pallas forget-mult kernel (ops/pallas_qrnn.py)
    # Pallas weights-resident fused LSTM cell for layers whose W_hh fits
    # VMEM — on v5e that includes the flagship H=2500 in bf16
    # (ops.pallas_lstm.fits_resident); layers past the residency boundary
    # keep the XLA scan. A TRAIN step does not read what a caller puts
    # here: it sets the field itself from backend, dtype, fits_resident
    # and its mesh (training/loop.py::train_cell_config; on the chip the
    # cell's step fell from 91.87 to 74.9 ms with it, PR 31). The serve
    # side (InferenceEngine) still reads it, from the export or its own
    # keyword; no cell has shown the kernel to win there (ROADMAP D4).
    lstm_use_pallas: bool = False
    # QRNN only: shard the recurrence's TIME axis over this mesh axis
    # (true sequence/context parallelism — parallel/seq_parallel.py). The
    # module must also be given a mesh (AWDLSTMLM(cfg, mesh=...)); without
    # one the layer falls back to the sequential scan, so an exported
    # config with seq_axis set still loads for single-device inference.
    seq_axis: Optional[str] = None
    dtype: Any = jnp.float32  # compute dtype (bfloat16 for TPU training)
    # Serve-path weight precision: "f32" (checkpoint dtype) or "int8"
    # (post-training symmetric per-channel quantization, applied at LOAD
    # by the inference engine — ops/quantize.py; the encoder then expects
    # int8 weight leaves + f32 `<name>_scale` siblings and fuses the
    # dequant into its matmuls). Inference-only: training requires f32.
    precision: str = "f32"

    def layer_size(self, layer: int) -> int:
        """Hidden size per layer: n_hid except the last, which must equal
        emb_sz so the decoder can tie with the embedding (fastai semantics)."""
        return self.emb_sz if layer == self.n_layers - 1 else self.n_hid


def init_lstm_states(config: AWDLSTMConfig, batch_size: int) -> Tuple[LSTMState, ...]:
    """Zero carried state per layer.

    LSTM: ``(h, c)``. QRNN: ``(h, x_last)`` — the second slot carries the
    layer's last raw input so the window=2 convolution stays exact across
    BPTT windows.
    """
    states = []
    for li in range(config.n_layers):
        h = jnp.zeros((batch_size, config.layer_size(li)), config.dtype)
        if config.qrnn:
            in_dim = config.emb_sz if li == 0 else config.n_hid
            states.append((h, jnp.zeros((batch_size, in_dim), config.dtype)))
        else:
            states.append((h, jnp.zeros_like(h)))
    return tuple(states)


def _locked_dropout_mask(rng, p: float, shape, dtype) -> jnp.ndarray:
    """Variational dropout: one (B, 1, D) mask reused across timesteps."""
    keep = jax.random.bernoulli(rng, 1.0 - p, shape)
    return keep.astype(dtype) / (1.0 - p)


def _centered_uniform(scale: float):
    """U(-scale, scale) — fastai's ``initrange`` / torch LSTM init are
    zero-centered (``nn.initializers.uniform`` is U[0, scale), not this)."""

    def init(key, shape, dtype=jnp.float32):
        return jax.random.uniform(key, shape, dtype, -scale, scale)

    return init


class AWDLSTMEncoder(nn.Module):
    """Embedding + stacked weight-dropped recurrent layers.

    ``__call__`` returns ``(raw_output, dropped_output, new_states)`` where
    ``raw_output`` is the last layer's undropped activations (for fastai's
    TAR regularizer) and ``dropped_output`` has output_p locked dropout
    applied (for the decoder and the AR regularizer).
    """

    config: AWDLSTMConfig
    # mesh for seq_axis time-sharding (see AWDLSTMConfig.seq_axis); kept
    # out of the config so exported configs stay JSON-serializable
    mesh: Optional[Any] = None

    # -- the encoder contract (models/contract.py): what the inference
    # engine asks of a model. Not wrapped by Flax: they read the config
    # alone and call ``apply`` from outside, on the unbound module.

    @property
    def out_dim(self) -> int:
        return self.config.emb_sz

    @nn.nowrap
    def init_states(self, batch: int, positions=None):
        """Fixed-size state: ``positions`` (documents' length) is not read."""
        return init_lstm_states(self.config, batch)

    @nn.nowrap
    def cache_positions(self, positions=None) -> int:
        return 0  # no part of the state grows with the document

    @nn.nowrap
    def window_positions(self, positions=None) -> int:
        return 0  # nor is any of it a ring

    @nn.nowrap
    def encode(self, params, tokens, states, lengths=None):
        """``lengths`` are not read: a recurrence runs every lane."""
        raw, _, new_states = self.apply(
            {"params": params}, tokens, states, deterministic=True)
        return raw, new_states

    @nn.nowrap
    def state_counters(self, states):
        return None  # the state holds no counts

    @nn.nowrap
    def counter_attrs(self, counted) -> dict:
        return {}

    @nn.nowrap
    def state_bytes_per_row(self, max_len=None) -> int:
        """``init_lstm_states``' two leaves a layer, by arithmetic (the
        engine asks once a group, on its hot path)."""
        cfg = self.config
        units = 0
        for li in range(cfg.n_layers):
            in_dim = cfg.emb_sz if li == 0 else cfg.n_hid
            units += cfg.layer_size(li) + (
                in_dim if cfg.qrnn else cfg.layer_size(li))
        return units * jnp.dtype(cfg.dtype).itemsize

    @nn.compact
    def __call__(
        self,
        tokens: jnp.ndarray,  # (B, T) int32
        states: Tuple[LSTMState, ...],
        deterministic: bool = True,
        valid_lens: Optional[jnp.ndarray] = None,
    ):
        """``valid_lens`` (``(B,) int32``, serve-path inference only): each
        row's live token prefix. The Pallas kernel branches route to
        their length-aware ragged variants (a tile of exhausted rows does
        no matmul/recurrence work — `ops/pallas_lstm.py` /
        `ops/pallas_qrnn.py`); the XLA scan branches ignore it — their
        dense math is already exact on the valid prefix (causality) and
        the pooled consumer masks the tail, which is the ragged slot
        step's parity contract (`inference/slots.py`)."""
        cfg = self.config
        B, T = tokens.shape
        if cfg.precision not in ("f32", "int8"):
            raise ValueError(f"unknown precision {cfg.precision!r}")
        int8 = cfg.precision == "int8"
        if int8 and not deterministic:
            raise ValueError(
                "precision='int8' is a serve-path (deterministic) mode — "
                "training runs f32 and quantizes at load")

        embedding = self.param(
            "embedding",
            _centered_uniform(0.1),  # fastai initrange=0.1
            (cfg.vocab_size, cfg.emb_sz),
            jnp.float32,
        )

        emb_table = embedding
        if not deterministic and cfg.embed_p > 0.0:
            # Embedding dropout: drop whole *rows* of the table so every
            # occurrence of a dropped word is zeroed identically.
            rng = self.make_rng("dropout")
            keep = jax.random.bernoulli(rng, 1.0 - cfg.embed_p, (cfg.vocab_size, 1))
            emb_table = embedding * keep / (1.0 - cfg.embed_p)

        # jax.named_scope on each part: the module is ONE compact body, so
        # without them a profiler capture names its device ops by number
        with jax.named_scope("embedding"):
            x = jnp.take(emb_table, tokens, axis=0).astype(cfg.dtype)  # (B, T, E)
            if int8:
                # dequant AFTER the gather: only the (B, T, E) activation is
                # dequantized — the full f32 table never materializes
                emb_scale = self.param(
                    "embedding_scale", nn.initializers.ones,
                    (cfg.emb_sz,), jnp.float32)
                x = x * emb_scale.astype(cfg.dtype)

        if not deterministic and cfg.input_p > 0.0:
            mask = _locked_dropout_mask(
                self.make_rng("dropout"), cfg.input_p, (B, 1, cfg.emb_sz), cfg.dtype
            )
            x = x * mask

        new_states = []
        raw_output = x
        for li in range(cfg.n_layers):
            with jax.named_scope(f"{'qrnn' if cfg.qrnn else 'lstm'}_{li}"):
                in_dim = cfg.emb_sz if li == 0 else cfg.n_hid
                H = cfg.layer_size(li)
                # torch LSTM init: U(-1/sqrt(H), 1/sqrt(H)) on all weights.
                winit = _centered_uniform(1.0 / float(np.sqrt(H)))

                if cfg.qrnn:
                    window = 2 if li == 0 else 1
                    w = self.param(f"qrnn_{li}_w", winit, (3 * H, window * in_dim))
                    b = self.param(f"qrnn_{li}_b", nn.initializers.zeros, (3 * H,))
                    w_c = w.astype(cfg.dtype)
                    if int8:
                        # The QRNN's int8 fusion point IS this gate projection:
                        # the ragged forget-mult kernel is weight-free
                        # (ops/pallas_qrnn.py only runs h = f*h + (1-f)*z), so
                        # dequant feeds the einsum and XLA fuses convert+scale
                        # into the matmul (ops/quantize.py module docs).
                        w_scale = self.param(
                            f"qrnn_{li}_w{SCALE_SUFFIX}", nn.initializers.ones,
                            (3 * H,), jnp.float32)
                        w_c = w_c * w_scale.astype(cfg.dtype)[:, None]
                    if not deterministic and cfg.weight_p > 0.0:
                        # AWD weight-drop on the QRNN gate weights (fastai wraps
                        # the QRNN linear in WeightDropout too).
                        keep = jax.random.bernoulli(
                            self.make_rng("dropout"), 1.0 - cfg.weight_p, w.shape
                        )
                        w_c = w_c * keep.astype(cfg.dtype) / (1.0 - cfg.weight_p)
                    h0, x_prev = states[li]
                    if cfg.seq_axis is not None and self.mesh is not None:
                        # time-sharded recurrence (context parallelism): each
                        # device scans its time block; block summaries compose
                        # over ICI (parallel/seq_parallel.py)
                        from code_intelligence_tpu.parallel.seq_parallel import (
                            qrnn_layer_seq_parallel,
                        )

                        batch_axis = (
                            "data" if "data" in self.mesh.axis_names else None
                        )
                        out, h_t = qrnn_layer_seq_parallel(
                            raw_output,
                            {"w": w_c, "b": b.astype(cfg.dtype)},
                            h0=h0,
                            mesh=self.mesh,
                            axis=cfg.seq_axis,
                            window=window,
                            x_prev=x_prev if window == 2 else None,
                            batch_axis=batch_axis,
                        )
                    else:
                        out, h_t = qrnn_layer(
                            raw_output,
                            {"w": w_c, "b": b.astype(cfg.dtype)},
                            h0=h0,
                            window=window,
                            x_prev=x_prev if window == 2 else None,
                            use_pallas=cfg.qrnn_use_pallas,
                            valid_lens=valid_lens,
                        )
                    st: LSTMState = (h_t, raw_output[:, -1])
                else:
                    w_ih = self.param(f"lstm_{li}_w_ih", winit, (4 * H, in_dim))
                    w_hh = self.param(f"lstm_{li}_w_hh", winit, (4 * H, H))
                    bias = self.param(f"lstm_{li}_bias", winit, (4 * H,))
                    if int8:
                        w_ih_scale = self.param(
                            f"lstm_{li}_w_ih{SCALE_SUFFIX}", nn.initializers.ones,
                            (4 * H,), jnp.float32)
                        w_hh_scale = self.param(
                            f"lstm_{li}_w_hh{SCALE_SUFFIX}", nn.initializers.ones,
                            (4 * H,), jnp.float32)
                        if (cfg.lstm_use_pallas and valid_lens is not None
                                and fits_resident_int8(H)):
                            # int8-resident fused serve kernel: W_hh stays int8
                            # in VMEM and dequantizes in-register, one gate
                            # slice at a time — fits resident where f32 didn't.
                            out, st = lstm_layer_fused_ragged_int8(
                                raw_output,
                                states[li],
                                w_ih,
                                w_ih_scale,
                                w_hh,
                                w_hh_scale,
                                bias.astype(cfg.dtype),
                                valid_lens,
                            )
                            new_states.append(st)
                            raw_output = out
                            continue
                        # XLA reference: dequant feeds the scan's matmuls and
                        # fuses (used by dense bucket/slot paths and off-TPU —
                        # there is no int8 dense-fused Pallas variant).
                        w_ih_d = w_ih.astype(cfg.dtype) * w_ih_scale.astype(
                            cfg.dtype)[:, None]
                        w_hh_d = w_hh.astype(cfg.dtype) * w_hh_scale.astype(
                            cfg.dtype)[:, None]
                        out, st = lstm_layer(
                            raw_output, states[li], w_ih_d, w_hh_d,
                            bias.astype(cfg.dtype), None,
                        )
                        new_states.append(st)
                        raw_output = out
                        continue
                    w_hh_mask = None
                    if not deterministic and cfg.weight_p > 0.0:
                        # DropConnect on recurrent weights, one mask per window.
                        keep = jax.random.bernoulli(
                            self.make_rng("dropout"), 1.0 - cfg.weight_p, w_hh.shape
                        )
                        w_hh_mask = keep.astype(cfg.dtype) / (1.0 - cfg.weight_p)
                    w_hh_c = w_hh.astype(cfg.dtype)
                    if cfg.lstm_use_pallas and fits_resident(
                        H, jnp.dtype(cfg.dtype).itemsize
                    ):
                        if w_hh_mask is not None:
                            w_hh_c = w_hh_c * w_hh_mask
                        if valid_lens is not None:
                            # length-aware serve kernel: exhausted tiles skip
                            # their matmuls (inference only — no VJP)
                            out, st = lstm_layer_fused_ragged(
                                raw_output,
                                states[li],
                                w_ih.astype(cfg.dtype),
                                w_hh_c,
                                bias.astype(cfg.dtype),
                                valid_lens,
                            )
                        else:
                            out, st = lstm_layer_fused(
                                raw_output,
                                states[li],
                                w_ih.astype(cfg.dtype),
                                w_hh_c,
                                bias.astype(cfg.dtype),
                            )
                    else:
                        out, st = lstm_layer(
                            raw_output,
                            states[li],
                            w_ih.astype(cfg.dtype),
                            w_hh_c,
                            bias.astype(cfg.dtype),
                            w_hh_mask,
                        )
            new_states.append(st)
            raw_output = out
            if li < cfg.n_layers - 1 and not deterministic and cfg.hidden_p > 0.0:
                mask = _locked_dropout_mask(
                    self.make_rng("dropout"), cfg.hidden_p, (B, 1, H), cfg.dtype
                )
                raw_output = raw_output * mask

        dropped = raw_output
        if not deterministic and cfg.output_p > 0.0:
            mask = _locked_dropout_mask(
                self.make_rng("dropout"), cfg.output_p, (B, 1, cfg.emb_sz), cfg.dtype
            )
            dropped = raw_output * mask

        return raw_output, dropped, tuple(new_states)


class AWDLSTMLM(nn.Module):
    """Encoder + (tied) decoder producing next-token logits.

    ``__call__`` returns ``(logits, raw_output, dropped_output,
    new_states)`` — the raw and dropped activations feed fastai's AR/TAR
    activation regularizers (``language_model_learner`` defaults alpha=2,
    beta=1). Its logits are for every caller but the trainer
    (`training/convert_fastai.py`'s parity check, a notebook): since
    PR 50 `training/loop.py` asks ``features`` for the encoder's outputs
    and the decoder's leaves and takes the product inside
    `ops/lm_loss.py::decoder_cross_entropy`, with the cross-entropy, so
    that a train step holds no float32 array of the logits' shape.
    """

    config: AWDLSTMConfig
    mesh: Optional[Any] = None  # for config.seq_axis (see AWDLSTMEncoder)

    def setup(self):
        self.encoder = AWDLSTMEncoder(self.config, mesh=self.mesh, name="encoder")
        if not self.config.tie_weights:
            self.decoder_w = self.param(
                "decoder_w",
                _centered_uniform(0.1),
                (self.config.vocab_size, self.config.emb_sz),
                jnp.float32,
            )
        if self.config.out_bias:
            self.decoder_b = self.param(
                "decoder_b", nn.initializers.zeros, (self.config.vocab_size,)
            )

    def features(
        self,
        tokens: jnp.ndarray,
        states: Tuple[LSTMState, ...],
        deterministic: bool = True,
    ):
        """``(raw_output, dropped_output, new_states, dec_w, dec_b)``:
        everything of ``__call__`` but the decoder's product. ``dec_w (V,
        E)`` and ``dec_b (V,)`` (``None`` without ``out_bias``) are the
        decoder's leaves at the compute dtype, the tied embedding where
        ``tie_weights``."""
        cfg = self.config
        raw, dropped, new_states = self.encoder(tokens, states, deterministic)
        if cfg.tie_weights:
            dec_w = self.encoder.variables["params"]["embedding"]
        else:
            dec_w = self.decoder_w
        with jax.named_scope("decoder"):
            dec_w = dec_w.astype(cfg.dtype)
            dec_b = self.decoder_b.astype(cfg.dtype) if cfg.out_bias else None
        return raw, dropped, new_states, dec_w, dec_b

    def __call__(
        self,
        tokens: jnp.ndarray,
        states: Tuple[LSTMState, ...],
        deterministic: bool = True,
    ):
        raw, dropped, new_states, dec_w, dec_b = self.features(
            tokens, states, deterministic)
        with jax.named_scope("decoder"):
            logits = jnp.einsum("bte,ve->btv", dropped, dec_w)
            if dec_b is not None:
                logits = logits + dec_b
        return logits, raw, dropped, new_states
