"""Pooled-embedding inference engine.

TPU-native rebuild of ``InferenceWrapper`` (`py/code_intelligence/
inference.py:25-263`, duplicated at `Issue_Embeddings/flask_app/
inference.py`): tokenize → encoder forward → concat[mean, max, last] of the
final layer's hidden states → ``3*emb_sz`` = 2400-d embedding
(`inference.py:89-93`).

TPU-first redesign (SURVEY.md §7 stage 4):

* **Fixed length buckets** replace the reference's pad-to-batch-max +
  OOM-halving retry (`inference.py:201-223`): every compiled shape is a
  (bucket_len, batch) pair from a fixed grid, so XLA compiles a handful of
  programs once and never recompiles or OOMs at serve time.
* **Windowed scan with carried state** replaces unbounded-length forwards:
  docs longer than the largest bucket are processed in fixed-size chunks
  whose hidden state carries across chunks (`encoder.reset()` between
  documents, `inference.py:60,70` — state never leaks across docs).
  Pooling (mean/max/last) accumulates across chunks and is exactly equal
  to full-sequence pooling.
* **Rows that have finished leave a group**: a group's first chunk
  program runs at ``batch_size``; before each later one the batch
  narrows to the smallest of ``batch_size`` halved up to three times
  that holds the documents still going (the groups are length-sorted,
  so those are the batch's last rows), carrying that suffix of the state
  and of the pool on the device. A group costs the lane-steps of the
  rows each of its chunks ran, not ``batch_size`` times its longest
  document; a batch size compiles at most four programs a bucket.
* Padding is masked out of all three pools (the reference pools over raw
  padded activations only in its batch path — here padded and unpadded
  paths agree by construction).

The 2400→1600 truncation contract for downstream classifier heads
(`py/code_intelligence/embeddings.py:116`,
`repo_specific_model.py:182`) is exposed as ``EMBED_TRUNCATE_DIM``.
"""

from __future__ import annotations

import bisect
import dataclasses
import functools
import inspect
import logging
import time
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from code_intelligence_tpu.models import AWDLSTMConfig, build_encoder
from code_intelligence_tpu.text import Tokenizer, Vocab, build_issue_text
from code_intelligence_tpu.text import rules as text_rules
from code_intelligence_tpu.text.rules import TK_UNK
from code_intelligence_tpu.utils import (
    flight_recorder, profiling, resilience, tracing)

from code_intelligence_tpu.constants import EMBED_TRUNCATE_DIM  # noqa: F401 (re-export)


def _raw_size(issue) -> int:
    """``len(title) + len(body)`` of a raw issue: the free proxy for its
    token count that decides WHEN ``embed_issues`` prepares it."""
    return sum(len(v) for v in (issue.get("title"), issue.get("body"))
               if isinstance(v, str))


def _first_traced(ctxs):
    """The first sampled SpanContext of ``ctxs`` (None when there is
    none): where a span that belongs to several documents at once — a
    group, a flush — is recorded, once."""
    return next((c for c in ctxs if c is not None and c.sampled), None)


class InferenceEngine:
    """Batched pooled-embedding inference over a frozen encoder."""

    def __init__(
        self,
        params,
        config: AWDLSTMConfig,
        vocab: Vocab,
        buckets: Sequence[int] = (32, 64, 128, 256, 512),
        batch_size: int = 32,
        chunk_len: Optional[int] = None,
        lstm_pallas: Optional[bool] = None,
        scheduler: str = "groups",
        version: str = "unversioned",
        mesh=None,
        precision: str = "f32",
    ):
        if isinstance(mesh, str):
            # mesh-sharded serve step (RUNBOOK §26): a --mesh spec string
            # ("data,model" / "data=4,model=2") resolved against the
            # visible devices; None = single-chip
            from code_intelligence_tpu.parallel.serve_shard import (
                build_serve_mesh)

            mesh = build_serve_mesh(mesh)
        if precision not in ("f32", "int8"):
            raise ValueError(
                f"precision must be 'f32' or 'int8', got {precision!r}")
        if isinstance(config, AWDLSTMConfig):
            config = self._awd_serve_config(config, lstm_pallas, mesh,
                                            precision)
        elif lstm_pallas or precision != "f32":
            # the kernel override and quantize-at-load are the AWD
            # encoder's; any other encoder computes in the type of the
            # weights it is handed
            raise ValueError(
                f"lstm_pallas / precision={precision!r} apply to the "
                f"AWD-LSTM encoder only, not to {type(config).__name__}")
        self.precision = precision
        self.mesh = mesh
        self.config = config
        self.vocab = vocab
        # the process's compile ledger (utils/flight_recorder.py): every
        # program this engine compiles is a named record on the wall
        # clock there, and a traced chunk program says what it paid
        self._compiles = flight_recorder.get_accountant()
        self._compiles.listen()
        # Accept encoder-only params ({"embedding": ..., "lstm_0_w_ih": ...})
        # or a full-LM params tree ({"encoder": {...}, "decoder_b": ...}).
        if "embedding" in params:
            enc = params
        elif "encoder" in params:
            enc = params["encoder"]
        elif "params" in params:
            p = params["params"]
            enc = p["encoder"] if "encoder" in p else p
        else:
            raise ValueError("unrecognized params tree for InferenceEngine")
        from code_intelligence_tpu.ops.quantize import (
            SCALE_SUFFIX, quantize_encoder_params, tree_bytes)

        # weight footprint BEFORE any quantization — the denominator of
        # the >=3x gate (inference/int8_check.py) and the
        # encoder_weight_bytes gauge's f32 baseline
        self.weight_bytes_f32 = tree_bytes(enc)
        if precision == "int8" and "embedding" + SCALE_SUFFIX not in enc:
            enc = quantize_encoder_params(dict(enc), config)
        self.weight_bytes = tree_bytes(enc)
        self._enc_params = {"params": enc}
        # either encoder, through the one contract (models/contract.py):
        # init_states / encode / out_dim / state_bytes_per_row
        self.encoder = build_encoder(config, enc)
        self.buckets = tuple(sorted(buckets))
        self.batch_size = batch_size
        # Window size for docs longer than the largest bucket; snapped to a
        # bucket so it reuses a compiled shape.
        self.chunk_len = self._bucket_for_static(
            chunk_len or self.buckets[-1], self.buckets
        )
        # "auto" everywhere (engine, universal model, corpus builds): one
        # tokenization behavior at train and serve time by construction.
        self.tokenizer = Tokenizer(backend="auto")
        self.embed_dim = 3 * self.encoder.out_dim
        self._fwd_cache: Dict[Tuple[int, int], object] = {}
        # default batching policy: "groups" = the reference-shaped
        # length-sorted lock-step path below; "slots" = continuous
        # in-flight batching (inference/slots.py); "ragged" = the same
        # slot loop with paged state and a length-aware page-sized step
        # (RaggedSlotScheduler — mixed-length batches cost ~sum-of-
        # tokens instead of rows×chunk_len). The serve path (MicroBatcher
        # / serving.server) defaults to slots; the group path stays as
        # the parity reference.
        self.scheduler = self._check_scheduler(scheduler)
        self._slot_scheduler = None
        self._ragged_scheduler = None
        # model-version label: stamped on responses (X-Model-Version),
        # per-version /metrics, and trace spans by the rollout manager
        self.version = version
        # vocab identity for the serving cache key (embed_cache.py):
        # computed ONCE at engine load — two exports with identical
        # version strings but different vocabs must never alias cache
        # entries, since the same token ids mean different documents
        self.vocab_hash = vocab.content_hash()

    @staticmethod
    def _awd_serve_config(config: AWDLSTMConfig, lstm_pallas, mesh,
                          precision: str) -> AWDLSTMConfig:
        """The AWD-LSTM encoder's own serve-time knobs."""
        # Serve-time kernel override: the weights-resident Pallas cell is
        # numerically the same layer (parity-tested), so an encoder
        # trained on the scan can still SERVE on the fused cell.
        if lstm_pallas is not None:
            config = dataclasses.replace(config, lstm_use_pallas=lstm_pallas)
        # Off the TPU the kernel has no compiled lowering (interpret mode
        # is for tests, orders of magnitude slower than the scan): a CPU
        # host serves the parity-identical scan — loudly, whether the flag
        # came from the caller or from an exported config (e.g. a distilled
        # student trained with lstm_use_pallas=True). On the TPU a
        # requested kernel is never swapped: what cannot run raises below.
        if config.lstm_use_pallas and jax.default_backend() != "tpu":
            logging.getLogger(__name__).warning(
                "lstm_use_pallas requested but backend is %s, not tpu — "
                "serving on the XLA scan instead", jax.default_backend())
            config = dataclasses.replace(config, lstm_use_pallas=False)
        if mesh is not None and config.lstm_use_pallas:
            # a Mosaic call inside a GSPMD-partitioned program is opaque
            # to the partitioner and the serve step has no shard_map
            # around it (ROADMAP S9/D6). Only reachable on the TPU — off
            # it the flag was already dropped above.
            raise ValueError(
                "lstm_pallas (and with it the int8-fused kernel) does not "
                "compose with --mesh: the sharded serve step has no "
                "shard_map around the Pallas call. Serve the mesh with "
                "--no-lstm_pallas, or one chip with the kernel.")
        # Serve-path weight precision (RUNBOOK §28): "int8" quantizes the
        # encoder weights AT LOAD (ops/quantize.py) — int8 leaves + f32
        # per-channel scales replace the f32 matmul weights, and the
        # dequant is fused into the encoder's matmuls (in-register in the
        # ragged Pallas tiles, XLA-fused on the reference path). Leaf
        # dtypes change but leaf SHAPES don't, so every scheduler keeps
        # exactly ONE compiled step shape. The engine owns this knob:
        # exports stay f32 (no new export format).
        return dataclasses.replace(config, precision=precision)

    def state_geometry(self) -> dict:
        """What one row in flight on the ``groups`` path holds on the
        device, from the encoder contract: the ledger's geometry note
        (``utils/memtrack.py::capacity_report`` turns it into
        ``rows_fit``, the largest ``batch_size`` the headroom takes)."""
        return {
            "out_dim": self.encoder.out_dim,
            "kv_positions": self.encoder.cache_positions(),
            "kv_positions_window": self.encoder.window_positions(),
            "state_bytes_per_row": self.encoder.state_bytes_per_row(),
        }

    def warmup(self, scheduler: Optional[str] = None) -> None:
        """Compile the serve path's step program(s) off the hot path —
        a promotion candidate pays its XLA compiles HERE (or during
        shadow replay), never on a live client's request."""
        self.embed_issues([{"title": "warmup", "body": "warmup body"}],
                          scheduler=scheduler)

    @classmethod
    def from_export(cls, model_dir, **kw) -> "InferenceEngine":
        """Load from an ``export_encoder`` directory (the serving artifact,
        analogous to the reference's 965MB pkl download at boot,
        `flask_app/app.py:24-33`)."""
        from code_intelligence_tpu.training.checkpoint import load_encoder

        params, config, vocab_path = load_encoder(model_dir)
        if vocab_path is None:
            raise FileNotFoundError(f"no vocab.json in {model_dir}")
        return cls(params, config, Vocab.load(vocab_path), **kw)

    # ------------------------------------------------------------------
    # Compiled forwards (one per (batch, bucket) shape, cached per instance
    # — a class-level lru_cache would pin self, leaking encoder params)
    # ------------------------------------------------------------------

    def _fwd(self, batch: int, length: int):
        cached = self._fwd_cache.get((batch, length))
        if cached is not None:
            return cached

        takes_lengths = self._encode_takes_lengths

        def fwd(params, tokens, lengths, h_states, pool_state):
            states = jax.tree.unflatten(self._state_treedef, h_states)
            # each row's valid tokens, to every encoder that names them
            # (models/contract.py)
            extra = {"lengths": lengths} if takes_lengths else {}
            raw, new_states = self.encoder.encode(
                params["params"], tokens, states, **extra)
            pool_state = self._accumulate_pool(raw, lengths, pool_state)
            return pool_state, jax.tree.leaves(new_states)

        # a device trace names a module after the function it was traced
        # from: ``jit_fwd_b16_l512``, so a capture's device time splits by
        # program shape (and joins the ``engine.program`` spans)
        fwd.__name__ = fwd.__qualname__ = f"fwd_b{batch}_l{length}"
        # the carried state is donated: each chunk program writes its new
        # state over the one it was handed. Programs are enqueued ahead of
        # the device, and without this every enqueued chunk of a group
        # holds a whole state of its own (the hybrid's is 93 MB a row)
        jitted = jax.jit(fwd, donate_argnums=(3,))
        self._fwd_cache[(batch, length)] = jitted
        return jitted

    @functools.cached_property
    def _encode_takes_lengths(self) -> bool:
        return "lengths" in inspect.signature(
            self.encoder.encode).parameters

    @functools.cached_property
    def _state_treedef(self):
        return jax.tree.structure(
            jax.eval_shape(lambda: self.encoder.init_states(1)))

    @functools.cached_property
    def _state_batch_axes(self) -> Tuple[Optional[int], ...]:
        """Each state leaf's batch axis, from the contract alone: the one
        axis on which the states of one row and of two differ (None for
        a leaf every row shares, a position counter)."""
        one, two = (jax.tree.leaves(jax.eval_shape(
            lambda rows=rows: self.encoder.init_states(rows)))
            for rows in (1, 2))
        axes = []
        for a, b in zip(one, two):
            differ = [ax for ax, (p, q) in enumerate(zip(a.shape, b.shape))
                      if p != q]
            if len(differ) > 1 or len(a.shape) != len(b.shape):
                raise ValueError(
                    f"init_states gives a leaf {a.shape} for one row and "
                    f"{b.shape} for two: no single batch axis")
            axes.append(differ[0] if differ else None)
        return tuple(axes)

    @functools.cached_property
    def _narrow(self):
        """``narrow(h_states, pool_state, keep)``: the last ``keep`` rows
        of a group's carried state and pool, and the pool of the rows
        before them, which are finished. One small device program a
        (batch, ``keep``) pair; nothing comes to the host."""
        axes = self._state_batch_axes

        def narrow(h_states, pool_state, keep):
            def kept(x, axis=0):
                n = x.shape[axis]
                return jax.lax.slice_in_dim(x, n - keep, n, axis=axis)

            return (tuple(p[: p.shape[0] - keep] for p in pool_state),
                    tuple(kept(p) for p in pool_state),
                    [x if ax is None else kept(x, ax)
                     for x, ax in zip(h_states, axes)])

        return jax.jit(narrow, static_argnums=(2,))

    def _batch_for(self, rows: int) -> int:
        """The batch a chunk program of ``rows`` live rows runs at: the
        smallest of ``batch_size`` halved up to three times (200, 100,
        50, 25) that holds them, so a batch size compiles at most four
        programs a bucket."""
        B = self.batch_size
        return min(b for b in (-(-B // d) for d in (1, 2, 4, 8)) if b >= rows)

    def _init_pool_state(self, batch: int):
        E = self.encoder.out_dim
        return (
            jnp.zeros((batch, E), jnp.float32),
            jnp.full((batch, E), -jnp.inf, jnp.float32),
            jnp.zeros((batch, E), jnp.float32),
            jnp.zeros((batch,), jnp.float32),
        )

    @staticmethod
    def _accumulate_pool(raw, lengths, pool_state):
        """Masked [mean, max, last] accumulation of one chunk's hidden
        states into the carried pool — the ONE copy of the pooling math
        both batching paths compile (the group fwd above and the slot
        step in inference/slots.py); the slots-vs-groups parity contract
        rests on them sharing it."""
        with jax.named_scope("pool"):  # device ops read pool/... in a capture
            raw = raw.astype(jnp.float32)  # (B, T, E)
            T = raw.shape[1]
            mask = (jnp.arange(T)[None, :] < lengths[:, None]).astype(jnp.float32)
            m3 = mask[:, :, None]
            psum, pmax, plast, pcount = pool_state
            psum = psum + jnp.sum(raw * m3, axis=1)
            pmax = jnp.maximum(pmax, jnp.max(jnp.where(m3 > 0, raw, -jnp.inf), axis=1))
            # last valid position in THIS chunk (if any); else keep previous.
            has = lengths > 0
            idx = jnp.clip(lengths - 1, 0, T - 1)
            last_here = jnp.take_along_axis(raw, idx[:, None, None], axis=1)[:, 0]
            plast = jnp.where(has[:, None], last_here, plast)
            pcount = pcount + lengths.astype(jnp.float32)
            return (psum, pmax, plast, pcount)

    def _finalize(self, pool_state) -> np.ndarray:
        # the ONE intended host sync of the bulk path, made explicit so
        # graftcheck's transfer audit (jax.transfer_guard("disallow"))
        # passes over the serve loop; device_get passes numpy through,
        # so the slots path (already-host rows) shares this code
        psum, pmax, plast, pcount = jax.device_get(tuple(pool_state))
        count = np.maximum(pcount, 1.0)[:, None]
        mean = psum / count
        pmax = np.where(np.isfinite(pmax), pmax, 0.0)
        return np.concatenate([mean, pmax, plast], axis=-1)  # (B, 3E)

    # ------------------------------------------------------------------
    # Tokenization
    # ------------------------------------------------------------------

    def numericalize(self, text: str) -> np.ndarray:
        toks = self.tokenizer.tokenize(text)
        if not toks:
            toks = [TK_UNK]
        return self.vocab.numericalize(toks)

    # ------------------------------------------------------------------
    # Public API
    # ------------------------------------------------------------------

    # groups whose pooled device state is held before a host flush: keeps
    # the bulk path free of per-group round-trips (the device keeps
    # computing while earlier groups are still unfetched) without holding
    # more than ~64 * 4 * (B, E) f32 pool arrays in HBM
    _FLUSH_GROUPS = 64
    # carried state of enqueued groups the device has not finished: a
    # group's state is allocated when it is enqueued, so a host that runs
    # far ahead of the chip holds one state a group it is ahead by. Past
    # this many bytes the host waits for the oldest group before it
    # enqueues another. Never reached by the AWD encoders (13 MB a group
    # of 200); the hybrid's 1.5 GB a group of 16 keeps two in flight
    _STATE_BYTES_IN_FLIGHT = 3 << 30

    def _check_scheduler(self, scheduler: str) -> str:
        if scheduler not in ("groups", "slots", "ragged"):
            raise ValueError(
                f"scheduler must be 'groups', 'slots' or 'ragged', "
                f"got {scheduler!r}")
        if scheduler != "groups" and not isinstance(self.config,
                                                    AWDLSTMConfig):
            # the slot arenas hold the AWD encoder's per-layer (h, c)
            # leaves and its step reaches into them (inference/slots.py);
            # a second kind of state in one arena is ROADMAP M2
            raise ValueError(
                f"scheduler {scheduler!r} serves the AWD-LSTM encoder "
                f"only; {type(self.encoder).__name__} runs on 'groups'")
        return scheduler

    def slot_scheduler(self, registry=None, chunk_len: Optional[int] = None,
                       ragged: bool = False,
                       page_len: Optional[int] = None):
        """The engine's continuous-batching scheduler (created on first
        use so the group-only path never compiles the slot step).
        ``ragged=True`` returns the paged length-aware scheduler instead
        — each mode caches its own instance with its own single compiled
        step shape (``page_len`` parameterizes only the ragged one)."""
        from code_intelligence_tpu.inference.slots import (
            RaggedSlotScheduler, SlotScheduler)

        self._check_scheduler("ragged" if ragged else "slots")
        if ragged:
            if chunk_len is not None:
                # the ragged step's geometry knob is page_len; silently
                # deriving it from chunk_len would hand back a scheduler
                # with a different step shape than the caller asked for
                raise ValueError(
                    "chunk_len does not apply to the ragged scheduler; "
                    "pass page_len instead")
            if self._ragged_scheduler is None:
                self._ragged_scheduler = RaggedSlotScheduler(
                    self, page_len=page_len, registry=registry,
                    mesh=self.mesh)
            else:
                if (page_len is not None
                        and page_len != self._ragged_scheduler.page_len):
                    # one compiled step shape per scheduler lifetime — a
                    # conflicting request must not be silently dropped
                    raise ValueError(
                        f"ragged scheduler already exists with page_len="
                        f"{self._ragged_scheduler.page_len}; cannot honor "
                        f"page_len={page_len}")
                if registry is not None:
                    self._ragged_scheduler.bind_registry(registry)
            return self._ragged_scheduler
        if self._slot_scheduler is None:
            self._slot_scheduler = SlotScheduler(
                self, chunk_len=chunk_len, registry=registry,
                mesh=self.mesh)
        else:
            if (chunk_len is not None
                    and self._bucket_for_static(chunk_len, self.buckets)
                    != self._slot_scheduler.chunk_len):
                # the step shape is compiled once for the scheduler's
                # lifetime; a conflicting request must not be dropped
                raise ValueError(
                    f"slot scheduler already exists with chunk_len="
                    f"{self._slot_scheduler.chunk_len}; cannot honor "
                    f"chunk_len={chunk_len}")
            if registry is not None:
                self._slot_scheduler.bind_registry(registry)
        return self._slot_scheduler

    def embed_ids_batch(  # graft: hot
        self, id_seqs: Sequence[np.ndarray], scheduler: Optional[str] = None,
        ctxs: Optional[Sequence] = None,
    ) -> np.ndarray:
        """Embed already-numericalized docs; returns (N, 3*emb_sz) float32.

        Returning implies a full device sync: every group's result has
        been materialized to host numpy (the benchmark's window clock relies
        on this).

        ``ctxs`` — optional per-doc tracing SpanContexts: the slots path
        attributes queue-wait/device/emit per document; the group path
        records the spans :meth:`_embed_groups` lists. It feeds that
        grouper the documents in ascending true length, so its groups
        are the length-sorted slabs of ``batch_size``."""
        policy = self._check_scheduler(scheduler or self.scheduler)
        if policy == "groups":
            # Length-sorted grouping (reference sorts by length too,
            # inference.py:191-212) into fixed buckets.
            order = np.argsort([len(s) for s in id_seqs], kind="stable")
            return self._embed_groups(
                order.tolist(), lambda i, overlapped: id_seqs[i], ctxs)
        self._check_deadline()
        return self.slot_scheduler(ragged=policy == "ragged").embed_ids(
            id_seqs, ctxs=ctxs)

    @staticmethod
    def _check_deadline() -> None:
        """Resilience backstop: a caller whose ambient deadline is already
        spent gets DeadlineExceeded HERE, before any device program is
        enqueued — budget-dead work must never occupy the chip. (Scoped
        deadlines are per-thread, so a batcher/scheduler thread serving
        a mixed batch is unaffected.)"""
        dl = resilience.current_deadline()
        if dl is not None:
            dl.check("engine.embed_ids_batch")

    def _embed_groups(self, order, prepare, ctxs) -> np.ndarray:  # graft: hot
        """The groups path's one grouper: rows of the ``len(order)``
        documents of a call, written back by document index.

        ``order`` is the feed: document indices in the order they are to
        be prepared; ``prepare(i, overlapped)`` returns document ``i``'s
        token ids (``overlapped``: a group of this call is already
        enqueued, so the chip works while this document is prepared).
        Prepared documents wait in a buffer; whenever it holds
        ``batch_size + batch_size // 4`` of them the ``batch_size``
        SHORTEST BY TRUE TOKEN LENGTH are enqueued as one group, and the
        host goes on preparing while the device runs it (dispatch is
        asynchronous; this is all on the calling thread). When the feed
        ends, what is left goes out length-sorted in slabs of
        ``batch_size``. A feed in ascending true length therefore gives
        exactly the length-sorted slabs; a feed that only roughly
        ascends (``embed_issues``' free proxy) gives the same slabs as
        long as no document arrives more than the quarter-batch
        look-ahead late, and costs padding, never a wrong row, when one
        does. A call of at most ``batch_size + batch_size // 4``
        documents never fills the buffer: it is prepared whole, sorted
        exactly and sent as it always was. What a group then costs the
        device is :meth:`_embed_group_device`'s: its first chunk program
        at ``batch_size`` rows, every later one at the rows still alive
        (rounded up to the halving grid), so a call's last group, the
        only one of many chunks, no longer runs its longest document's
        chunks at the whole batch.

        Spans, when ``ctxs`` carries per-doc SpanContexts: ONE
        ``engine.group`` per group (host assembly + enqueue, no device
        sync; the group's padding counts, ``row_chunks_dropped`` = how
        far its chunk programs narrowed, and ``late_docs`` = its
        documents shorter than the longest document of a group this call
        had already enqueued: 0 when the feed was ordered well, the
        field signal that a corpus defeats the proxy) on the group's
        first traced doc; ONE ``engine.finalize`` per flush (the host
        blocked on the chip) on the call's first traced doc; and per
        traced doc one ``engine.group_embed`` from the end of the
        preparation slab the document was prepared in to the call's last
        flush (the lock-step group pays its whole call's device time —
        the latency behavior the slot scheduler exists to fix). On a
        multi-group call a document's ``engine.text_rules`` /
        ``engine.tokenize`` spans may therefore lie inside OTHER
        documents' ``engine.group_embed`` interval, never inside its
        own: the stages overlap by design. In a profiler capture
        ``engine.host_prep`` is one TraceAnnotation per preparation slab
        (the feed between two enqueues), beside ``engine.group`` and
        ``engine.finalize``."""
        self._check_deadline()
        if self.mesh is not None \
                and not getattr(self, "_warned_mesh_groups", False):
            # the groups path's (batch, bucket) forwards never shard —
            # a mesh engine serving through it silently runs single-chip
            # (the server/bench CLIs refuse the combination outright)
            self._warned_mesh_groups = True
            logging.getLogger(__name__).warning(
                "engine has a serve mesh but the 'groups' path runs "
                "UNSHARDED compiled forwards — use scheduler='slots' or "
                "'ragged' for the sharded step (RUNBOOK §26)")
        n = len(order)
        out = np.zeros((n, self.embed_dim), np.float32)
        if n == 0:
            return out
        B = self.batch_size
        lookahead = B + B // 4
        call_ctx = _first_traced(ctxs or ())
        embed_from = [0.0] * n if ctxs is not None else None
        buf: List[Tuple[int, int, np.ndarray]] = []  # (true length, doc, ids)
        pending = []
        in_flight: List[Tuple[object, int]] = []  # (a pool leaf, state bytes)
        longest_sent = -1  # no group enqueued yet

        # traced calls: what the encoder counted on the device for each
        # group (models/contract.py::state_counters), fetched after the
        # group's pooled rows and outside the span's clock reads
        counted = [] if call_ctx is not None else None

        def flush():
            if not pending:
                return
            tf0 = time.perf_counter()
            with profiling.annotate("engine.finalize"):
                for idx, pools in pending:
                    # the group's documents are the last rows of its batch
                    out[idx] = np.concatenate(
                        [self._finalize(p) for p in pools])[-len(idx):]
            tf1 = time.perf_counter()
            attrs = {}
            if counted:
                attrs = self.encoder.counter_attrs(jax.device_get(counted))
                counted.clear()
            tracing.record_span("engine.finalize", tf0, tf1, call_ctx,
                                groups=len(pending), **attrs)
            pending.clear()

        feed, feeding = iter(order), True
        while feeding:
            held = len(buf)
            with profiling.annotate("engine.host_prep"):
                for i in feed:
                    ids = prepare(i, longest_sent >= 0)
                    buf.append((len(ids), i, ids))
                    if len(buf) >= lookahead:
                        break
                else:
                    feeding = False
            if embed_from is not None:
                now = time.perf_counter()
                for _, i, _ in buf[held:]:
                    embed_from[i] = now
            buf.sort()  # by true length; ties by document index, never ids
            take = B if feeding else len(buf)
            for start in range(0, take, B):
                group = buf[start : start + B]
                idx = [i for _, i, _ in group]
                # enqueue the group's device programs; defer the host
                # fetch so the device pipelines groups instead of idling
                # on a host round-trip every batch_size docs
                group_ctx = _first_traced(ctxs[i] for i in idx) \
                    if ctxs is not None else None
                tg0 = time.perf_counter()
                with profiling.annotate("engine.group"):
                    pools, counts = self._embed_group_device(
                        [ids for _, _, ids in group], counted, group_ctx)
                tg1 = time.perf_counter()
                if group_ctx is not None:
                    tracing.record_span(
                        "engine.group", tg0, tg1, group_ctx, **counts,
                        late_docs=sum(
                            length < longest_sent for length, _, _ in group))
                longest_sent = max(longest_sent, group[-1][0])
                pending.append((idx, pools))
                # a leaf the group's LAST program writes
                in_flight.append((pools[-1][3], counts["state_bytes"]))
                while len(in_flight) > 1 and sum(
                        b for _, b in in_flight) > self._STATE_BYTES_IN_FLIGHT:
                    # not a pipeline flush: the newest groups stay queued
                    in_flight.pop(0)[0].block_until_ready()  # graft: noqa[blocking-dispatch] — memory backpressure on the oldest group only; never reached below 3 GB of carried state in flight
                if len(pending) >= self._FLUSH_GROUPS:
                    flush()
            del buf[:take]
        flush()
        if ctxs is not None:
            t1 = time.perf_counter()
            for ctx, t0 in zip(ctxs, embed_from):
                tracing.record_span("engine.group_embed", t0, t1, ctx)
        return out

    @staticmethod
    def _bucket_for_static(length: int, buckets) -> int:
        for b in buckets:
            if length <= b:
                return b
        return buckets[-1]

    def _bucket_for(self, length: int) -> int:
        return self._bucket_for_static(length, self.buckets)

    def _embed_group_device(self, seqs: List[np.ndarray],  # graft: hot
                            counted: Optional[list] = None,
                            trace_ctx=None):
        """Enqueue one group's forward passes; returns the DEVICE pool
        state (no host sync — ``_finalize`` materializes it) and the
        group's counts.

        ``seqs`` come in ascending length (both feeders sort), and sit
        in the LAST rows of the batch, padding rows first, so the rows
        still alive at any chunk are a suffix of it. The first chunk
        program runs at ``batch_size``. Before each later chunk the rows
        whose documents have ended leave: the chunk runs at the smallest
        batch of the halving grid (``_batch_for``) that holds the live
        rows, on the suffix of the carried state and pool that
        ``_narrow`` cuts on the device. The pool rows that left are
        finished; the pool comes back as its pieces in row order (one
        piece, as it always was, unless the group narrowed), the last
        of them an output of the group's last program. A single-chunk
        group, and a group whose rows all live to the end, run the
        programs they always ran.

        Counts, the ``engine.group`` span's attributes, counted here
        where the padding is made: what the group holds (``rows``,
        ``valid_tokens``), what it was enqueued as (``batch`` x
        ``bucket`` x ``chunks`` = ``lane_steps``), what the device is
        asked to run for it (``lane_steps_run`` = the rows each chunk
        program ran x ``bucket``, summed; ``row_chunks_dropped`` =
        ``batch`` less the rows run, summed over the chunks: 0 when
        nothing narrowed; ``cache_steps_run`` = the rows each chunk
        program ran x the positions they had reached by its end, summed:
        what an encoder that attends to a growing cache is asked to
        meet; ``window_steps_run`` = the same sum with the positions
        reached capped at what a ring holds: what its layers under a
        sliding window are asked to meet, 0 where it has none), and
        from the encoder ``state_bytes`` (the
        state carried out of the first chunk program, all ``batch``
        rows), ``kv_positions`` and ``kv_positions_window`` (positions a
        row is allocated in the cache that grows with the document and
        in the ring; 0 for an encoder without that kind of state).
        ``counted``, where a list is given,
        gains what the encoder counted in the group's carried state
        (still on the device).

        ``trace_ctx``, where a SpanContext is given (traced calls only:
        without one no clock is read here), gains ONE ``engine.program``
        span a chunk program, from just before the chunk's blocks go to
        the device to the return of the jitted call (the enqueue; no
        device sync), so that the group's span has children and block
        filling, ``init_states`` and ``_narrow`` are its self time. Its
        attributes: ``rows`` (the batch this program runs at), ``batch``
        (the group's first-chunk batch), ``bucket``, ``valid_tokens``
        (the chunk's own) and ``lane_steps`` (``rows`` x ``bucket``); with
        (``rows``, ``bucket``) a capture's module ``jit_fwd_b<rows>_l<bucket>``
        is laid against them; ``compile_s`` where the jitted call traced,
        lowered or compiled anything (the compile ledger grew on this
        thread across it: a shape's first call, and never a warmed one). Over a group's programs ``lane_steps`` and
        ``valid_tokens`` sum to the group's ``lane_steps_run`` and
        ``valid_tokens``."""
        B = self.batch_size  # the first chunk's shape; pad the remainder
        lens = [len(s) for s in seqs]
        if any(a > b for a, b in zip(lens, lens[1:])):
            raise ValueError("a group's documents come in ascending length")
        max_len = lens[-1]
        # Short groups run in one pass at the smallest fitting bucket; long
        # docs stream through chunk_len-sized windows with carried state.
        bucket = self._bucket_for(max_len) if max_len <= self.buckets[-1] else self.chunk_len
        n_chunks = max(1, -(-max_len // bucket))
        # the carried state, sized for the group's longest document (only
        # a state that grows with the document reads the size)
        positions = bucket * n_chunks
        h_leaves = jax.tree.leaves(self.encoder.init_states(B, positions))
        ring = self.encoder.window_positions(positions)
        pool = self._init_pool_state(B)
        pad_id = self.vocab.pad_id

        batch, rows_run, cache_steps, window_steps, pools = B, 0, 0, 0, []
        for ci in range(n_chunks):
            if ci:
                alive = len(seqs) - bisect.bisect_right(lens, ci * bucket)
                keep = self._batch_for(alive)
                if keep < batch:
                    left, pool, h_leaves = self._narrow(
                        tuple(h_leaves), pool, keep)
                    pools.append(left)
                    batch = keep
            live = seqs[-batch:]
            tokens = np.full((batch, bucket), pad_id, np.int32)
            lengths = np.zeros((batch,), np.int32)
            for r, s in enumerate(live, batch - len(live)):
                chunk = s[ci * bucket : (ci + 1) * bucket]
                tokens[r, : len(chunk)] = chunk
                lengths[r] = len(chunk)
            if trace_ctx is not None:
                compiled = self._compiles.stages_mark()
                tp0 = time.perf_counter()
            pool, h_leaves = self._fwd(batch, bucket)(
                self._enc_params, jnp.asarray(tokens), jnp.asarray(lengths), tuple(h_leaves), pool
            )
            if trace_ctx is not None:
                tracing.record_span(
                    "engine.program", tp0, time.perf_counter(), trace_ctx,
                    rows=batch, batch=B, bucket=bucket,
                    valid_tokens=int(lengths.sum()),
                    lane_steps=batch * bucket,
                    **self._compiles.compile_attrs(compiled))
            rows_run += batch
            cache_steps += batch * bucket * (ci + 1)
            window_steps += batch * min(bucket * (ci + 1), ring)
        pools.append(pool)
        if counted is not None:
            counts = self.encoder.state_counters(
                jax.tree.unflatten(self._state_treedef, h_leaves))
            if counts is not None:
                counted.append(counts)
        return pools, {
            "rows": len(seqs), "batch": B, "bucket": bucket,
            "chunks": n_chunks,
            "valid_tokens": sum(lens),
            "lane_steps": B * bucket * n_chunks,
            "lane_steps_run": rows_run * bucket,
            "row_chunks_dropped": B * n_chunks - rows_run,
            "cache_steps_run": cache_steps,
            "window_steps_run": window_steps,
            "state_bytes": B * self.encoder.state_bytes_per_row(positions),
            "kv_positions": self.encoder.cache_positions(positions),
            "kv_positions_window": ring,
        }

    def embed_text(self, text: str) -> np.ndarray:
        """(3*emb_sz,) embedding of one pre-processed document string —
        ``get_pooled_features`` (`inference.py:74-93`)."""
        return self.embed_ids_batch([self.numericalize(text)])[0]

    def embed_issue(self, title: str, body: str) -> np.ndarray:
        """``process_dict`` + pooled features (`inference.py:95-126`)."""
        return self.embed_text(build_issue_text(title, body))

    def embed_issues(
        self,
        issues: Sequence[Dict[str, str]],
        truncate: Optional[int] = None,
        scheduler: Optional[str] = None,
        ctxs: Optional[Sequence] = None,
    ) -> np.ndarray:
        """Bulk path — ``df_to_embedding`` (`inference.py:138-229`).

        ``truncate=EMBED_TRUNCATE_DIM`` reproduces the downstream 1600-d
        contract (`embeddings.py:116`).

        ``ctxs`` — optional per-issue tracing SpanContexts (the server
        handler and the micro-batcher pass them); when omitted but an
        ambient trace is open on this thread, every doc attaches to it.
        """
        if ctxs is None:
            amb = tracing.current_context()
            if amb is not None:
                ctxs = [amb] * len(issues)
        elif len(ctxs) != len(issues):
            # one context a document, by index
            raise ValueError(
                f"ctxs has {len(ctxs)} entries for {len(issues)} issues")

        def text_of(i):
            d = issues[i]
            return build_issue_text(d.get("title", ""), d.get("body", ""))

        if ctxs is None:
            # no clock read and no record per document
            def prepare(i, overlapped):
                return self.numericalize(text_of(i))
        else:
            # rule_passes / rule_passes_run: the regex scans the pre-rule
            # chain could make for this document and the scans it made,
            # counted outside the clock reads; ``engine.tokenize`` holds
            # the chain's second application (``Tokenizer.tokenize``,
            # not counted), the word split and ``numericalize``
            def prepare(i, overlapped):
                with text_rules.counting_passes() as passes:
                    tt0 = time.perf_counter()
                    text = text_of(i)
                    tt1 = time.perf_counter()
                tracing.record_span("engine.text_rules", tt0, tt1, ctxs[i],
                                    n_chars=len(text), rule_passes=passes[0],
                                    rule_passes_run=passes[1])
                tt0 = time.perf_counter()
                ids = self.numericalize(text)
                tt1 = time.perf_counter()
                tracing.record_span(
                    "engine.tokenize", tt0, tt1, ctxs[i],
                    n_tokens=len(ids),
                    n_tokens_overlapped=len(ids) if overlapped else 0)
                return ids

        if self._check_scheduler(scheduler or self.scheduler) == "groups":
            # shortest first by the free proxy, each document prepared as
            # it is fed: the first group is on the chip once a batch and
            # a quarter of short documents exist, and the long documents
            # are tokenised while the short groups run
            order = np.argsort([_raw_size(d) for d in issues], kind="stable")
            emb = self._embed_groups(order.tolist(), prepare, ctxs)
        else:
            # the slot schedulers take a list: all of the call's host
            # work first, then the hand-over
            with profiling.annotate("engine.host_prep"):
                ids = [prepare(i, False) for i in range(len(issues))]
            emb = self.embed_ids_batch(ids, scheduler=scheduler, ctxs=ctxs)
        return emb[:, :truncate] if truncate else emb
