"""Continuous slot-based batching for the embedding serve path.

The group-synchronous bulk path (`engine.embed_ids_batch`) batches the way
the reference's V100 path did: length-sorted groups run lock-step, so one
long stack-trace dump stalls every short bug report batched with it, and
each chunk re-pads fresh host arrays. This module replaces the group
barrier with the slot/ragged scheduling shape of continuous in-flight
batching ("Ragged Paged Attention" / "LightSeq" serving loops, PAPERS.md):

* One persistent ``(batch_size, chunk_len)`` step program for the whole
  serve lifetime. Rows are independent **slots**, each holding one
  in-flight document's carried LSTM state and pool accumulators.
* When a slot's document finishes, its pooled row is emitted (one lazy
  device gather per finish batch — no per-step host sync) and the slot is
  refilled from the pending queue on the very next step. No group
  barrier, no per-group shape changes, exactly one compiled step shape.
* ``donate_argnums`` on the step's state/pool buffers: the steady-state
  loop allocates nothing on device (donation is a no-op on CPU, where the
  same code path is the parity/smoke target).
* The hot loop moves ONE host→device block per step: tokens, per-slot
  chunk lengths, and the refill-reset bits ride a single packed
  ``(B, chunk_len + 2)`` int32 staging block. Every step stages into a
  FRESH block that the host never writes again once it is handed over:
  dispatch is asynchronous, the host runs many steps ahead of the
  device, and a handed-over block is read later: the CPU backend
  aliases a 64-byte-aligned numpy buffer zero-copy, and on the TPU v5e
  the host-to-device copy is still in flight when ``jnp.asarray`` /
  ``device_put`` returns (PR 21 chip probe: a block rewritten right
  after the call arrived rewritten in 100 of 100 tries). The pool
  accumulators ride a single packed ``(B, 3*emb_sz + 1)`` float32 array
  for the same reason (one gather emits a finished row).

Invariant (pinned by tests/test_slot_scheduler.py): slot reuse never
leaks state across documents — every refill carries a reset bit that
zeroes the slot's LSTM state and re-initializes its pool accumulators
inside the compiled step, before the chunk runs.

Ragged paged mode (:class:`RaggedSlotScheduler`, ``--scheduler ragged``)
applies the Ragged Paged Attention idea (PAPERS.md) to the same loop:
the dense step makes every row pay ``chunk_len`` compute per step
regardless of its valid tokens — short bug reports subsidize long
stack-trace dumps and idle slots burn full lanes. The ragged scheduler

* steps ``page_len`` tokens at a time (``page_len << chunk_len``), so a
  document's cost is ``ceil(len/page_len)*page_len`` ≈ its own token
  count instead of ``ceil(len/chunk_len)*chunk_len``;
* pages the carried LSTM state and pool accumulators into fixed-size
  arenas (``n_pages = 2·batch``) indexed by a per-slot PAGE TABLE that
  rides the packed staging block (never a separate h2d transfer):
  finish RETIRES the document's page (it sits immutably in the arena —
  the step only scatters to active slots' pages) and hands the slot a
  fresh page from the free list, so emission is deferred to one batched
  gather when the free list runs dry or ``materialize()`` needs rows;
* carries per-row valid lengths into the compiled step, which forwards
  them to the encoder — on the Pallas kernel paths a tile of exhausted
  rows does no matmul/recurrence work (``fused_lstm_forward_ragged`` /
  the ragged forget-mult); the XLA scan path ignores them (dense math
  is exact on the valid prefix, pooling masks the tail) and stays the
  parity reference and automatic fallback.

Still exactly ONE compiled step shape per scheduler, audited under
``no_implicit_transfers()`` + ``recompile_guard(budget=0)``.

Mesh-sharded mode (``mesh=``, RUNBOOK §26): either scheduler can run
its ONE compiled step under a ``("data", "model")`` mesh
(`parallel/serve_shard.py`) — batch rows (staging block, state arenas,
packed/paged pool, page table) split over ``data``; the frozen encoder
params (embedding table, LSTM/QRNN gate matmuls) partition over
``model`` via the SAME regex rules training compiles with. Every
single-chip invariant carries over intact: the state/pool buffers stay
donated (``donate_argnums`` composes with ``in_shardings``), the paged
arenas and free list stay device-resident with per-shard-consistent
page geometry (``batch % data == 0`` enforced at construction), the
staging block remains the ONE host→device block per step (an explicit
sharded ``device_put``), and steady state stays
``recompile_guard(budget=0)`` clean under its own step name
(``slots.step[_ragged]_mesh``). ``mesh=None`` (the default) is
bit-for-bit today's single-chip path.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Deque, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from code_intelligence_tpu.models import init_lstm_states
from code_intelligence_tpu.utils import flight_recorder, tracing

# occupancy / steps-per-doc histogram edges: slot counts and chunk counts
# are small integers; the latency-shaped default buckets would collapse
# everything into the first bucket
_COUNT_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128)


class _Ticket:
    """One submitted document: its ids, and (once finished) a reference
    into its finish batch's gathered pool rows."""

    __slots__ = ("ids", "gathered", "row", "steps", "ctx",
                 "t_submit", "t_slot", "t_done")

    def __init__(self, ids: np.ndarray, ctx=None):
        self.ids = np.asarray(ids, np.int32).reshape(-1)
        self.gathered = None  # device (m, 3E+1) rows of the finish batch
        self.row = 0          # this doc's row within that gather
        self.steps = 0
        # per-document stage timing rides the ticket only when the caller
        # handed a trace context — the untraced path stays stamp-free
        self.ctx = ctx        # utils.tracing.SpanContext or None
        self.t_submit = time.perf_counter() if ctx is not None else 0.0
        self.t_slot = 0.0     # first occupied a device slot
        self.t_done = 0.0     # last chunk ran (emit)

    @property
    def done(self) -> bool:
        return self.gathered is not None


class SlotScheduler:
    """Persistent continuous-batching step loop over an engine's encoder.

    ``chunk_len`` defaults to the engine's bucket nearest 64 tokens: small
    enough that a short bug report doesn't ride a 512-wide program, large
    enough that long docs don't dissolve into per-step dispatch overhead.
    """

    # subclass hooks: the ragged scheduler swaps the step name (its own
    # recompile-guard scope), widens the staging block by one page-table
    # column, and allocates paged device state
    _STEP_NAME = "slots.step"
    _STAGING_EXTRA = 2  # [length, refill-reset] ride after the tokens

    def __init__(self, engine, chunk_len: Optional[int] = None,
                 registry=None, mesh=None):
        self.engine = engine
        self.batch_size = engine.batch_size
        self.chunk_len = self._snap_chunk(chunk_len)
        self.registry = None
        self._lock = threading.Lock()  # serializes submit/run callers
        # mesh-sharded mode (RUNBOOK §26): batch rows over 'data',
        # encoder params over 'model'. None = today's single-chip path,
        # bit-for-bit (no sharding annotations touch the step).
        self.mesh = mesh
        self._step_name = self._STEP_NAME
        self._params = None        # mesh-placed copy of the enc params
        self._param_shardings = None
        self._n_data_shards = 1
        if mesh is not None:
            from code_intelligence_tpu.parallel import serve_shard

            serve_shard.validate_serve_mesh(mesh, engine.batch_size)
            self._step_name = self._STEP_NAME + "_mesh"
            self._n_data_shards = int(dict(mesh.shape).get("data", 1))
            self._param_shardings = serve_shard.cached_param_shardings(
                engine._enc_params, mesh)
            # place the frozen params ONCE (vocab/gate dims over
            # 'model' per the shared partition rules) — never per step
            self._params = jax.device_put(engine._enc_params,
                                          self._param_shardings)
            self._staging_sharding = serve_shard.row_sharding(mesh, 2)
            # per-data-shard lane counters (host ints, like the global
            # ones): rows [k*B/d, (k+1)*B/d) live on shard k under the
            # contiguous dim-0 split of P("data", ...)
            self._shard_stepped = np.zeros(self._n_data_shards, np.int64)
            self._shard_valid = np.zeros(self._n_data_shards, np.int64)
        B, C = self.batch_size, self.chunk_len
        E = engine.config.emb_sz
        self._pool_width = 3 * E + 1  # [psum | pmax | plast | pcount]
        # host-side slot table: per-slot in-flight ticket and its offset
        self._slot_doc: List[Optional[_Ticket]] = [None] * B
        self._slot_off = np.zeros((B,), np.int64)
        self._queue: Deque[_Ticket] = deque()
        # packed staging block: [:, :C] tokens, [:, C] length, [:, C+1]
        # refill-reset bit (+ the page-table column in ragged mode) —
        # one host->device block per step, allocated per step (see
        # _advance: a block handed to the device is never written again)
        self._staging_shape = (B, C + self._STAGING_EXTRA)
        # persistent device state: carried LSTM leaves + packed pool
        self._init_device_state()
        self._step_cost = None
        self._step = self._build_step()
        self.steps_run = 0
        self.docs_done = 0
        # lane accounting (host-side ints, no device reads): stepped =
        # every lane-token a dispatched step paid for, valid = the
        # tokens that carried real document content — the wasted-lane
        # story the ragged mode exists to shrink
        self.tokens_stepped = 0
        self.tokens_valid = 0
        # host-device transfer accounting (host-side ints): h2d = the
        # one staged block each step dispatches, d2h = the pool rows
        # materialize() fetches — the scheduler's whole transfer story,
        # exported as h2d_d2h_bytes (RUNBOOK §32)
        self.h2d_bytes = 0
        self.d2h_bytes = 0
        if registry is not None:
            self.bind_registry(registry)

    def _snap_chunk(self, chunk_len: Optional[int]) -> int:
        return self.engine._bucket_for_static(
            chunk_len or 64, self.engine.buckets)

    def _init_device_state(self) -> None:
        self._h_leaves = tuple(
            jax.tree.leaves(init_lstm_states(self.engine.config,
                                             self.batch_size)))
        self._pool = self._init_pool()
        self._h_leaves, self._pool = self._place_state(
            self._h_leaves, self._pool)

    def _put_gather_indices(self, idx: np.ndarray):
        """Device placement for the finish/flush gather indices. Under a
        mesh they must land REPLICATED on the mesh explicitly — a plain
        ``jnp.asarray`` commits them to one device and the eager gather
        against the mesh-sharded pool then pays an implicit
        device-to-device reshard every finish batch (the exact class of
        transfer the runtime audit exists to catch)."""
        if self.mesh is None:
            return jnp.asarray(idx)
        from jax.sharding import NamedSharding, PartitionSpec

        return jax.device_put(idx, NamedSharding(self.mesh, PartitionSpec()))

    def _place_state(self, h_leaves, pool):
        """No-op without a mesh; under one, commit the carried state and
        pool to their batch-row shardings so the first donated dispatch
        already reuses sharded buffers (reset() re-places on heal)."""
        if self.mesh is None:
            return h_leaves, pool
        from code_intelligence_tpu.parallel import serve_shard

        h_leaves = tuple(
            jax.device_put(l, serve_shard.row_sharding(self.mesh, l.ndim))
            for l in h_leaves)
        pool = jax.device_put(
            pool, serve_shard.row_sharding(self.mesh, pool.ndim))
        return h_leaves, pool

    # -- metrics -----------------------------------------------------------

    def bind_registry(self, registry) -> None:
        """Attach a ``utils.metrics.Registry`` (idempotent)."""
        if registry is None or self.registry is registry:
            return
        registry.histogram(
            "slot_occupancy", "occupied slots per scheduler step",
            buckets=_COUNT_BUCKETS)
        registry.histogram(
            "slot_steps_per_doc", "chunk steps each document needed",
            buckets=_COUNT_BUCKETS)
        registry.gauge(
            "slot_refill_queue_depth", "documents waiting for a free slot")
        registry.gauge(
            "slots_wasted_lane_fraction",
            "masked tokens / stepped tokens over the scheduler lifetime "
            "(idle lanes + padded tails; the ragged scheduler's win)")
        # serve-path precision surface (RUNBOOK §28): which weight
        # precision this engine serves, and the resident encoder weight
        # footprint — the pair the int8 gate's >=3x drop shows up on
        registry.gauge(
            "serve_precision_int8",
            "1 when the engine serves the int8-quantized encoder "
            "(--precision int8), 0 for f32")
        registry.gauge(
            "encoder_weight_bytes",
            "resident encoder weight bytes as loaded (int8 values + f32 "
            "scales under --precision int8; the f32 checkpoint size "
            "otherwise)")
        registry.set("serve_precision_int8",
                     1 if getattr(self.engine, "precision", "f32") == "int8"
                     else 0)
        registry.set("encoder_weight_bytes",
                     int(getattr(self.engine, "weight_bytes", 0)))
        if self.mesh is not None:
            # mesh-sharded serve step (RUNBOOK §26): shape gauges are
            # static per scheduler; per-shard lanes update per step;
            # the per-device flops gauge lands when step_cost_analysis
            # is first pulled (it pays an AOT lowering — warmup/bench/
            # gate territory, never the bind path)
            registry.gauge("slots_mesh_devices",
                           "devices in the serve mesh the slot step is "
                           "sharded over (absent/0 = single-chip)")
            registry.gauge("slots_mesh_axis_size",
                           "serve mesh axis sizes by axis (data|model)")
            registry.gauge(
                "slots_step_flops_per_device",
                "AOT cost_analysis flops of the ONE sharded step, per "
                "device (the SPMD-partitioned program's flops)")
            registry.gauge(
                "slots_wasted_lane_fraction_shard",
                "per-data-shard wasted-lane fraction (masked / stepped "
                "tokens on that shard's rows) — a shard whose value "
                "runs hot is starved of work by arrival order")
            from code_intelligence_tpu.parallel import serve_shard

            registry.set("slots_mesh_devices",
                         serve_shard.mesh_size(self.mesh))
            for axis, size in dict(self.mesh.shape).items():
                registry.set("slots_mesh_axis_size", int(size),
                             labels={"axis": str(axis)})
        # dispatch-discipline surface (RUNBOOK §32): cumulative compiles
        # of THIS scheduler's step fn (any growth after warmup is a
        # recompile — CompileWatch fails tier-1 audits on it) and the
        # bytes the scheduler moves across the host-device boundary
        registry.gauge(
            "jit_recompiles_total",
            "cumulative XLA compiles recorded for the watched step fn "
            "(flight-recorder ledger; growth after warmup = recompile)")
        registry.gauge(
            "h2d_d2h_bytes",
            "bytes moved across the host-device boundary by the serve "
            "path, by direction (dir=h2d staged dispatch blocks, "
            "dir=d2h materialized pool rows)")
        self.registry = registry
        self._export_dispatch_gauges()
        # compile accounting (compile_seconds / compiled_hbm_bytes) for
        # the slot step lands on the same scrape surface
        flight_recorder.get_accountant().bind_registry(registry)

    def _export_dispatch_gauges(self) -> None:
        """Refresh jit_recompiles_total / h2d_d2h_bytes (cheap host
        reads; called at bind and at each materialize boundary)."""
        if self.registry is None:
            return
        self.registry.set(
            "jit_recompiles_total",
            flight_recorder.get_accountant().count(self._step_name))
        self.registry.set("h2d_d2h_bytes", self.h2d_bytes,
                          labels={"dir": "h2d"})
        self.registry.set("h2d_d2h_bytes", self.d2h_bytes,
                          labels={"dir": "d2h"})

    # -- device-memory ledger (utils/memtrack.py, RUNBOOK §31) -------------

    # owner-name hook: the ragged subclass's pool arena is the PAGED pool
    _POOL_OWNER = "pool"

    def register_memory_owners(self, ledger, prefix: str = "slots") -> None:
        """Register this scheduler's device buffers on a
        ``DeviceMemoryLedger``: the carried-state arenas, the packed
        (dense) / paged (ragged) pool, the mesh-sharded param copy when
        one exists, and the host-tier staging block. Providers read the
        live attributes, so ``reset()`` rebuilding the device state
        never strands the attribution on dead buffers."""
        ledger.register(f"{prefix}.state_arenas", lambda: self._h_leaves)
        ledger.register(f"{prefix}.{self._POOL_OWNER}", lambda: self._pool)
        if self.mesh is not None:
            # the engine's frozen params, re-placed over the mesh — a
            # second resident copy the single-chip path doesn't have
            ledger.register(f"{prefix}.params_sharded", lambda: self._params)
        ledger.register_host(
            f"{prefix}.staging",
            lambda: int(np.prod(self._staging_shape)) * 4)

    # -- compiled step -----------------------------------------------------

    @staticmethod
    def _pack_pool(pool_state) -> jnp.ndarray:
        """4-tuple pool (engine layout) -> packed (B, 3E+1)."""
        psum, pmax, plast, pcount = pool_state
        return jnp.concatenate([psum, pmax, plast, pcount[:, None]], axis=1)

    def _unpack_pool(self, pool: jnp.ndarray):
        E = self.engine.config.emb_sz
        return (pool[:, :E], pool[:, E:2 * E], pool[:, 2 * E:3 * E],
                pool[:, 3 * E])

    def _init_pool(self) -> jnp.ndarray:
        # packed form of the engine's pool-init identity — ONE source for
        # the zeros/-inf/zeros/count layout
        return self._pack_pool(self.engine._init_pool_state(self.batch_size))

    def _build_step(self):
        engine = self.engine
        treedef = engine._state_treedef
        C = self.chunk_len

        def step(params, staged, h_leaves, pool):
            tokens = staged[:, :C]
            lengths = staged[:, C]
            reset = staged[:, C + 1] > 0
            # refill reset: zero the slot's carried state and re-init its
            # pool row BEFORE the chunk runs — state never leaks across
            # documents on slot reuse
            r = reset[:, None]
            h_leaves = tuple(
                jnp.where(r, jnp.zeros_like(leaf), leaf) for leaf in h_leaves)
            pool = jnp.where(r, self._init_pool()[:1], pool)

            states = jax.tree.unflatten(treedef, h_leaves)
            raw, _, new_states = engine.encoder.apply(
                params, tokens, states, deterministic=True)
            # the SAME pooling math the group path compiles (parity
            # contract — see engine._accumulate_pool)
            pool = self._pack_pool(engine._accumulate_pool(
                raw, lengths, self._unpack_pool(pool)))
            return pool, tuple(jax.tree.leaves(new_states))

        return self._jit_step(step)

    def _jit_step(self, step):
        """jit the step body under this scheduler's placement mode.

        Donated state/pool either way: the steady-state loop re-uses the
        same device buffers instead of allocating per step (no-op on
        CPU; composes with ``in_shardings`` under a mesh — the sharded
        state never round-trips the host). The accountant wrapper
        records compile wall time / flops / HBM per compiled shape
        (must stay 1 in steady state) on /debug/flight and the
        compile_seconds gauges, keyed by this scheduler's step name
        (``..._mesh`` under a mesh — its own recompile-guard scope); it
        exposes _cache_size so compiled_step_shapes() works unchanged.
        """
        if self.mesh is None:
            self._step_raw = jax.jit(step, donate_argnums=(2, 3))
        else:
            from code_intelligence_tpu.parallel import serve_shard

            state_sh = tuple(
                serve_shard.row_sharding(self.mesh, l.ndim)
                for l in self._h_leaves)
            pool_sh = serve_shard.row_sharding(self.mesh, self._pool.ndim)
            self._step_raw = jax.jit(
                step, donate_argnums=(2, 3),
                in_shardings=(self._param_shardings,
                              self._staging_sharding, state_sh, pool_sh),
                out_shardings=(pool_sh, state_sh))
        return flight_recorder.instrument(self._step_raw, self._step_name)

    def compiled_step_shapes(self) -> int:
        """Number of compiled step programs (steady state must be 1).
        Returns -1 when the jit cache size isn't introspectable on the
        installed jax (private API) — callers treat that as unknown, not
        as a recompile."""
        cache_size = getattr(self._step, "_cache_size", None)
        return int(cache_size()) if cache_size is not None else -1

    def step_cost_analysis(self) -> dict:
        """AOT ``{'flops', 'bytes_accessed'}`` of the ONE compiled step
        program: lowers the persistent step shape explicitly and reads
        XLA's ``cost_analysis`` — device-free, so the ragged-vs-dense
        flops-per-token claim is provable on CPU (``runbook_ci
        --check_ragged``). Memoized: the lowering is a real compile and
        must never ride the serve hot path."""
        if self._step_cost is None:
            def sds(a):
                return jax.ShapeDtypeStruct(a.shape, a.dtype)

            args = (
                jax.tree.map(sds, self.engine._enc_params),
                jax.ShapeDtypeStruct(
                    (self.batch_size, self.chunk_len + self._STAGING_EXTRA),
                    jnp.int32),
                jax.tree.map(sds, self._h_leaves),
                sds(self._pool),
            )
            cost = self._step_raw.lower(*args).compile().cost_analysis()
            self._step_cost = {
                "flops": float(cost.get("flops", 0.0)),
                "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
            }
        if self.mesh is not None and self.registry is not None:
            # under a mesh the lowered module is the SPMD-partitioned
            # per-device program, so these flops ARE per-device — the
            # ×N capacity claim made observable (RUNBOOK §26). Set on
            # EVERY pull, outside the memoize branch: a registry bound
            # after the first pull must still receive the value.
            self.registry.set("slots_step_flops_per_device",
                              self._step_cost["flops"])
        return self._step_cost

    @property
    def n_data_shards(self) -> int:
        """Data-axis shard count (1 without a mesh) — the public index
        space of :meth:`shard_wasted_lane_fraction`."""
        return self._n_data_shards

    def shard_wasted_lane_fraction(self, shard: int) -> float:
        """Per-data-shard wasted-lane fraction (mesh mode only): the
        shard's own masked ÷ stepped tokens — arrival order can starve
        one shard's rows while the fleet average looks healthy."""
        if self.mesh is None:
            return 0.0
        stepped = int(self._shard_stepped[shard])
        if stepped <= 0:
            return 0.0
        return 1.0 - int(self._shard_valid[shard]) / stepped

    def wasted_lane_fraction(self) -> float:
        """Masked tokens / stepped tokens over the scheduler lifetime —
        the fraction of paid lane-compute that carried no document
        content (idle slots + padded tails)."""
        if self.tokens_stepped <= 0:
            return 0.0
        return 1.0 - self.tokens_valid / self.tokens_stepped

    # -- scheduling --------------------------------------------------------

    def submit(self, ids: np.ndarray, ctx=None) -> _Ticket:
        """Queue one numericalized document; returns its ticket. ``ctx``
        (a tracing SpanContext) attributes the doc's queue-wait/device
        stages to its originating request's trace."""
        t = _Ticket(ids, ctx=ctx)
        self._queue.append(t)
        return t

    def _refill(self, staged: np.ndarray) -> int:
        """Fill freed slots from the queue and stage every active slot's
        next chunk into the given (fresh, pad-filled) packed block.
        Returns occupancy."""
        B, C = self.batch_size, self.chunk_len
        staged[:, C:] = 0  # lengths + reset bits
        occupied = 0
        for s in range(B):
            if self._slot_doc[s] is None and self._queue:
                doc = self._slot_doc[s] = self._queue.popleft()
                self._slot_off[s] = 0
                staged[s, C + 1] = 1
                if doc.ctx is not None:  # queue-wait ends here
                    doc.t_slot = time.perf_counter()
            doc = self._slot_doc[s]
            if doc is None:
                continue  # idle slot: length 0, pad tokens are masked out
            occupied += 1
            off = self._slot_off[s]
            chunk = doc.ids[off:off + C]
            staged[s, :len(chunk)] = chunk
            staged[s, C] = len(chunk)
            doc.steps += 1
        return occupied

    def _emit_finished(self) -> None:
        """Mark slots whose document's last chunk just ran; gather their
        pool rows as ONE lazy device gather (no host sync here)."""
        done_slots = [
            s for s, doc in enumerate(self._slot_doc)
            if doc is not None and self._slot_off[s] + self.chunk_len >= len(doc.ids)
        ]
        if not done_slots:
            return
        # jnp.take, not self._pool[idx]: bracket indexing bakes a clip
        # bound as a fresh scalar constant that transfers host->device on
        # EVERY call — the per-step implicit transfer the runtime audit
        # (no_implicit_transfers over the slot loop) exists to catch.
        # Indices are live slot ids, in bounds by construction.
        gathered = jnp.take(
            self._pool,
            self._put_gather_indices(np.asarray(done_slots, np.int32)),
            axis=0)
        for k, s in enumerate(done_slots):
            doc = self._slot_doc[s]
            doc.gathered, doc.row = gathered, k
            self._slot_doc[s] = None
            self.docs_done += 1
            if doc.ctx is not None:  # device residency ends at emit
                doc.t_done = time.perf_counter()
            if self.registry is not None:
                self.registry.observe("slot_steps_per_doc", doc.steps)

    def _advance(self) -> bool:  # graft: hot
        """One scheduler step: refill, stage, dispatch, emit. Returns False
        when there is nothing left to run."""
        # a FRESH block every step: the device may read a handed-over
        # block at any later time (the host runs steps ahead of it), so
        # the host must never write one again. Costs one
        # B*(C+extra)*4-byte allocation + pad fill per step (8.5 KB at
        # the serve default 32x64) — no sync, still ONE h2d block.
        staged = np.full(self._staging_shape, self.engine.vocab.pad_id,
                         np.int32)
        occupied = self._refill(staged)
        if occupied == 0:
            return False
        # lane accounting off the host staging buffer (no device read):
        # every dispatched step pays batch×chunk lanes of compute; only
        # the staged lengths carried content
        self.tokens_stepped += self.batch_size * self.chunk_len
        self.tokens_valid += int(staged[:, self.chunk_len].sum())
        if self.mesh is not None:
            # per-data-shard lanes: dim 0 of the staging block splits
            # into contiguous row groups, one per data shard
            rows = self.batch_size // self._n_data_shards
            lens = staged[:, self.chunk_len]
            for k in range(self._n_data_shards):
                self._shard_stepped[k] += rows * self.chunk_len
                self._shard_valid[k] += int(
                    lens[k * rows:(k + 1) * rows].sum())
        if self.registry is not None:
            self.registry.observe("slot_occupancy", occupied)
            self.registry.set("slot_refill_queue_depth", len(self._queue))
            self.registry.set("slots_wasted_lane_fraction",
                              self.wasted_lane_fraction())
            if self.mesh is not None:
                for k in range(self._n_data_shards):
                    self.registry.set(
                        "slots_wasted_lane_fraction_shard",
                        self.shard_wasted_lane_fraction(k),
                        labels={"shard": str(k)})
        if self.mesh is None:
            params, staged_dev = self.engine._enc_params, jnp.asarray(staged)
        else:
            # the ONE h2d block per step, explicitly sharded: each data
            # shard receives its own rows (never a replicate-then-slice)
            params = self._params
            staged_dev = jax.device_put(staged, self._staging_sharding)
        self.h2d_bytes += int(staged.nbytes)  # the ONE h2d block per step
        self._pool, self._h_leaves = self._step(
            params, staged_dev, self._h_leaves, self._pool)
        self.steps_run += 1
        # host-side finish detection (pure offset arithmetic, no sync),
        # then a lazy row gather from the step's output pool — enqueued
        # before the next step may donate that buffer away
        self._emit_finished()
        for s, doc in enumerate(self._slot_doc):
            if doc is not None:
                self._slot_off[s] += self.chunk_len
        return True

    def in_flight(self) -> int:
        """Documents queued or resident in slots (advisory read, no
        lock): the server's graceful-drain signal — zero means a swap or
        shutdown strands nothing on the device."""
        return len(self._queue) + sum(
            doc is not None for doc in self._slot_doc)

    def drain(self) -> None:
        """Run steps until every queued and in-flight document finished."""
        while self._advance():
            pass
        if self.registry is not None:
            self.registry.set("slot_refill_queue_depth", len(self._queue))

    def reset(self) -> None:
        """Rebuild the persistent device state and empty the slot table.

        The step donates its state/pool buffers, so a runtime failure
        mid-step (transient device error) leaves them consumed; without
        this, the engine-cached scheduler would serve 'Array has been
        deleted' forever after. ``embed_ids`` calls it on any failure —
        the failing call's documents are lost (the caller sees the
        error), the NEXT call gets a healthy scheduler."""
        self._slot_doc = [None] * self.batch_size
        self._slot_off[:] = 0
        self._queue.clear()
        self._init_device_state()

    # -- results -----------------------------------------------------------

    def _finalize_rows(self, rows: np.ndarray) -> np.ndarray:
        """Packed (n, 3E+1) pool rows -> (n, 3E) embeddings."""
        E = self.engine.config.emb_sz
        return self.engine._finalize(
            (rows[:, :E], rows[:, E:2 * E], rows[:, 2 * E:3 * E], rows[:, 3 * E]))

    def materialize(self, tickets: Sequence[_Ticket]) -> np.ndarray:
        """Host-materialize finished tickets' embeddings with ONE device
        sync: all finish batches' gathers are concatenated on device and
        fetched together (per-batch fetches measured noise-sensitive on a
        contended host)."""
        offsets = {}  # id(gathered) -> row offset in the concat
        parts = []
        total = 0
        for t in tickets:
            if not t.done:
                raise RuntimeError("ticket not finished; call drain() first")
            key = id(t.gathered)
            if key not in offsets:
                offsets[key] = total
                parts.append(t.gathered)
                total += t.gathered.shape[0]
        # explicit fetch (not np.asarray): this is the slot loop's ONE
        # intended sync point, and the transfer audit pins that nothing
        # else in the loop transfers implicitly
        host = jax.device_get(parts[0] if len(parts) == 1
                              else jnp.concatenate(parts, axis=0))
        self.d2h_bytes += int(host.nbytes)  # the ONE d2h sync per batch
        self._export_dispatch_gauges()
        rows = np.stack([host[offsets[id(t.gathered)] + t.row]
                         for t in tickets])
        return self._finalize_rows(rows)

    # -- public API --------------------------------------------------------

    def embed_ids(self, id_seqs: Sequence[np.ndarray],  # graft: hot
                  ctxs: Optional[Sequence] = None) -> np.ndarray:
        """Embed already-numericalized docs through the slot loop; returns
        ``(N, 3*emb_sz)`` float32, order-preserving — the drop-in
        equivalent of ``engine.embed_ids_batch``.

        ``ctxs`` (one tracing SpanContext or None per doc) attributes each
        document's queue-wait / device-steps / pool-emit stages to its
        request's trace — the serving path's per-stage latency story."""
        n = len(id_seqs)
        if n == 0:
            return np.zeros((0, self.engine.embed_dim), np.float32)
        if ctxs is None:
            ctxs = [None] * n
        elif len(ctxs) != n:
            # zip() would silently drop the unmatched documents — a
            # wrong-shaped result corrupting caller row alignment
            raise ValueError(
                f"ctxs has {len(ctxs)} entries for {n} documents")
        with self._lock:
            tickets = [self.submit(ids, ctx=ctx)
                       for ids, ctx in zip(id_seqs, ctxs)]
            try:
                self.drain()
                t_emit0 = time.perf_counter()
                out = self.materialize(tickets)
                t_emit1 = time.perf_counter()
            except Exception:
                # donated buffers may be consumed — heal for the next call
                self.reset()
                raise
        for t in tickets:
            if t.ctx is None:
                continue
            # guarded, post-hoc, outside the scheduler lock: tracing is an
            # observer, never a dependency of the serve path
            tracing.record_span("slots.queue_wait", t.t_submit, t.t_slot,
                                t.ctx)
            tracing.record_span("slots.device_steps", t.t_slot, t.t_done,
                                t.ctx, steps=t.steps,
                                chunk_len=self.chunk_len)
            tracing.record_span("slots.pool_emit", t_emit0, t_emit1, t.ctx)
        return out


class RaggedSlotScheduler(SlotScheduler):
    """Ragged paged slot memory: length-aware continuous batching.

    Same public API and invariants as :class:`SlotScheduler` (one
    compiled step shape, reset-on-refill, per-doc completion, one packed
    never-rewritten staging block per step) with three structural
    changes — see the module docstring for the why:

    * the step is ``(batch, page_len)`` with ``page_len`` ≪ the dense
      ``chunk_len`` (default ``max(8, chunk_len // 4)``), so a row's
      cost tracks its own token count;
    * carried LSTM state and pool accumulators live in page ARENAS
      (``n_pages = 2·batch`` rows); the staging block carries one extra
      int32 column — each slot's state-page index — and the compiled
      step gathers/scatters state through that page table;
    * finishing a document RETIRES its page instead of gathering it:
      the page sits immutable in the arena (the step only writes active
      slots' pages) until one batched gather recycles the whole retired
      set — when the free list runs dry or ``materialize()`` needs rows.

    The step hands the staged per-row valid lengths to the encoder
    (``valid_lens=``), which routes the Pallas kernel paths to their
    ragged variants; the XLA scan path ignores them and stays the
    bit-for-bit parity reference (``tests/test_slot_scheduler.py``).
    """

    _STEP_NAME = "slots.step_ragged"
    _STAGING_EXTRA = 3  # [length, refill-reset, state-page]
    _POOL_OWNER = "paged_pool"

    def __init__(self, engine, page_len: Optional[int] = None,
                 registry=None, mesh=None):
        self._page_len_req = int(page_len) if page_len else 0
        # B active pages + B retired-awaiting-emit: at most one finish
        # per slot per step, so the free list can never run dry faster
        # than a flush refills it. (n_pages = 2B keeps per-shard page
        # geometry consistent under a mesh: batch % data == 0 implies
        # every data shard owns the same page count.)
        self.n_pages = 2 * engine.batch_size
        super().__init__(engine, chunk_len=None, registry=registry,
                         mesh=mesh)
        self.page_len = self.chunk_len  # the public name for the knob

    def _snap_chunk(self, chunk_len: Optional[int]) -> int:
        if self._page_len_req:
            return max(1, self._page_len_req)
        dense = self.engine._bucket_for_static(64, self.engine.buckets)
        return max(8, dense // 4)

    # -- page accounting (the occupancy primitive ROADMAP direction 2's
    # unified page table needs; reconciled against the ledger's
    # paged-pool row in tests) ---------------------------------------------

    def pages_free(self) -> int:
        """Free-list depth (host-side int, no device read)."""
        return len(self._free_pages)

    def pages_live(self) -> int:
        """Pages holding live document state: occupied slots' pages plus
        retired pages awaiting their batched emit gather. The remainder
        (``n_pages - free - live``) is idle slots' parked pages."""
        return (sum(doc is not None for doc in self._slot_doc)
                + len(self._retired))

    def _export_page_gauges(self) -> None:
        if self.registry is None:
            return
        self.registry.set("slots_pages_free", self.pages_free())
        self.registry.set("slots_pages_live", self.pages_live())

    def bind_registry(self, registry) -> None:
        super().bind_registry(registry)
        if registry is None:
            return
        registry.gauge(
            "slots_pages_free",
            "ragged state-arena free-list depth (pages not bound to any "
            "slot and not awaiting emit)")
        registry.gauge(
            "slots_pages_live",
            "ragged state-arena pages holding live document state "
            "(occupied slots + retired-awaiting-emit)")
        self._export_page_gauges()

    def register_memory_owners(self, ledger, prefix: str = "slots") -> None:
        super().register_memory_owners(ledger, prefix=prefix)
        # arena geometry for capacity_report: what one page costs and
        # how many exist (pool row + its share of every state arena)
        per_page = (int(self._pool.nbytes)
                    + sum(int(l.nbytes) for l in self._h_leaves)) \
            // self.n_pages
        ledger.note_geometry(pages_total=self.n_pages,
                             page_len=self.page_len,
                             page_bytes=int(per_page))

    def _init_device_state(self) -> None:
        B = self.batch_size
        # page table: slot s starts on page s; the spare half feeds the
        # free list. Retired docs awaiting their batched gather are
        # (ticket, page) pairs.
        self._slot_page = np.arange(B, dtype=np.int64)
        self._free_pages: Deque[int] = deque(range(B, self.n_pages))
        self._retired: List = []
        self._h_leaves = tuple(
            jax.tree.leaves(init_lstm_states(self.engine.config,
                                             self.n_pages)))
        self._pool = self._pack_pool(
            self.engine._init_pool_state(self.n_pages))
        # under a mesh the ARENAS shard their page dim over 'data' (the
        # same row sharding as the dense state, just 2B rows)
        self._h_leaves, self._pool = self._place_state(
            self._h_leaves, self._pool)

    def _build_step(self):
        engine = self.engine
        treedef = engine._state_treedef
        C = self.chunk_len

        def step(params, staged, h_leaves, pool):
            tokens = staged[:, :C]
            lengths = staged[:, C]
            reset = staged[:, C + 1] > 0
            pages = staged[:, C + 2]
            # page-table gather: each slot's carried state + pool row.
            # Retired pages are never in `pages`, so they stay immutable
            # through the donated in-place scatter below — that is what
            # makes the deferred finish-gather safe.
            rows = tuple(jnp.take(leaf, pages, axis=0) for leaf in h_leaves)
            prow = jnp.take(pool, pages, axis=0)
            r = reset[:, None]
            rows = tuple(
                jnp.where(r, jnp.zeros_like(row), row) for row in rows)
            prow = jnp.where(r, self._init_pool()[:1], prow)
            states = jax.tree.unflatten(treedef, rows)
            # valid_lens: the Pallas kernel paths skip exhausted tiles'
            # matmul work; the scan path ignores it (parity reference)
            raw, _, new_states = engine.encoder.apply(
                params, tokens, states, deterministic=True,
                valid_lens=lengths)
            prow = self._pack_pool(engine._accumulate_pool(
                raw, lengths, self._unpack_pool(prow)))
            h_leaves = tuple(
                leaf.at[pages].set(row)
                for leaf, row in zip(h_leaves, jax.tree.leaves(new_states)))
            pool = pool.at[pages].set(prow)
            return pool, h_leaves

        return self._jit_step(step)

    def _refill(self, staged: np.ndarray) -> int:
        occupied = super()._refill(staged)
        # the page table rides the SAME packed staging block — never its
        # own per-step h2d transfer (the transfer audit pins this)
        staged[:, self.chunk_len + 2] = self._slot_page
        return occupied

    def _emit_finished(self) -> None:
        """Retire finished slots' pages (no device work here): swap the
        slot onto a fresh page from the free list and leave the finished
        page immutable until :meth:`_flush_retired` batches the gather."""
        B, C = self.batch_size, self.chunk_len
        for s in range(B):
            doc = self._slot_doc[s]
            if doc is None or self._slot_off[s] + C < len(doc.ids):
                continue
            if not self._free_pages:
                self._flush_retired()  # recycle before we run dry
            self._retired.append((doc, int(self._slot_page[s])))
            self._slot_page[s] = self._free_pages.popleft()
            self._slot_doc[s] = None
            self.docs_done += 1
            if doc.ctx is not None:  # device residency ends at retire
                doc.t_done = time.perf_counter()
            if self.registry is not None:
                self.registry.observe("slot_steps_per_doc", doc.steps)
        self._export_page_gauges()

    def _flush_retired(self) -> None:
        """ONE lazy device gather for the whole retired set, then recycle
        the pages. Enqueued before any later step can scatter to a
        recycled page, same ordering contract as the dense path's
        per-finish-batch gather — but amortized over up to ``batch``
        documents instead of paid every step."""
        if not self._retired:
            return
        pages = np.asarray([p for _, p in self._retired], np.int32)
        # jnp.take (not bracket indexing) for the same reason as the
        # dense emit: a baked clip-bound scalar would transfer h2d on
        # every flush. Indices are retired page ids, in bounds.
        gathered = jnp.take(self._pool, self._put_gather_indices(pages),
                            axis=0)
        for k, (doc, p) in enumerate(self._retired):
            doc.gathered, doc.row = gathered, k
            self._free_pages.append(p)
        self._retired.clear()
        self._export_page_gauges()

    def materialize(self, tickets: Sequence[_Ticket]) -> np.ndarray:
        self._flush_retired()
        return super().materialize(tickets)
