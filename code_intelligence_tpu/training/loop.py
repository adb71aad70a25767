"""pjit-sharded LM training loop.

Replaces the fastai ``Learner.fit_one_cycle`` hot loop the reference runs
(`Issue_Embeddings/train.py:104-116`; call stack SURVEY.md §3.1) with a
jit-compiled train step over a ``("data", "model")`` mesh:

* truncated-BPTT hidden state lives **inside the donated TrainState**, so
  the carry never leaves device HBM between steps (SURVEY.md §7
  "stateful truncated BPTT under pjit");
* loss = cross-entropy + fastai's AR/TAR activation regularizers
  (``language_model_learner`` defaults alpha=2, beta=1). The decoder's
  60,000-way product lives in `ops/lm_loss.py::decoder_cross_entropy`
  since PR 50, with the cross-entropy and the accuracy, as one op with a
  backward of its own: the step asks the model for its ``features`` (the
  encoder's outputs and the decoder's leaves) and never holds a float32
  array of the logits' shape. Which core runs the op (Pallas kernels on
  one TPU chip in bfloat16, the einsum under ``optax`` everywhere else)
  is the op's own choice; ``AWDLSTMLM.__call__``'s logits are for every
  other caller;
* one-cycle LR + momentum schedules (`train.py:109-111`), with a runtime
  ``lr_scale`` knob so ReduceLROnPlateau works without recompiling;
* all dropout randomness is jit-internal (`jax.random.fold_in`).

The loop itself is host-side Python feeding numpy windows from
``LMStreamLoader``; everything numeric is one compiled XLA program per
(bs, bptt) shape.
"""

from __future__ import annotations

import dataclasses
import logging
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import struct
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from code_intelligence_tpu.models import AWDLSTMConfig, AWDLSTMLM, init_lstm_states
from code_intelligence_tpu.ops.lm_loss import decoder_cross_entropy, loss_is_kernel
from code_intelligence_tpu.ops.pallas_lstm import fits_resident
from code_intelligence_tpu.parallel import (
    batch_sharding,
    make_mesh,
    param_shardings,
    replicated,
    state_sharding,
)
from code_intelligence_tpu.training import schedules
from code_intelligence_tpu.utils import flight_recorder as flight
from code_intelligence_tpu.utils import profiling, tracing

log = logging.getLogger(__name__)


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    """Optimization hyperparameters (reference defaults, `train.py:42-46`)."""

    batch_size: int = 104
    bptt: int = 67
    lr: float = 1.3e-3  # best-run lr=0.0013 (`hyperparam_sweep/README.md:25`)
    one_cycle: bool = True
    cycle_len: int = 1  # epochs per cycle (`train.py:106-111`)
    moms: Tuple[float, float] = (0.85, 0.95)
    wd: float = 0.01  # fastai default true weight decay
    alpha: float = 2.0  # AR on dropped output
    beta: float = 1.0  # TAR on raw output
    grad_clip: Optional[float] = None
    pct_start: float = 0.3
    adam_eps: float = 1e-7
    # Windows per device dispatch (lax.scan inside one jit). >1 amortizes
    # per-dispatch host latency. 1 = the classic step-per-dispatch loop.
    # Semantics are identical either way
    # (tests/test_training.py::TestTrainSteps). The default IS the product
    # path, and the value the `lstm_train_lm` cell runs; 20 has not been
    # re-decided on a directly attached chip (ROADMAP S7/D11).
    steps_per_dispatch: int = 20


def train_cell_is_resident(backend: str, itemsize: int, hidden: int,
                           mesh_size: int) -> bool:
    """Resident cell or scan, for ONE LSTM layer of a train step: the
    rule, from what the program can observe and nothing a user sets.

    The weights-resident Pallas cell (`ops/pallas_lstm.py`, forward with
    residuals and the adjoint) runs where it exists and where it fits:
    on the TPU (off it the kernel is the interpreter, a test device);
    when the layer's ``W_hh`` at the compute dtype fits VMEM
    (``fits_resident``: H=2500 and H=800 in bfloat16, not H=2500 in
    float32, 100 MB); and on a one-device mesh (a GSPMD-partitioned step
    cannot hold a Mosaic call: JAX refuses it at the first dispatch, 4x
    v5e, PR 21). Everywhere else the layer runs the XLA scan."""
    return (backend == "tpu" and mesh_size == 1
            and fits_resident(hidden, itemsize))


def train_cell_config(config: AWDLSTMConfig,
                      mesh_size: int) -> Tuple[AWDLSTMConfig, int]:
    """``config`` as a train step builds its model, and how many of its
    LSTM layers then run the resident cell. `models/awd_lstm.py` asks
    ``fits_resident`` per layer under ``lstm_use_pallas``, so the field
    is set iff some layer qualifies; what the caller's config said is
    not read (the serve side reads its own: ``InferenceEngine``)."""
    backend = jax.default_backend()
    itemsize = jnp.dtype(config.dtype).itemsize
    resident = 0 if config.qrnn else sum(
        train_cell_is_resident(backend, itemsize, config.layer_size(li),
                               mesh_size)
        for li in range(config.n_layers))
    return dataclasses.replace(config, lstm_use_pallas=resident > 0), resident


class TrainState(struct.PyTreeNode):
    step: jnp.ndarray
    params: Any
    opt_state: Any
    lstm_states: Any
    rng: jax.Array
    lr_scale: jnp.ndarray  # runtime knob for ReduceLROnPlateau


class LMTrainer:
    """Builds the compiled train/eval steps for an AWD-LSTM LM on a mesh."""

    def __init__(
        self,
        model_config: AWDLSTMConfig,
        train_config: TrainConfig = TrainConfig(),
        mesh: Optional[Mesh] = None,
        steps_per_epoch: Optional[int] = None,
    ):
        self.mcfg = model_config
        self.tcfg = train_config
        flight.get_accountant().listen()  # every compile, a named record
        self.mesh = mesh if mesh is not None else make_mesh()
        if (self.mesh.size > 1 and jax.default_backend() == "tpu"
                and model_config.qrnn_use_pallas):
            # the train step is one GSPMD-partitioned jit with no
            # shard_map around the kernel; on the chip JAX refuses that
            # at the first dispatch (4x v5e, PR 21). Refuse it here, by
            # name. (Off the TPU interpret mode lowers to plain HLO,
            # which partitions — the CPU mesh tests keep running it.)
            raise ValueError(
                "--qrnn_pallas does not compose with a "
                f"multi-device mesh {dict(self.mesh.shape)} on TPU: JAX "
                "refuses the partitioned step (\"Mosaic kernels cannot be "
                "automatically partitioned. Please wrap the call in a "
                "shard_map.\"). Train one chip with the kernel, or the "
                "mesh on the XLA scan.")
        # the LSTM recurrence's cell (resident Pallas cell or XLA scan) is
        # this step's own choice, per layer, from backend, dtype,
        # fits_resident and the mesh: train_cell_is_resident
        step_config, self.resident_lstm_layers = train_cell_config(
            model_config, self.mesh.size)
        # likewise the decoder's product and the cross-entropy: Pallas
        # kernels or the einsum under optax is the op's own choice
        # (ops/lm_loss.py::loss_is_kernel); 1 where a step of this
        # trainer's shapes runs the kernels, for the spans to say
        self.loss_kernel = int(loss_is_kernel(
            jax.default_backend(), model_config.dtype,
            train_config.batch_size * train_config.bptt,
            model_config.emb_sz, model_config.vocab_size, self.mesh.size))
        # seq_axis: the model's QRNN layers time-shard their recurrence over
        # this mesh (parallel/seq_parallel.py); without it mesh stays out of
        # the module so jit caching keys only on config
        self.model = AWDLSTMLM(
            step_config,
            mesh=self.mesh if model_config.seq_axis else None,
        )
        total = (steps_per_epoch or 1000) * train_config.cycle_len
        if train_config.one_cycle:
            # fit_one_cycle(cyc_len, max_lr=lr*2) — train.py:109-111.
            self.lr_schedule = schedules.one_cycle_lr(
                total, train_config.lr * 2, pct_start=train_config.pct_start
            )
            self.mom_schedule = schedules.one_cycle_momentum(
                total, *train_config.moms, pct_start=train_config.pct_start
            )
        else:
            self.lr_schedule = schedules.constant(train_config.lr)
            self.mom_schedule = schedules.constant(train_config.moms[1])
        self.optimizer = self._build_optimizer()
        self._train_step = None
        self._train_steps = None
        self._eval_step = None
        self._eval_steps = None
        # set by FlightRecorderCallback.on_train_begin: when present,
        # train AND eval dispatches append per-step telemetry records
        self.flight_recorder = None

    def _build_optimizer(self) -> optax.GradientTransformation:
        t = self.tcfg
        chain = []
        if t.grad_clip:
            chain.append(optax.clip_by_global_norm(t.grad_clip))
        chain.append(
            optax.inject_hyperparams(optax.adamw)(
                learning_rate=self.lr_schedule,
                b1=self.mom_schedule,
                b2=0.99,  # fastai Adam default betas (0.9→cycled, 0.99)
                eps=t.adam_eps,
                weight_decay=t.wd,
            )
        )
        return optax.chain(*chain)

    # ------------------------------------------------------------------
    # State
    # ------------------------------------------------------------------

    def init_state(self, rng: jax.Array, local_batch_size: Optional[int] = None) -> TrainState:
        bs = local_batch_size or self.tcfg.batch_size
        tokens = jnp.zeros((bs, self.tcfg.bptt), jnp.int32)
        states = init_lstm_states(self.mcfg, bs)
        params = self.model.init({"params": rng}, tokens, states)["params"]
        # Place params/opt-state according to the mesh sharding rules so
        # GSPMD sees the intended layout from step 0.
        shardings = param_shardings(params, self.mesh)
        params = jax.tree.map(jax.device_put, params, shardings)
        opt_state = self.optimizer.init(params)
        # Scalars are committed replicated: checkpoint restore then yields
        # identical placements for fresh and resumed states (a restored
        # scalar pinned to one device while params span the mesh is a jit
        # "incompatible devices" error). Non-scalar opt leaves (mu/nu)
        # inherit the params' shardings from zeros_like.
        rep = replicated(self.mesh)
        opt_state = jax.tree.map(
            lambda x: jax.device_put(x, rep) if getattr(x, "ndim", None) == 0 else x,
            opt_state,
        )
        return TrainState(
            step=jax.device_put(jnp.zeros((), jnp.int32), rep),
            params=params,
            opt_state=opt_state,
            lstm_states=jax.tree.map(
                lambda x: jax.device_put(x, state_sharding(self.mesh)), states
            ),
            rng=jax.device_put(rng, rep),
            lr_scale=jax.device_put(jnp.ones(()), rep),
        )

    def reset_lstm_states(self, state: TrainState) -> TrainState:
        """Zero the carried hidden state (between epochs / corpora —
        the reference's ``encoder.reset()`` semantics)."""
        return state.replace(
            lstm_states=jax.tree.map(jnp.zeros_like, state.lstm_states)
        )

    # ------------------------------------------------------------------
    # Compiled steps
    # ------------------------------------------------------------------

    def _decoded(self, params, x, y, lstm_states, **apply_kwargs):
        """``(ce, accuracy, raw, dropped, new_states)`` of one window:
        the model up to the decoder's leaves (``AWDLSTMLM.features``),
        then the decoder's product, the cross-entropy and the accuracy
        as `ops/lm_loss.py`'s one op. The train and the validation step
        share it."""
        raw, dropped, new_states, dec_w, dec_b = self.model.apply(
            {"params": params}, x, lstm_states, method="features",
            **apply_kwargs)
        # named like the model's own parts (models/awd_lstm.py; the op
        # names its own: the product `decoder`, the rest `loss`), so a
        # capture shows the step as embedding / lstm_i / decoder / loss /
        # optimizer instead of fusion numbers
        ce, hit = decoder_cross_entropy(dropped, dec_w, dec_b, y,
                                        devices=self.mesh.size)
        with jax.named_scope("loss"):
            ce, acc = ce.mean(), jnp.mean(hit.astype(jnp.float32))
        return ce, acc, raw, dropped, new_states

    def _loss(self, params, x, y, lstm_states, dropout_rng):
        ce, acc, raw, dropped, new_states = self._decoded(
            params, x, y, lstm_states, deterministic=False,
            rngs={"dropout": dropout_rng})
        with jax.named_scope("loss"):
            # fastai RNNRegularizer (alpha=AR on dropped, beta=TAR on raw).
            ar = self.tcfg.alpha * jnp.mean(jnp.square(dropped.astype(jnp.float32)))
            tar = self.tcfg.beta * jnp.mean(
                jnp.square((raw[:, 1:] - raw[:, :-1]).astype(jnp.float32))
            )
        return ce + ar + tar, (new_states, ce, acc)

    def _pin_carry(self, lstm_states):
        """Hand the carried states back in the sharding they came in with
        (``init_state`` / ``_evaluate`` place them batch-sharded). Under a
        model axis GSPMD would otherwise return them split over 'model'
        too, and the changed input signature recompiles the next dispatch
        (seen as a second ``train.steps`` compile under
        ``--model_parallel 2``)."""
        return jax.lax.with_sharding_constraint(
            lstm_states, state_sharding(self.mesh))

    def _make_train_step(self):
        step = self._train_step_body()

        def train_step(state: TrainState, x: jnp.ndarray, y: jnp.ndarray):
            state, metrics = step(state, x, y)
            return state.replace(
                lstm_states=self._pin_carry(state.lstm_states)), metrics

        data_sh = batch_sharding(self.mesh)
        return jax.jit(
            train_step,
            donate_argnums=(0,),
            in_shardings=(None, data_sh, data_sh),
        )

    def _train_step_body(self):
        optimizer = self.optimizer

        def train_step(state: TrainState, x: jnp.ndarray, y: jnp.ndarray):
            step_rng = jax.random.fold_in(state.rng, state.step)
            (loss, (new_states, ce, acc)), grads = jax.value_and_grad(
                self._loss, has_aux=True
            )(state.params, x, y, state.lstm_states, step_rng)
            with jax.named_scope("optimizer"):
                updates, new_opt = optimizer.update(grads, state.opt_state, state.params)
                updates = jax.tree.map(lambda u: u * state.lr_scale, updates)
                new_params = optax.apply_updates(state.params, updates)
            new_states = jax.lax.stop_gradient(new_states)
            metrics = {
                "loss": loss,
                "ce": ce,
                "accuracy": acc,
                "grad_norm": optax.global_norm(grads),
                # flight-record fields, computed in the compiled step so
                # the host loop never pays extra dispatches for them:
                # param_norm is one O(P) reduction (noise against the
                # O(P*B*T) fwd+bwd), lr is the schedule the optimizer
                # itself applies (inject_hyperparams) times the runtime
                # plateau scale
                "param_norm": optax.global_norm(new_params),
                "lr": self.lr_schedule(state.step) * state.lr_scale,
            }
            return (
                state.replace(
                    step=state.step + 1,
                    params=new_params,
                    opt_state=new_opt,
                    lstm_states=new_states,
                ),
                metrics,
            )

        return train_step

    def _make_train_steps(self):
        """k windows per dispatch: ``lax.scan`` of the SAME step body.

        Each dispatch pays a fixed host cost; scanning k (x, y) windows
        inside one jit amortizes it k-fold. Semantics are identical to k
        sequential ``train_step``
        calls by construction (same body, same per-step rng fold-in via the
        carried ``state.step``, BPTT hidden carry through the scan) — pinned
        exactly by tests/test_training.py. Metrics come back stacked (k,).
        """
        step = self._train_step_body()

        def train_steps(state: TrainState, xs: jnp.ndarray, ys: jnp.ndarray):
            def body(st, xy):
                st, metrics = step(st, xy[0], xy[1])
                return st, metrics

            state, metrics = jax.lax.scan(body, state, (xs, ys))
            return state.replace(
                lstm_states=self._pin_carry(state.lstm_states)), metrics

        window_sh = NamedSharding(self.mesh, P(None, "data", None))
        return jax.jit(
            train_steps,
            donate_argnums=(0,),
            in_shardings=(None, window_sh, window_sh),
        )

    def _eval_step_body(self):
        def eval_step(params, lstm_states, x, y):
            ce, acc, _, _, new_states = self._decoded(
                params, x, y, lstm_states, deterministic=True)
            return ce, acc, new_states

        return eval_step

    def _make_eval_step(self):
        step = self._eval_step_body()

        def eval_step(params, lstm_states, x, y):
            ce, acc, states = step(params, lstm_states, x, y)
            return ce, acc, self._pin_carry(states)

        data_sh = batch_sharding(self.mesh)
        return jax.jit(eval_step, in_shardings=(None, None, data_sh, data_sh))

    def _make_eval_steps(self):
        """k eval windows per dispatch — the validation-side twin of
        ``train_steps`` (same dispatch-latency argument; validation is
        pure dispatch + forward, so it benefits even more)."""
        step = self._eval_step_body()

        def eval_steps(params, lstm_states, xs, ys):
            def body(st, xy):
                ce, acc, st = step(params, st, xy[0], xy[1])
                return st, (ce, acc)

            states, (ces, accs) = jax.lax.scan(body, lstm_states, (xs, ys))
            return ces, accs, self._pin_carry(states)

        window_sh = NamedSharding(self.mesh, P(None, "data", None))
        return jax.jit(
            eval_steps, in_shardings=(None, None, window_sh, window_sh)
        )

    # Compiled-step properties, wrapped in the XLA accountant
    # (utils/flight_recorder.py): each newly-compiled shape records
    # compile wall time, cost_analysis flops, and memory_analysis HBM
    # footprint, surfaced on /debug/flight and as compile_seconds /
    # compiled_hbm_bytes gauges. The wrapper falls back to the plain
    # jitted callable on any accounting failure.

    @property
    def train_step(self):
        if self._train_step is None:
            self._train_step = flight.instrument(
                self._make_train_step(), "train.step")
        return self._train_step

    @property
    def train_steps(self):
        if self._train_steps is None:
            self._train_steps = flight.instrument(
                self._make_train_steps(), "train.steps")
        return self._train_steps

    @property
    def eval_step(self):
        if self._eval_step is None:
            self._eval_step = flight.instrument(
                self._make_eval_step(), "eval.step")
        return self._eval_step

    @property
    def eval_steps(self):
        if self._eval_steps is None:
            self._eval_steps = flight.instrument(
                self._make_eval_steps(), "eval.steps")
        return self._eval_steps

    # ------------------------------------------------------------------
    # Fit (host loop + callbacks)
    # ------------------------------------------------------------------

    def evaluate(self, state: TrainState, valid_loader) -> Dict[str, float]:
        # ambient span: attaches to the caller's open trace, free no-op
        # when there is none (fit() records its own train.eval trace
        # around _evaluate)
        with tracing.span("train.eval"):
            return self._evaluate(state, valid_loader)

    def _evaluate(self, state: TrainState, valid_loader) -> Dict[str, float]:
        ces: List[float] = []
        accs: List[float] = []
        # Fresh states sized to the *eval* loader: a valid_loader with a
        # different local_bs than training must work without reshaping.
        eval_states = jax.device_put(
            init_lstm_states(self.mcfg, valid_loader.local_bs),
            state_sharding(self.mesh))
        k = max(1, self.tcfg.steps_per_dispatch)
        buf: List[Tuple[np.ndarray, np.ndarray]] = []
        recorder = self.flight_recorder
        # one sync for the whole evaluate (it syncs per dispatch anyway)
        train_step_now = int(state.step) if recorder is not None else 0
        tokens_per_window = valid_loader.local_bs * self.tcfg.bptt

        def _record_eval(window_ces, dt, n):
            # one record per eval step — same ring, kind="eval", so the
            # flight dump interleaves train and eval telemetry in time
            for ce in window_ces:
                recorder.record(
                    step=train_step_now, kind="eval", loss=float(ce),
                    tokens_per_sec=tokens_per_window / max(dt / n, 1e-9),
                    step_time_s=dt / n)

        def flush():
            nonlocal eval_states
            xs = np.stack([x for x, _ in buf])
            ys = np.stack([y for _, y in buf])
            t0 = time.perf_counter()
            win_ces, win_accs, eval_states = self.eval_steps(
                state.params, eval_states, xs, ys
            )
            win_ces = np.asarray(jax.device_get(win_ces), np.float64)
            dt = time.perf_counter() - t0
            ces.extend(win_ces)
            accs.extend(np.asarray(jax.device_get(win_accs), np.float64))
            if recorder is not None:
                _record_eval(win_ces, dt, len(buf))
            buf.clear()

        def run_single(x, y):
            nonlocal eval_states
            t0 = time.perf_counter()
            ce, acc, eval_states = self.eval_step(state.params, eval_states, x, y)
            # ONE explicit fetch for both scalars: float(ce) + float(acc)
            # paid two implicit device round-trips per window
            ce, acc = map(float, jax.device_get((ce, acc)))
            dt = time.perf_counter() - t0
            ces.append(ce)
            accs.append(acc)
            if recorder is not None:
                _record_eval([ce], dt, 1)

        for x, y in valid_loader.epoch(0):
            if k == 1:
                run_single(x, y)
                continue
            buf.append((x, y))
            if len(buf) == k:
                flush()
        for x, y in buf:  # tail (< k) through the single-window program
            run_single(x, y)
        val_loss = float(np.mean(ces)) if ces else float("nan")
        return {
            "val_loss": val_loss,
            "val_accuracy": float(np.mean(accs)) if accs else float("nan"),
            "val_perplexity": float(np.exp(val_loss)),
        }

    def fit(  # graft: hot
        self,
        train_loader,
        valid_loader=None,
        epochs: Optional[int] = None,
        callbacks: Sequence = (),
        state: Optional[TrainState] = None,
        rng: Optional[jax.Array] = None,
    ) -> Tuple[TrainState, List[Dict[str, float]]]:
        epochs = epochs if epochs is not None else self.tcfg.cycle_len
        if state is None:
            state = self.init_state(
                rng if rng is not None else jax.random.PRNGKey(0),
                local_batch_size=train_loader.local_bs,
            )
        # spans on the process-global tracer. A trace holds
        # MAX_SPANS_PER_TRACE spans and reaches /debug/traces and the
        # on_trace observers only when its root ends, so a fit is NOT one
        # trace: every train.dispatch / train.step / train.eval is a trace
        # of its own, delivered as it finishes, and train.fit /
        # train.epoch are one-span records (started explicitly, never on
        # the thread's ambient stack, so the spans inside them start new
        # traces). All carry ``fit_id`` (the train.fit record's trace id)
        # and the epoch. A caller that holds its own span open around
        # fit() has chosen one trace for it, by the tracer's parent rule.
        # The first dispatch of each compiled shape is flagged
        # compile=True, separating XLA compile time from steady-state
        # step time, and gains compile_s, the seconds of the trace,
        # lowering and backend stage it paid (set from the instrumented
        # step's compile path, utils/flight_recorder.py: no statement
        # here). Bounded and guarded (utils/tracing.py): the hot loop
        # never pays more than a few dict ops per DISPATCH (k steps), and
        # never raises.
        tracer = tracing.get_tracer()
        resident, loss_kernel = self.resident_lstm_layers, self.loss_kernel
        fit_span = tracer.start_span("train.fit", epochs=epochs,
                                     resident_lstm_layers=resident,
                                     loss_kernel=loss_kernel)
        fit_id = fit_span.trace_id
        ep_span = None
        with self.mesh:
            for cb in callbacks:
                cb.on_train_begin(self)
            history: List[Dict[str, float]] = []
            stop = False
            step0 = int(state.step)  # one sync per fit(), not per step
            # per-DISPATCH wall-time stats for the whole fit; dispatches
            # that paid an XLA compile are dropped from the samples (the
            # loop knows exactly which ones, a sharper cut than
            # StepTimer's positional exclude_first_n) so the epoch's
            # dispatch_p* fields describe steady state
            timer = profiling.StepTimer()
            tokens_per_window = train_loader.local_bs * self.tcfg.bptt

            def notify(step, metrics):
                """on_step_end fan-out; any callback returning "stop"
                (a flight-recorder divergence halt) halts the fit
                within this step."""
                halt = False
                for cb in callbacks:
                    # host-side counter: int(state.step) here would force
                    # a device sync every step and kill async dispatch.
                    if cb.on_step_end(step, metrics) == "stop":
                        halt = True
                return halt

            try:
                for epoch in range(epochs):
                    ep_span = tracer.start_span(
                        "train.epoch", fit_id=fit_id, epoch=epoch)
                    state = self.reset_lstm_states(state)
                    t0 = time.time()
                    losses = []
                    k = max(1, self.tcfg.steps_per_dispatch)
                    buf: List[Tuple[np.ndarray, np.ndarray]] = []
                    halt = False

                    def run_single(state, x, y, step0, _epoch=epoch):
                        compiled = self._train_step is not None
                        timer.start()
                        with tracer.span("train.step", fit_id=fit_id,
                                         epoch=_epoch, compile=not compiled,
                                         resident_lstm_layers=resident,
                                         loss_kernel=loss_kernel):
                            state, metrics = self.train_step(state, x, y)
                        dt = timer.stop()
                        if not compiled:
                            timer.samples.pop()  # compile, not steady state
                        step0 += 1
                        # enrich with the host-side flight-record fields;
                        # on this k=1 path dt is host-visible dispatch
                        # time (no sync) — truthful device timing is the
                        # k>1 path's device_get-inclusive dt
                        metrics = dict(metrics)
                        metrics.update(
                            step_time_s=dt,
                            tokens_per_sec=tokens_per_window / max(dt, 1e-9),
                            compile=not compiled)
                        losses.append(metrics)
                        return state, step0, notify(step0, metrics)

                    def flush(state, step0, _epoch=epoch):
                        xs = np.stack([x for x, _ in buf])
                        ys = np.stack([y for _, y in buf])
                        n = len(buf)
                        compiled = self._train_steps is not None
                        timer.start()
                        # the same name in a profiler capture's host
                        # timeline, on the device trace's own clock
                        with tracer.span("train.dispatch", fit_id=fit_id,
                                         epoch=_epoch, windows=n,
                                         compile=not compiled,
                                         resident_lstm_layers=resident,
                                         loss_kernel=loss_kernel), \
                                profiling.annotate("train.dispatch"):
                            state, ms = self.train_steps(state, xs, ys)
                            # ONE transfer for the whole chunk — per-element
                            # device slicing would enqueue ~4k tiny programs,
                            # each paying the dispatch cost the scan just
                            # amortized. The device_get stays inside the
                            # span: it IS the step's device-sync time.
                            ms = jax.device_get(ms)
                        dt = timer.stop()
                        if not compiled:
                            timer.samples.pop()  # compile, not steady state
                        per_step = dt / n
                        extra = {
                            "step_time_s": per_step,
                            "tokens_per_sec": tokens_per_window
                            / max(per_step, 1e-9),
                            "compile": not compiled,
                        }
                        halt = False
                        for i in range(n):
                            metrics = {key: v[i] for key, v in ms.items()}
                            metrics.update(extra)
                            losses.append(metrics)
                            step0 += 1
                            if notify(step0, metrics):
                                # the rest of the chunk already ran on
                                # device, but a divergence halt means its
                                # metrics are no longer worth reporting
                                halt = True
                                break
                        buf.clear()
                        return state, step0, halt

                    for x, y in train_loader.epoch(epoch):
                        if k == 1:
                            state, step0, halt = run_single(state, x, y, step0)
                        else:
                            buf.append((x, y))
                            if len(buf) == k:
                                state, step0, halt = flush(state, step0)
                        if halt:
                            break
                    # tail windows (< k) go through the single-step program
                    # so the scanned shape never varies (one compile per k)
                    if not halt:
                        for x, y in buf:
                            state, step0, halt = run_single(state, x, y, step0)
                            if halt:
                                break
                    buf.clear()
                    if halt:
                        # halt-and-checkpoint: give halt-aware callbacks
                        # (FlightRecorderCallback) the exact halted state;
                        # skip epoch metrics/eval — the run is diverging
                        for cb in callbacks:
                            fn = getattr(cb, "on_halt", None)
                            if fn is None:
                                continue
                            try:
                                fn(step0, state, self)
                            except Exception:
                                log.exception("on_halt callback failed")
                        ep_span.set(halted=True)
                        ep_span.end()
                        break
                    epoch_metrics = {
                        "epoch": epoch,
                        # ONE explicit device pull for the epoch's losses
                        # (k=1 leaves device scalars in `losses`; float()
                        # on each would be len(losses) implicit syncs).
                        # numpy mean on host: stacking hundreds of device
                        # scalars in one eager concat intermittently
                        # aborts the XLA CPU client; epoch end syncs
                        # anyway
                        "loss": float(np.mean(jax.device_get(
                            [m["loss"] for m in losses])))
                        if losses
                        else float("nan"),
                        "time": time.time() - t0,
                        "tokens_per_sec": train_loader.tokens_per_epoch / max(time.time() - t0, 1e-9),
                    }
                    ts = timer.summary()
                    if ts:  # fit-cumulative steady-state dispatch stats
                        epoch_metrics["dispatch_p50_s"] = ts["p50_s"]
                        epoch_metrics["dispatch_p99_s"] = ts["p99_s"]
                    if valid_loader is not None:
                        with tracer.span("train.eval", fit_id=fit_id,
                                         epoch=epoch):
                            epoch_metrics.update(
                                self._evaluate(state, valid_loader))
                    history.append(epoch_metrics)
                    for cb in callbacks:
                        action = cb.on_epoch_end(epoch, epoch_metrics, state, self)
                        if action == "stop":
                            stop = True
                        elif isinstance(action, tuple) and action[0] == "lr_scale":
                            state = state.replace(
                                lr_scale=state.lr_scale * jnp.asarray(action[1])
                            )
                    ep_span.end()
                    if stop:
                        break
            except Exception as exc:
                # crash path: let crash-aware callbacks dump their flight
                # rings (guarded — a dump failure must not mask the real
                # error), then re-raise unchanged
                for cb in callbacks:
                    fn = getattr(cb, "on_crash", None)
                    if fn is None:
                        continue
                    try:
                        fn(step0, exc)
                    except Exception:
                        log.exception("on_crash callback failed")
                fit_span.set(error=type(exc).__name__)
                raise
            finally:
                # both idempotent: an epoch cut short by a crash still
                # closes its record instead of leaking a live trace
                if ep_span is not None:
                    ep_span.end()
                fit_span.end()
            for cb in callbacks:
                cb.on_train_end(history)
        return state, history
