"""Driver ``train``: the LM trainer on a seeded token stream, whole
dispatches of ``steps_per_dispatch`` windows back to back for the whole
window, through ``LMTrainer.fit`` (the host loop ``training/cli.py``
runs), one chip, the configuration's ``train`` block.

Set-up builds ONE trainer and state, replaces the program's initial
parameters with the benchmark's seeded weights, and drives the first
dispatch through ``fit`` (which compiles the scanned step): its first
three steps are what the plain reference follows after the window. The
same trainer and the state it returned go into the window. The rate is
tokens of completed dispatches over the time to the last completion.
"""

from __future__ import annotations

import math
import time

import numpy as np

from benchmark.harness import check, traffic
from benchmark.harness.cell import load_reference
from benchmark.harness.spans import SpanLog


class _Recorder:
    """``fit`` callback: every step's metrics and when it was reported
    (after the dispatch's one transfer, so a completed dispatch)."""

    def __init__(self):
        self.steps = []  # (perf_counter, loss, grad_norm, param_norm)

    def on_train_begin(self, trainer):
        pass

    def on_step_end(self, step, metrics):
        self.steps.append((time.perf_counter(), float(metrics["loss"]),
                           float(metrics["grad_norm"]),
                           float(metrics["param_norm"])))
        return None

    def on_epoch_end(self, epoch, metrics, state, trainer):
        return None

    def on_train_end(self, history):
        pass


class _Feed:
    """The loader ``fit`` iterates: windows of a pre-cut token stream,
    whole dispatches only. With ``seconds`` it stops at the first
    dispatch boundary past the deadline; ``on_boundary`` runs between
    dispatches (the profiler's start/stop and host annotation)."""

    def __init__(self, stream, k, dispatches=None, seconds=None,
                 profiler=None):
        self.x, self.y = stream["x"], stream["y"]
        self.k = k
        self.dispatches = dispatches
        self.seconds = seconds
        self.profiler = profiler
        self.local_bs = self.x.shape[1]
        self.tokens_per_epoch = int(self.x[0].size) * k * (dispatches or 1)
        self.issued = 0
        self.t0 = None

    def __len__(self):
        return self.x.shape[0]

    def epoch(self, epoch):
        n_pool = self.x.shape[0] // self.k
        self.t0 = time.perf_counter()
        while True:
            if self.dispatches is not None and self.issued >= self.dispatches:
                return
            if self.seconds is not None and self.issued and \
                    time.perf_counter() - self.t0 >= self.seconds:
                return
            base = (self.issued % n_pool) * self.k
            if self.profiler is not None:
                self.profiler.step(first_done=self.issued >= 1)
                with self.profiler.annotate("bench.dispatch"):
                    for j in range(self.k):
                        yield self.x[base + j], self.y[base + j]
                    # resumed here once fit has run the dispatch
            else:
                for j in range(self.k):
                    yield self.x[base + j], self.y[base + j]
            self.issued += 1


def build_trainer(ctx):
    import jax
    import jax.numpy as jnp

    from code_intelligence_tpu.models import AWDLSTMConfig
    from code_intelligence_tpu.parallel import make_mesh
    from code_intelligence_tpu.training import LMTrainer, TrainConfig

    model, train = ctx.config["model"], ctx.config["train"]
    mcfg = AWDLSTMConfig(
        vocab_size=model["vocab_size"], emb_sz=model["emb_sz"],
        n_hid=model["n_hid"], n_layers=model["n_layers"],
        qrnn=bool(model.get("qrnn", False)),
        tie_weights=bool(model.get("tie_weights", True)),
        dtype=jnp.dtype(model["dtype"]), **train["dropout"])
    tcfg = TrainConfig(
        batch_size=train["batch_size"], bptt=train["bptt"], lr=train["lr"],
        one_cycle=train["one_cycle"],
        steps_per_dispatch=train["steps_per_dispatch"])
    mesh = make_mesh({"data": 1}, devices=jax.devices()[:1])
    return LMTrainer(mcfg, tcfg, mesh=mesh,
                     steps_per_epoch=train["steps_per_epoch"])


def seeded_lm_params(ctx, ref, key):
    """The benchmark's weights in the LM's layout (encoder + decoder
    bias; the decoder is tied to the embedding)."""
    import jax
    import jax.numpy as jnp

    model = ctx.config["model"]

    def make(k):
        return {"encoder": ref.init_params(k, model,
                                           ctx.config.get("weights")),
                "decoder_b": jnp.zeros((model["vocab_size"],), jnp.float32)}

    return jax.jit(make)(key)


def run(ctx) -> dict:
    import jax

    from benchmark.reference import common
    from code_intelligence_tpu.text import SPECIALS
    from code_intelligence_tpu.utils import tracing

    model, train, mix = ctx.config["model"], ctx.config["train"], ctx.mix
    ref = load_reference(ctx.config["architecture"], ctx.bench_dir)
    k = int(train["steps_per_dispatch"])
    rows, bptt = int(train["batch_size"]), int(train["bptt"])
    key = common.seed_key(ctx.seed)
    # host copies: the trainer donates its state, and the state holds
    # (an alias of) the key it was given
    w_key, run_key = np.asarray(jax.random.split(key))

    trainer = (ctx.overrides.get("build_trainer") or build_trainer)(ctx)
    state = trainer.init_state(run_key)
    weights = seeded_lm_params(ctx, ref, w_key)
    have = jax.tree.map(lambda a: (a.shape, str(a.dtype)), state.params)
    want = jax.tree.map(lambda a: (a.shape, str(a.dtype)), weights)
    if have != want:
        raise RuntimeError("the trainer's parameter tree is not the "
                           f"benchmark's: {have} against {want}")
    state = state.replace(params=jax.tree.map(
        lambda new, old: jax.device_put(new, old.sharding),
        weights, state.params))
    del weights

    pool_dispatches = int(mix.get("dispatch_pool", 12))

    def stream(windows, which):
        return traffic.make_token_stream(
            mix, model["vocab_size"], len(SPECIALS), SPECIALS.index("xxbos"),
            ctx.seed, rows, bptt, windows, stream=which)

    first, pool = stream(k, 1), stream(k * pool_dispatches, 2)

    # first dispatch: compiles the scanned step and takes the first
    # steps from the seed, through the window's own call and feed
    rec0 = _Recorder()
    state, _ = trainer.fit(_Feed(first, k, dispatches=1), None, epochs=1,
                           callbacks=[rec0], state=state)

    span_log = SpanLog()
    if ctx.trace:
        tracing.get_tracer().on_trace(span_log.ingest)
    watch = ctx.compile_counter()
    rec = _Recorder()
    feed = _Feed(pool, k, seconds=ctx.seconds, profiler=ctx.profiler)
    ctx.window_opens()
    t0 = time.perf_counter()
    state, _ = trainer.fit(feed, None, epochs=1, callbacks=[rec],
                           state=state)
    ctx.profiler.stop()
    window_s = rec.steps[-1][0] - t0
    n_steps = len(rec.steps)
    tokens = n_steps * rows * bptt
    compiles = watch.new()
    peak = ctx.memory_peak_bytes()

    losses = [s[1] for s in rec0.steps + rec.steps]
    numbers = {
        "nonfinite_losses": float(sum(not math.isfinite(x) for x in losses)),
        "loss_rise": max(0.0, float(np.mean(losses[-k:]))
                         - float(np.mean(losses[:k]))),
    }
    # -- the reference follows the first three steps, once the program's
    # state is freed
    del state
    ctx.release(trainer)
    lm = load_reference("lm_train", ctx.bench_dir)
    n_ref = int(ctx.cell["check"].get("steps", 3))
    weights = seeded_lm_params(ctx, ref, w_key)
    t_ref = time.perf_counter()
    followed = lm.follow(ref, model, train, weights, first["x"], first["y"],
                         run_key, steps=n_ref,
                         lower=ctx.overrides.get("reference_lower"))
    del weights
    ctx.log(f"reference followed {n_ref} steps in "
            f"{time.perf_counter() - t_ref:.1f} s")
    got = {"loss": [s[1] for s in rec0.steps[:n_ref]],
           "grad_norm": [s[2] for s in rec0.steps[:n_ref]],
           "param_norm": [s[3] for s in rec0.steps[:n_ref]]}
    if ctx.overrides.get("reference_lower"):
        # control: the lower-precision reference stands in the program's
        # place, against the float32 reference
        got = followed
        followed = lm.follow(ref, model, train,
                             seeded_lm_params(ctx, ref, w_key), first["x"],
                             first["y"], run_key, steps=n_ref)
    numbers.update(train_numbers(got, followed))
    ctx.log(f"program {got}")
    ctx.log(f"reference {followed}")
    verdict = check.judge(numbers, ctx.cell["check"]["limits"])
    ctx.log("compared: %s" % verdict["compared"])
    return {
        "correct": verdict["correct"], "compared": verdict["compared"],
        "attempted": n_steps // k, "failed": int(numbers["nonfinite_losses"]),
        "end_to_end": {"train_tokens_per_s": tokens / window_s},
        "window_s": window_s, "memory_peak_bytes": peak,
        "counters": {"compiles_in_window": compiles, "steps": n_steps,
                     "dispatches": n_steps // k, "tokens": tokens,
                     "first_loss": losses[0], "last_loss": losses[-1]},
        "spans": span_log,
    }


def train_numbers(got: dict, want: dict) -> dict:
    """Each step's loss and gradient norm against the reference's, as
    relative gaps, and the growth of the parameters' norm from the
    first step to the last one followed."""
    out = {}
    for i, (g, w) in enumerate(zip(got["loss"], want["loss"]), 1):
        out[f"loss_gap_{i}"] = abs(g - w) / abs(w)
    for i, (g, w) in enumerate(zip(got["grad_norm"], want["grad_norm"]), 1):
        out[f"grad_norm_gap_{i}"] = abs(g - w) / abs(w)
    grow_g = got["param_norm"][-1] - got["param_norm"][0]
    grow_w = want["param_norm"][-1] - want["param_norm"][0]
    out["param_growth_gap"] = abs(grow_g - grow_w) / abs(grow_w)
    return out
