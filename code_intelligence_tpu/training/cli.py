"""LM training CLI.

The reference's entry point is a fire CLI over ``LangModel``
(`Issue_Embeddings/train.py:119-120`, invoked as
``python train.py --bs 104 --bptt 67 --cycle_len 1`` from `run_train.sh:3`).
Same flags here, plus corpus/mesh/checkpoint arguments:

    python -m code_intelligence_tpu.training.cli \
        --corpus_dir ./corpus --model_dir ./runs/lm \
        --bs 104 --bptt 67 --emb_sz 800 --n_hid 2500 --n_layers 4 \
        --lr 3e-3 --cycle_len 1 --one_cycle

Artifacts written: orbax checkpoints (best-on-val), ``history.csv``
(CSVLogger), ``metrics.jsonl`` (step stream), and an exported encoder
directory for the inference engine.
"""

from __future__ import annotations

import argparse
import json
import logging
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from code_intelligence_tpu.constants import BASE_DROPOUTS


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--corpus_dir", required=True, help="dir with train/ and valid/ corpora")
    p.add_argument("--model_dir", required=True, help="output dir for checkpoints/logs")
    # Reference hyperparameters (train.py:42-46,68-73).
    p.add_argument("--bs", type=int, default=104)
    p.add_argument("--bptt", type=int, default=67)
    p.add_argument("--emb_sz", type=int, default=800)
    p.add_argument("--n_hid", type=int, default=2500)
    p.add_argument("--n_layers", type=int, default=4)
    p.add_argument("--lr", type=float, default=1.3e-3)  # best-run lr (sweep README:25)
    p.add_argument("--cycle_len", type=int, default=1)
    p.add_argument("--one_cycle", action="store_true", default=True)
    p.add_argument("--no_one_cycle", dest="one_cycle", action="store_false")
    p.add_argument("--qrnn", action="store_true")
    p.add_argument("--qrnn_pallas", action="store_true",
                   help="Pallas forget-mult kernel for the QRNN recurrence")
    p.add_argument("--seq_parallel", type=int, default=1, metavar="N",
                   help="shard the QRNN recurrence's TIME axis over N "
                        "devices (context parallelism; requires --qrnn and "
                        "bptt %% N == 0)")
    p.add_argument("--output_p", type=float, default=BASE_DROPOUTS["output_p"])
    p.add_argument("--hidden_p", type=float, default=BASE_DROPOUTS["hidden_p"])
    p.add_argument("--input_p", type=float, default=BASE_DROPOUTS["input_p"])
    p.add_argument("--embed_p", type=float, default=BASE_DROPOUTS["embed_p"])
    p.add_argument("--weight_p", type=float, default=BASE_DROPOUTS["weight_p"])
    p.add_argument("--wd", type=float, default=0.01)
    p.add_argument("--grad_clip", type=float, default=None)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--bf16", action="store_true", help="bfloat16 compute (TPU)")
    p.add_argument("--max_tokens", type=int, default=None, help="truncate corpus (smoke runs)")
    p.add_argument("--early_stop_patience", type=int, default=2)
    p.add_argument("--steps_per_dispatch", type=int, default=20, metavar="K",
                   help="train K bptt windows per device dispatch "
                        "(lax.scan inside one jit) — amortizes per-dispatch "
                        "host latency; semantics identical to K=1 (the "
                        "classic loop)")
    p.add_argument("--data_parallel", type=int, default=None, help="mesh data axis (default: all devices)")
    p.add_argument("--model_parallel", type=int, default=1, help="mesh model axis (TP)")
    p.add_argument("--resume", action="store_true", help="resume from latest checkpoint")
    p.add_argument("--wandb_project", default=None, metavar="PROJECT",
                   help="also stream metrics to a W&B project (requires the "
                        "wandb client; metrics.jsonl is always written)")
    p.add_argument("--wandb_mode", default=None,
                   help="wandb mode, e.g. 'offline' (air-gapped runs)")
    p.add_argument("--flight_ring", type=int, default=4096, metavar="N",
                   help="flight-recorder ring capacity: every train/eval "
                        "step appends one fixed-size telemetry record "
                        "(step, loss, grad/param norm, lr, tokens/sec, "
                        "step time, compile flag); dumped as JSONL next "
                        "to the checkpoint on halt or crash. 0 disables")
    p.add_argument("--halt_on_divergence",
                   action=argparse.BooleanOptionalAction, default=True,
                   help="halt-and-checkpoint within one step when a "
                        "divergence sentinel trips (NaN/inf loss, "
                        "grad-norm spike); --no-halt_on_divergence "
                        "records trips but keeps training")
    p.add_argument("--metrics_port", type=int, default=None, metavar="PORT",
                   help="serve /metrics (flight gauges + XLA compile "
                        "accounting), /debug/flight, and /debug/traces "
                        "on this port for the duration of the run")
    return p


def main(argv=None) -> dict:
    args = build_parser().parse_args(argv)
    logging.basicConfig(level=logging.INFO, format="%(asctime)s %(message)s")
    log = logging.getLogger("train")

    if args.qrnn_pallas:
        args.qrnn = True  # kernel flag implies the QRNN variant (as in sweep)
    sp = args.seq_parallel
    if sp > 1:
        if not args.qrnn:
            raise SystemExit("--seq_parallel requires --qrnn (the LSTM "
                             "recurrence is non-linear in h and cannot "
                             "shard time; see parallel/seq_parallel.py)")
        if args.bptt % sp != 0:
            raise SystemExit(f"--seq_parallel {sp} must divide --bptt "
                             f"{args.bptt} (shard_map blocks the time axis "
                             "evenly)")
        if args.qrnn_pallas:
            raise SystemExit(
                "--qrnn_pallas cannot combine with --seq_parallel: the "
                "time-sharded recurrence is its own associative-scan "
                "implementation (parallel/seq_parallel.py) and would "
                "silently ignore the Pallas kernel flag")

    from code_intelligence_tpu.data import LMStreamLoader, TokenCorpus
    from code_intelligence_tpu.models import AWDLSTMConfig
    from code_intelligence_tpu.parallel import make_mesh
    from code_intelligence_tpu.training import (
        CSVLogger,
        EarlyStopping,
        JSONLLogger,
        LMTrainer,
        ReduceLROnPlateau,
        SaveBest,
        TrainConfig,
    )
    from code_intelligence_tpu.training import checkpoint as ckpt

    from code_intelligence_tpu.utils import devices

    log.info("compile cache: %s", devices.enable_compile_cache())
    log.info("devices: %s", devices.describe())

    corpus_dir = Path(args.corpus_dir)
    train_corpus = TokenCorpus(corpus_dir / "train")
    valid_corpus = TokenCorpus(corpus_dir / "valid")
    vocab = train_corpus.vocab
    log.info("corpus: %d train tokens, %d valid tokens, vocab %d",
             train_corpus.total_tokens, valid_corpus.total_tokens, len(vocab))

    # stream() keeps the corpus mmap'd on disk; only bounded smoke runs
    # (--max_tokens) materialize a prefix.
    train_tokens = (
        train_corpus.stream() if args.max_tokens is None else train_corpus.tokens(args.max_tokens)
    )
    valid_tokens = (
        valid_corpus.stream() if args.max_tokens is None else valid_corpus.tokens(args.max_tokens)
    )
    train_loader = LMStreamLoader(train_tokens, args.bs, args.bptt, seed=args.seed)
    valid_loader = LMStreamLoader(valid_tokens, args.bs, args.bptt, shuffle_offsets=False)

    n_dev = len(jax.devices())
    dp = args.data_parallel or (n_dev // (args.model_parallel * sp))
    if dp < 1 or dp * args.model_parallel * sp > n_dev:
        raise SystemExit(
            f"mesh data={dp} x model={args.model_parallel} x seq={sp} "
            f"needs {max(dp, 1) * args.model_parallel * sp} devices, "
            f"have {n_dev}")
    devices = jax.devices()[: dp * args.model_parallel * sp]  # allow device subsets
    axes = {"data": dp}
    if args.model_parallel > 1:
        axes["model"] = args.model_parallel
    if sp > 1:
        axes["seq"] = sp
    mesh = make_mesh(axes, devices=devices)

    mcfg = AWDLSTMConfig(
        vocab_size=len(vocab),
        emb_sz=args.emb_sz,
        n_hid=args.n_hid,
        n_layers=args.n_layers,
        pad_id=vocab.pad_id,
        output_p=args.output_p,
        hidden_p=args.hidden_p,
        input_p=args.input_p,
        embed_p=args.embed_p,
        weight_p=args.weight_p,
        qrnn=args.qrnn,
        qrnn_use_pallas=args.qrnn_pallas,
        seq_axis="seq" if sp > 1 else None,
        dtype=jnp.bfloat16 if args.bf16 else jnp.float32,
    )
    tcfg = TrainConfig(
        batch_size=args.bs,
        bptt=args.bptt,
        lr=args.lr,
        one_cycle=args.one_cycle,
        cycle_len=args.cycle_len,
        wd=args.wd,
        grad_clip=args.grad_clip,
        steps_per_dispatch=args.steps_per_dispatch,
    )
    trainer = LMTrainer(mcfg, tcfg, mesh=mesh, steps_per_epoch=len(train_loader))

    model_dir = Path(args.model_dir)
    model_dir.mkdir(parents=True, exist_ok=True)
    (model_dir / "train_args.json").write_text(json.dumps(vars(args), default=str, indent=1))

    state = trainer.init_state(jax.random.PRNGKey(args.seed))
    if args.resume and ckpt.latest_step(model_dir / "ckpt") is not None:
        state = ckpt.restore_checkpoint(model_dir / "ckpt", state)
        log.info("resumed from step %d", int(state.step))

    callbacks = [
        EarlyStopping(patience=args.early_stop_patience),
        ReduceLROnPlateau(patience=1),
        SaveBest(model_dir / "ckpt"),
        CSVLogger(model_dir / "history.csv"),
        JSONLLogger(model_dir / "metrics.jsonl"),
    ]
    tracker = None
    if args.wandb_project:
        # alongside, never instead of, the JSONL stream (the reference
        # streams the same run to W&B, train.py:75-81,115-116)
        from code_intelligence_tpu.training.trackers import (TrackerCallback,
                                                             WandbTracker)

        tracker = WandbTracker(args.wandb_project, mode=args.wandb_mode)
        callbacks.append(TrackerCallback(
            tracker, run_name=model_dir.name, config=vars(args)))
    if args.flight_ring > 0 or args.metrics_port is not None:
        from code_intelligence_tpu.utils import flight_recorder, metrics

        registry = metrics.Registry()
        flight_recorder.get_accountant().bind_registry(registry)
        recorder = None
        if args.flight_ring > 0:
            from code_intelligence_tpu.training.telemetry import (
                FlightRecorderCallback)

            recorder = flight_recorder.FlightRecorder(
                capacity=args.flight_ring, registry=registry)
            callbacks.insert(0, FlightRecorderCallback(
                recorder, ckpt_dir=model_dir / "ckpt",
                halt_on_divergence=args.halt_on_divergence, tracker=tracker))
        if args.metrics_port is not None:
            from code_intelligence_tpu.utils import tracing

            tracer = tracing.get_tracer()
            tracer.bind_registry(registry)  # trace_span_seconds roll-up too
            metrics.start_metrics_server(
                registry, args.metrics_port, tracer=tracer, flight=recorder)
    state, history = trainer.fit(
        train_loader, valid_loader, epochs=args.cycle_len, callbacks=callbacks, state=state
    )

    enc_dir = ckpt.export_encoder(model_dir / "encoder_export", state.params, mcfg, vocab)
    log.info("exported encoder to %s", enc_dir)
    summary = history[-1] if history else {}
    log.info("done: %s", summary)
    return summary


if __name__ == "__main__":
    main()
