"""LM hyperparameter sweep CLI.

The reference's sweep trains the LM with fastai-default sizing under a
W&B agent (`hyperparam_sweep/lm_tune.py:41-119`, launched one agent per
GPU by `hp_runner.sh:4-8`). Here:

    python -m code_intelligence_tpu.sweep.cli \
        --corpus_dir ./corpus --sweep_yaml sweep.yaml \
        --out_dir ./runs/sweep --trials 16

runs trials one-per-device over the LM trainer, streaming results to
``results.jsonl`` and printing the best config (the reference's best-run
record, `hyperparam_sweep/README.md:25`).
"""

from __future__ import annotations

import argparse
import json
import logging
from pathlib import Path

log = logging.getLogger(__name__)

DEFAULT_SWEEP_YAML = """
method: random
metric: {name: val_loss, goal: minimize}
parameters:
  lr:       {distribution: log_uniform_values, min: 1.0e-4, max: 1.0e-2}
  bptt:     {values: [50, 63, 67, 70]}
  emb_sz:   {values: [400, 500, 700, 800, 900]}
  n_hid:    {values: [1725, 2000, 2400, 2500, 3000]}
  n_layers: {values: [4, 5, 6]}
  drop_mult: {distribution: uniform, min: 0.5, max: 1.5}
early_terminate: {type: envelope, min_trials: 3}
"""


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--corpus_dir", required=True)
    p.add_argument("--out_dir", required=True)
    p.add_argument("--sweep_yaml", default=None, help="defaults to the reference-shaped sweep")
    p.add_argument("--trials", type=int, default=8)
    p.add_argument("--bs", type=int, default=None,
                   help="fallback batch size when the sweep yaml doesn't "
                        "sample bs (default: constants.SWEEP_TRIAL_FALLBACKS)")
    p.add_argument("--epochs", type=int, default=1)
    p.add_argument("--max_tokens", type=int, default=None,
                   help="subsample corpus (the reference swept on 20%% of data)")
    p.add_argument("--serial", action="store_true", help="one device, sequential")
    p.add_argument(
        "--gang", action="store_true",
        help="gang-scheduled trials: each trial data-parallel over ALL "
             "devices, trials sequential (full-data runs — SURVEY §2.5 DP "
             "row; per-device independent trials are the default, like the "
             "reference's 1-agent-per-GPU hp_runner.sh)",
    )
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--qrnn", action="store_true",
                   help="sweep the QRNN variant instead of the LSTM")
    p.add_argument("--qrnn_pallas", action="store_true",
                   help="Pallas forget-mult kernel (implies --qrnn)")
    p.add_argument("--wandb_project", default=None, metavar="PROJECT",
                   help="also stream each trial as a tracker run (requires "
                        "the wandb client; results.jsonl is always written)")
    p.add_argument("--wandb_mode", default=None,
                   help="wandb mode, e.g. 'offline'")
    args = p.parse_args(argv)
    logging.basicConfig(level=logging.INFO, format="%(asctime)s %(message)s")

    tracker_factory = None
    if args.wandb_project:
        from code_intelligence_tpu.training.trackers import WandbTracker

        tracker_factory = lambda: WandbTracker(  # noqa: E731 — one per trial
            args.wandb_project, mode=args.wandb_mode)
        # fail fast BEFORE any corpus load or trial runs (the training CLI
        # does the same via construction): per-trial tracker errors are
        # swallowed by design, so a missing wandb client would otherwise
        # burn the whole sweep's compute with zero tracker runs
        tracker_factory()

    import jax

    from code_intelligence_tpu.constants import (BASE_DROPOUTS,
                                                 SWEEP_TRIAL_FALLBACKS)
    from code_intelligence_tpu.data import LMStreamLoader, TokenCorpus
    from code_intelligence_tpu.models import AWDLSTMConfig
    from code_intelligence_tpu.parallel import make_mesh
    from code_intelligence_tpu.sweep import SweepConfig, SweepRunner
    from code_intelligence_tpu.training import LMTrainer, TrainConfig
    from code_intelligence_tpu.utils import devices

    devices.enable_compile_cache()

    out_dir = Path(args.out_dir)
    out_dir.mkdir(parents=True, exist_ok=True)
    sweep_cfg = SweepConfig.from_yaml(args.sweep_yaml or DEFAULT_SWEEP_YAML)

    corpus = TokenCorpus(Path(args.corpus_dir) / "train")
    valid = TokenCorpus(Path(args.corpus_dir) / "valid")
    vocab = corpus.vocab
    train_tokens = corpus.tokens(args.max_tokens)
    valid_tokens = valid.tokens(args.max_tokens)

    fb = SWEEP_TRIAL_FALLBACKS  # shared with quality/sweep_refit.py

    def train_fn(params, report, device):
        drop = float(params.get("drop_mult", fb["drop_mult"]))
        n_dp = len(jax.devices()) if args.gang else 1
        mcfg = AWDLSTMConfig(
            vocab_size=len(vocab),
            emb_sz=int(params.get("emb_sz", fb["emb_sz"])),
            n_hid=int(params.get("n_hid", fb["n_hid"])),
            n_layers=int(params.get("n_layers", fb["n_layers"])),
            pad_id=vocab.pad_id,
            # drop_mult scales the shared base rates (constants.BASE_DROPOUTS)
            # — quality/sweep_refit.py applies the same scaling at refit time
            **{k: v * drop for k, v in BASE_DROPOUTS.items()},
            qrnn=args.qrnn or args.qrnn_pallas,
            qrnn_use_pallas=args.qrnn_pallas,
        )
        bptt = int(params.get("bptt", fb["bptt"]))
        # the reference sweeps bs/wd/one_cycle too (sweep.yaml:24-33);
        # --bs is only the fallback when the sweep doesn't sample it
        bs = int(params.get("bs", args.bs if args.bs is not None else fb["bs"]))
        if n_dp > 1:
            bs = max(bs - bs % n_dp, n_dp)  # divisible by the DP mesh
        tcfg = TrainConfig(
            batch_size=bs, bptt=bptt, lr=float(params.get("lr", fb["lr"])),
            wd=float(params.get("wd", fb["wd"])),
            one_cycle=bool(params.get("one_cycle", True)),
            cycle_len=args.epochs,
        )
        # every hyperparameter as the trial actually ran it — registered on
        # the runner (trial.resolved) so the refit retrains the SAME config
        # even for params this sweep's yaml never sampled (a custom yaml
        # omitting n_hid must not refit at the training CLI's default)
        resolved = {
            "emb_sz": mcfg.emb_sz, "n_hid": mcfg.n_hid,
            "n_layers": mcfg.n_layers, "drop_mult": drop, "bptt": bptt,
            "bs": bs, "lr": tcfg.lr, "wd": tcfg.wd,
            "one_cycle": tcfg.one_cycle,
        }
        # register BEFORE fitting: an envelope-stopped trial raises out of
        # trainer.fit and never returns, but can still win best_trial()
        report.resolved = resolved
        dl = LMStreamLoader(train_tokens, bs, bptt, seed=args.seed)
        vl = LMStreamLoader(valid_tokens, bs, bptt, shuffle_offsets=False)
        mesh = (
            make_mesh({"data": n_dp}) if n_dp > 1
            else make_mesh({"data": 1}, devices=[device])
        )
        trainer = LMTrainer(mcfg, tcfg, mesh=mesh, steps_per_epoch=len(dl))

        class Reporter:
            def on_train_begin(self, tr): ...
            def on_step_end(self, step, metrics): ...
            def on_train_end(self, history): ...
            def on_epoch_end(self, epoch, metrics, state, tr):
                report({k: v for k, v in metrics.items() if isinstance(v, (int, float))})
                return None

        trainer.fit(dl, vl, epochs=args.epochs, callbacks=[Reporter()])
        return {}

    runner = SweepRunner(
        sweep_cfg,
        train_fn,
        # gang mode: one "slot" — trials run sequentially, each spanning
        # the full device mesh inside train_fn
        devices=jax.devices()[:1] if (args.serial or args.gang) else None,
        results_path=out_dir / "results.jsonl",
        seed=args.seed,
        tracker_factory=tracker_factory,
    )
    runner.run(args.trials, parallel=not (args.serial or args.gang))
    best = runner.best_trial()
    summary = {
        # run_params = sampled + runtime-resolved fallbacks; an early-stopped
        # winner may lack `resolved`, but the refit's own fallbacks mirror
        # this CLI's (quality/sweep_refit.py REFIT_FALLBACKS), so the refit
        # architecture matches either way
        "best_params": best.run_params() if best else None,
        "best_sampled_params": best.params if best else None,
        "best_metric": best.best_metric if best else None,
        "metric": sweep_cfg.metric_name,
        "n_trials": len(runner.trials),
        "statuses": {s: sum(1 for t in runner.trials if t.status == s)
                     for s in ("done", "stopped", "failed")},
        # architecture the trials actually ran — the refit
        # (quality/sweep_refit.py) must rebuild the SAME recurrence, not
        # silently fall back to the LSTM default
        "arch": {
            "qrnn": bool(args.qrnn or args.qrnn_pallas),
            "qrnn_pallas": bool(args.qrnn_pallas),
        },
    }
    (out_dir / "best.json").write_text(json.dumps(summary, indent=1))
    log.info("sweep complete: %s", summary)
    return summary


if __name__ == "__main__":
    main()
