"""Close the sweep -> flagship loop (round-3 VERDICT item 5).

The reference swept 538 trials on 20% of the data and then retrained the
flagship with the winning hyperparameters (`hyperparam_sweep/README.md:25,32`
-- the "best run" record IS the flagship config in `train.py:42-46`). The
sweep CLI (`sweep/cli.py`) reproduces the search; this module reproduces the
*refit*: take `best.json` from a sweep output dir, retrain the LM on the FULL
quality corpus with those hyperparameters, and record the val-perplexity
delta against the flagship run inside the quality report, so the sweep's
effect on the headline LM number is a measured fact rather than a claim.

    python -m code_intelligence_tpu.quality.sweep_refit \
        --sweep_dir /tmp/sweep_r03 --workdir /tmp/quality_r03 \
        --report QUALITY_r03.json --cycle_len 3 --bf16
"""

from __future__ import annotations

import argparse
import hashlib
import json
import logging
import math
import time
from pathlib import Path
from typing import Optional

from code_intelligence_tpu.constants import (BASE_DROPOUTS,
                                             SWEEP_TRIAL_FALLBACKS)

log = logging.getLogger(__name__)


_INT_PARAMS = ("bptt", "emb_sz", "n_hid", "n_layers")

# The refit must fall back to what a sweep TRIAL used — not the training
# CLI's flagship defaults (emb_sz=800/n_hid=2500/n_layers=4) — or a custom
# sweep yaml that omits a model dim would silently refit a different
# architecture than the winning trial. Shared constant so cli.py and the
# refit can never diverge. best.json's `best_params` carries the
# trial-resolved values anyway; this only fires for pre-`resolved` or
# hand-edited sweep outputs.
REFIT_FALLBACKS = SWEEP_TRIAL_FALLBACKS


def refit_model_dir(workdir: Path, best_params: dict, arch: dict) -> Path:
    """Per-winner checkpoint dir.

    ``--resume`` into a FIXED dir would orbax-crash (or silently resume a
    stale run) when a later sweep's winner has different model dimensions
    than the checkpoint an earlier refit left behind — so key the dir by the
    hyperparameters + architecture. Re-running the SAME winner still resumes
    (a refit can be killed mid-run); a different winner gets a fresh dir.
    """
    sig = json.dumps({"p": best_params, "a": arch}, sort_keys=True)
    digest = hashlib.sha256(sig.encode()).hexdigest()[:12]
    return workdir / f"sweep_refit_{digest}"


def refit_argv(best_params: dict, corpus_dir: Path, model_dir: Path,
               cycle_len: int, bs_default: Optional[int] = None, seed: int = 0,
               bf16: bool = True, arch: Optional[dict] = None) -> list:
    """Training-CLI argv for a full-scale refit of the sweep's best trial."""
    argv = [
        "--corpus_dir", str(corpus_dir),
        "--model_dir", str(model_dir),
        "--cycle_len", str(cycle_len),
        "--seed", str(seed),
        "--resume",  # a killed refit resumes, like stage_lm does
    ]
    for key in ("lr", "wd"):
        argv += [f"--{key}", str(best_params.get(key, REFIT_FALLBACKS[key]))]
    for key in _INT_PARAMS:
        # a sweep yaml with float bounds samples floats for integer params;
        # the trial tolerated them via int() (sweep/cli.py) — mirror that
        argv += [f"--{key}",
                 str(int(best_params.get(key, REFIT_FALLBACKS[key])))]
    # bs is registered into best_params pre-fit (sweep/cli.py report.resolved)
    # so this fallback only fires for pre-`resolved` best.json files; it must
    # match the sweep CLI's own --bs default, or pass --bs explicitly with
    # the value the sweep ran with
    if bs_default is None:
        bs_default = REFIT_FALLBACKS["bs"]
    argv += ["--bs", str(int(best_params.get("bs", bs_default)))]
    drop = float(best_params.get("drop_mult", REFIT_FALLBACKS["drop_mult"]))
    for flag, base in BASE_DROPOUTS.items():
        argv += [f"--{flag}", str(base * drop)]
    if not bool(best_params.get("one_cycle", True)):
        argv.append("--no_one_cycle")
    # the LSTM's cell is the train step's own choice (training/loop.py),
    # so an older best.json's "lstm_pallas" is not passed on
    for flag in ("qrnn", "qrnn_pallas"):
        if (arch or {}).get(flag):
            argv.append(f"--{flag}")
    if bf16:
        argv.append("--bf16")
    return argv


def build_sweep_section(best: dict, flagship_lm: dict,
                        refit_summary: Optional[dict],
                        elapsed_s: Optional[float] = None,
                        platform: Optional[str] = None) -> dict:
    """The ``sweep`` block merged into the quality report.

    ``best`` is the sweep CLI's best.json; ``flagship_lm`` the report's lm
    section; ``refit_summary`` the training CLI's summary for the full-scale
    retrain with the best params (None => search ran but refit didn't).
    """
    section = {
        "n_trials": best.get("n_trials"),
        "trial_statuses": best.get("statuses"),
        "metric": best.get("metric"),
        "best_params": best.get("best_params"),
        "best_trial_metric": best.get("best_metric"),
        "arch": best.get("arch"),
        "refit": None,
        "note": (
            "search on a corpus subsample (the reference swept on 20% data, "
            "hyperparam_sweep/README.md:32); refit = full-corpus retrain "
            "with the winning hyperparameters"
        ),
    }
    if refit_summary is not None:
        refit_ppl = refit_summary.get("val_perplexity")
        if refit_ppl is None and refit_summary.get("val_loss") is not None:
            refit_ppl = math.exp(refit_summary["val_loss"])
        flag_ppl = flagship_lm.get("val_perplexity")
        section["refit"] = {
            "val_perplexity": refit_ppl,
            "val_loss": refit_summary.get("val_loss"),
            "val_accuracy": refit_summary.get("val_accuracy"),
            "flagship_val_perplexity": flag_ppl,
            "delta_val_perplexity": (
                round(refit_ppl - flag_ppl, 4)
                if refit_ppl is not None and flag_ppl is not None else None
            ),
            "_elapsed_s": elapsed_s,
            "_platform": platform,
        }
    return section


def merge_into_report(report_path: Path, section: dict) -> dict:
    from code_intelligence_tpu.quality.harness import _atomic_write_json

    report = json.loads(report_path.read_text())
    report["sweep"] = section
    # tmp+rename: a stage timeout SIGKILLs whole stage process groups;
    # an in-place write here could truncate the accumulated report
    _atomic_write_json(report_path, report)
    return report


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--sweep_dir", required=True,
                   help="sweep CLI output dir (contains best.json)")
    p.add_argument("--workdir", required=True,
                   help="quality-harness workdir (corpus lives under corpus/)")
    p.add_argument("--report", required=True, help="QUALITY_r0N.json to update")
    p.add_argument("--cycle_len", type=int, default=3,
                   help="epochs for the refit (match the flagship run)")
    p.add_argument("--bs", type=int, default=None,
                   help="fallback batch size for pre-`resolved` best.json "
                        "files (default: the sweep CLI's own --bs default)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--bf16", action="store_true", default=True)
    p.add_argument("--no_bf16", dest="bf16", action="store_false",
                   help="refit in f32 (bf16 is the TPU default)")
    p.add_argument("--no_refit", action="store_true",
                   help="merge the search result only (no full retrain)")
    args = p.parse_args(argv)
    logging.basicConfig(level=logging.INFO, format="%(asctime)s %(message)s")

    best = json.loads((Path(args.sweep_dir) / "best.json").read_text())
    report_path = Path(args.report)
    flagship_lm = json.loads(report_path.read_text()).get("lm", {})

    refit_summary, elapsed, platform = None, None, None
    if not args.no_refit and best.get("best_params"):
        from code_intelligence_tpu.quality.harness import _platform
        from code_intelligence_tpu.training import cli as train_cli

        workdir = Path(args.workdir)
        if best.get("arch") is None:
            log.warning(
                "best.json has no 'arch' record (pre-arch sweep output?) — "
                "refitting with the LSTM default; if the sweep ran --qrnn or "
                "a Pallas kernel, re-run it or hand-edit best.json['arch']")
        arch = best.get("arch") or {}
        model_dir = refit_model_dir(workdir, best["best_params"], arch)
        t0 = time.time()
        refit_summary = train_cli.main(refit_argv(
            best["best_params"], workdir / "corpus", model_dir,
            cycle_len=args.cycle_len, bs_default=args.bs, seed=args.seed,
            bf16=args.bf16, arch=arch,
        ))
        elapsed, platform = round(time.time() - t0, 1), _platform()

    section = build_sweep_section(best, flagship_lm, refit_summary,
                                  elapsed_s=elapsed, platform=platform)
    merge_into_report(report_path, section)
    print(json.dumps({"sweep": section}, default=str)[:2000])
    return section


if __name__ == "__main__":
    main()
