"""Checkpointing: orbax-backed train-state save/resume + encoder export.

The reference's artifact story (SURVEY.md §5 "checkpoint/resume"): fastai
``SaveModelCallback`` best-on-val (`train.py:98`), a 965 MB Learner pickle,
an encoder-only ``.pth`` for fine-tuning, re-downloaded at process start.
Here:

* full ``TrainState`` (params + opt state + step) as sharded orbax
  checkpoints — resumable mid-training (pod preemption, SURVEY.md §5);
* ``export_encoder`` mirrors the pkl→encoder split: encoder params + model
  config + vocab in one directory the inference engine loads directly.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Any, Optional

import jax
import numpy as np
import orbax.checkpoint as ocp

from code_intelligence_tpu.models import AWDLSTMConfig

ENCODER_SUBDIR = "encoder"
CONFIG_NAME = "model_config.json"


def save_checkpoint(ckpt_dir, state: Any, step: int = 0) -> None:
    path = Path(ckpt_dir).absolute()
    path.mkdir(parents=True, exist_ok=True)
    with ocp.CheckpointManager(path) as mgr:
        mgr.save(step, args=ocp.args.StandardSave(state), force=True)
        mgr.wait_until_finished()


def latest_step(ckpt_dir) -> Optional[int]:
    path = Path(ckpt_dir).absolute()
    if not path.exists():
        return None
    with ocp.CheckpointManager(path) as mgr:
        return mgr.latest_step()


def restore_checkpoint(ckpt_dir, target: Any, step: Optional[int] = None) -> Any:
    """Restore into the structure of ``target`` (an abstract or concrete
    TrainState pytree)."""
    path = Path(ckpt_dir).absolute()
    with ocp.CheckpointManager(path) as mgr:
        step = step if step is not None else mgr.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoint under {path}")
        return mgr.restore(step, args=ocp.args.StandardRestore(target))


# ---------------------------------------------------------------------------
# Encoder export (the pkl -> encoder .pth split, Issue_Embeddings/README.md:81-93)
# ---------------------------------------------------------------------------


def export_encoder(out_dir, params: Any, config: AWDLSTMConfig, vocab=None) -> Path:
    """Write encoder-only params + config (+ vocab) for the inference engine.

    Plain ``.npz`` + JSON rather than orbax: inference artifacts should be
    loadable with zero training deps (and from the C++ runtime).
    """
    from code_intelligence_tpu.utils.params_io import save_params_npz

    out = Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    enc = params["encoder"] if "encoder" in params else params
    save_params_npz(out / "encoder_params.npz", enc)
    cfg = dataclasses.asdict(config)
    # which encoder reads it back (models/contract.py::make_config)
    cfg["architecture"] = config.architecture
    for key in ("dtype", "state_dtype"):  # dtype fields, by name
        if key in cfg:
            cfg[key] = np.dtype(cfg[key]).name if cfg[key] is not None \
                else "float32"
    (out / CONFIG_NAME).write_text(json.dumps(cfg, indent=1))
    if vocab is not None:
        vocab.save(out / "vocab.json")
    return out


def load_encoder(model_dir):
    """Load ``(encoder_params, config, vocab_path_or_None)``; the export's
    ``architecture`` says which encoder's configuration it holds."""
    from code_intelligence_tpu.models import make_config
    from code_intelligence_tpu.utils.params_io import load_params_npz

    model_dir = Path(model_dir)

    cfg_raw = json.loads((model_dir / CONFIG_NAME).read_text())
    # exports older than the encoder contract hold the AWD-LSTM
    architecture = cfg_raw.pop("architecture", AWDLSTMConfig.architecture)
    if architecture == AWDLSTMConfig.architecture:
        cfg_raw.setdefault("dtype", "float32")
    config = make_config(architecture, cfg_raw)
    params = load_params_npz(model_dir / "encoder_params.npz")
    vocab_path = model_dir / "vocab.json"
    return params, config, (vocab_path if vocab_path.exists() else None)
