"""On-chip A/B for the chunked validation dispatch.

`LMTrainer.evaluate` scans ``steps_per_dispatch`` validation windows per
device program (`training/loop.py` eval_steps — commit `2bc0b75`), the
validation-side twin of the scanned train dispatch. This measures the
actual win on the flagship config: full validation pass wall-clock at
k=1 (one dispatch per bptt window) vs the product default k=20.

    python scripts/bench_eval_dispatch.py

Runs in this process and prints one JSON object; without a TPU it exits
non-zero and prints nothing.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def measure() -> dict:
    import jax
    import numpy as np

    import jax.numpy as jnp

    from code_intelligence_tpu.data import LMStreamLoader
    from code_intelligence_tpu.models import AWDLSTMConfig
    from code_intelligence_tpu.parallel import make_mesh
    from code_intelligence_tpu.training import LMTrainer, TrainConfig
    from code_intelligence_tpu.utils import devices

    device = devices.require_tpu("bench_eval_dispatch.py")
    devices.enable_compile_cache()
    BS, BPTT = 104, 67
    cfg = AWDLSTMConfig(vocab_size=60000, emb_sz=800, n_hid=2500,
                        n_layers=4, dtype=jnp.bfloat16)
    rng = np.random.RandomState(0)
    tokens = rng.randint(2, 60000, size=1_000_000).astype(np.int32)
    mesh = make_mesh({"data": len(jax.devices())})
    n_windows = len(tokens) // BS // BPTT - 1

    out = {"status": "ok", "device": device, "n_windows": n_windows,
           "bs": BS, "bptt": BPTT}
    times = {}
    for k in (1, 20):
        trainer = LMTrainer(
            cfg, TrainConfig(batch_size=BS, bptt=BPTT, steps_per_dispatch=k),
            mesh=mesh, steps_per_epoch=10)
        state = trainer.init_state(jax.random.PRNGKey(0))
        loader = LMStreamLoader(tokens, BS, BPTT, shuffle_offsets=False)
        with mesh:
            trainer.evaluate(state, loader)  # compile both program shapes
            best = float("inf")
            for _ in range(2):
                t0 = time.perf_counter()
                m = trainer.evaluate(state, loader)
                best = min(best, time.perf_counter() - t0)
        times[k] = best
        out[f"eval_k{k}_s"] = round(best, 3)
        out[f"eval_k{k}_windows_per_sec"] = round(n_windows / best, 1)
        # per-k loss: a state-carry/window-boundary bug in the scanned
        # dispatch would show up as k=20 diverging from k=1
        out[f"eval_k{k}_val_loss"] = round(float(m["val_loss"]), 4)
    out["dispatch_batching_speedup"] = round(times[1] / times[20], 3)
    out["val_loss_match"] = (
        abs(out["eval_k1_val_loss"] - out["eval_k20_val_loss"]) < 1e-3)
    return out


if __name__ == "__main__":
    print(json.dumps(measure()))
