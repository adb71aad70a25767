"""chip_smoke.py's legs, at a tiny size on the CPU backend.

The script itself only runs on the chip (tests/test_bench_harness.py pins
that it refuses anything else); this keeps its legs from rotting between
chip runs. Pallas kernels run in interpret mode here, so nothing checks
for the Mosaic custom call.
"""

import jax

import bench
import chip_smoke


def test_every_leg_runs_at_tiny_widths(tmp_path):
    w = chip_smoke.Widths(vocab=3000, emb=16, hid=24, layers=3, bs=8,
                          bptt=10, steps_per_dispatch=3, serve_batch=8,
                          n_docs=10)
    legs = ("train_scan", "serve_slots", "kernels", "train_pallas",
            "serve_ragged", "serve_int8", "multichip")
    results = chip_smoke.run(w, legs=legs, expect_mosaic=False,
                             work=tmp_path)
    assert results["train_scan"]["compiles"] == {"train.steps": 1,
                                                 "eval.steps": 1}
    assert len(results["kernels"]) == 11  # every pallas_call in the repo
    # the conftest's 8 virtual devices: the multi-device leg ran on all
    n = len(jax.devices())
    assert results["multichip"]["devices_used"] == n >= 4
    for layout in (f"dp{n}", f"dp{n // 2}_mp2"):
        assert len(results["multichip"][layout]
                   ["live_bytes_per_device"]) == n


def test_flagship_widths_are_the_ones_the_repo_supports():
    w = chip_smoke.FLAGSHIP
    assert {"vocab_size": w.vocab, "emb_sz": w.emb, "n_hid": w.hid,
            "n_layers": w.layers} == bench._BENCH_MODEL
    assert (w.bs, w.bptt, w.serve_batch) == (104, 67, 32)
