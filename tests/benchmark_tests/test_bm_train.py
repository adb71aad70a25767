"""The train driver, tiny, on the CPU: the plain reference follows the
program's first three steps to float32 rounding, dropout masks and
AdamW included; the lower-precision control and a step that returns its
state unchanged both come out as not correct."""

import pytest

import bm_util
from benchmark import run

LIMITS = {"loss_gap_1": 1e-4, "loss_gap_2": 1e-4, "loss_gap_3": 1e-4,
          "grad_norm_gap_1": 1e-3, "grad_norm_gap_2": 1e-3,
          "grad_norm_gap_3": 1e-3, "param_growth_gap": 0.05,
          "nonfinite_losses": 0, "loss_rise": 0}


def main(tmp, **kw):
    return run.main(["--workload", "tiny_cell", "--seed", str(2**31 + 11),
                     "--seconds", "0.3", "--trace", "0"], root=tmp, **kw)


@pytest.fixture
def bench(tmp_path, monkeypatch):
    monkeypatch.setattr(run, "require_device", bm_util.cpu_gate)
    bm_util.tiny_benchmark(tmp_path, driver="train", limits=LIMITS)
    return tmp_path


def test_reference_follows_the_first_three_steps(bench):
    line = main(bench)
    assert line["correct"], line["compared"]
    worst = {c["name"]: c["value"] for c in line["compared"]}
    assert max(worst[f"loss_gap_{i}"] for i in (1, 2, 3)) < 1e-5
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert line["counters"]["steps"] % 3 == 0  # whole dispatches only
    assert line["counters"]["last_loss"] < line["counters"]["first_loss"]
    assert line["counters"]["compiles_in_window"] == 0


def test_control_lower_precision_is_not_correct(bench):
    line = main(bench, overrides={"reference_lower": "int8"})
    assert not line["correct"]


def test_step_that_returns_its_state_unchanged_is_not_correct(bench):
    """The timed path broken underneath: the compiled step computes its
    loss and gradients and hands back the parameters it was given."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bm_train_driver", bench / "benchmark" / "drivers" / "train.py")
    driver = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(driver)

    def build(ctx):
        trainer = driver.build_trainer(ctx)
        real = trainer._train_step_body

        def body():
            step = real()

            def unchanged(state, x, y):
                new, metrics = step(state, x, y)
                return new.replace(params=state.params), metrics

            return unchanged

        trainer._train_step_body = body
        return trainer

    line = main(bench, overrides={"build_trainer": build})
    assert not line["correct"]
    bad = {c["name"] for c in line["compared"] if not c["inside"]}
    assert bad & {"loss_gap_2", "loss_gap_3", "param_growth_gap",
                  "loss_rise"}
