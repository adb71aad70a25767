"""Quality harness: the staged pipeline runs end-to-end at micro scale,
resumes from stage markers, and emits the side-by-side report."""

import json

import pytest

from code_intelligence_tpu.quality.harness import (
    REFERENCE,
    QualityConfig,
    run_quality,
    stage_report,
)


@pytest.fixture(scope="module")
def micro_cfg(tmp_path_factory):
    wd = tmp_path_factory.mktemp("quality")
    cfg = QualityConfig.smoke(wd)
    # even smaller than smoke: unit-test scale
    cfg.n_lm_issues = 60
    cfg.n_train_issues = 40
    cfg.n_test_issues = 24
    cfg.max_vocab = 2000
    cfg.emb_sz = 8
    cfg.n_hid = 12
    cfg.n_layers = 1
    cfg.bs = 8  # divisible by the 8-device test mesh
    cfg.bptt = 16
    cfg.ft_epochs = (1,)
    cfg.ft_batch_size = 8
    cfg.ft_max_len = 48
    cfg.mlp_truncate = 16
    cfg.distill_n_hid = 8   # must not exceed the micro teacher's n_hid
    cfg.distill_steps = 10
    cfg.distill_batch_size = 8
    cfg.distill_max_len = 48
    return cfg


@pytest.fixture(scope="module")
def report(micro_cfg):
    return run_quality(micro_cfg, micro_cfg.workdir / "QUALITY.json")


@pytest.mark.slow  # the module-scoped `report` fixture runs the full
# micro quality pipeline (~70s, tier-1's single worst setup); unit
# coverage of the stages lives in test_fine_tune/test_distill/
# test_oracle — this family is the integration re-check
class TestPipeline:
    def test_report_has_all_sections(self, report):
        assert set(report) >= {"corpus", "lm", "fine_tuned_classifier",
                               "mlp_head", "bayes_ceiling"}

    def test_report_status_complete(self, report):
        assert report["status"] == "COMPLETE"
        assert "missing_stages" not in report

    def test_bayes_ceiling_present_with_margin(self, report):
        ceil = report["bayes_ceiling"]
        assert 0.5 < ceil["weighted_auc"] <= 1.0
        assert ceil["per_label_auc"]
        # margin = measured - ceiling on the SAME test slice
        assert ceil["fine_tuned_margin"] == pytest.approx(
            report["fine_tuned_classifier"]["weighted_auc"]
            - ceil["weighted_auc"], abs=1e-3)

    def test_lm_metrics_finite(self, report):
        assert report["lm"]["val_perplexity"] > 1.0
        assert report["lm"]["generator_word_ppl_floor"] > 1.0

    def test_ft_metrics_present(self, report):
        ft = report["fine_tuned_classifier"]
        assert ft["weighted_auc"] is not None
        assert 0.0 <= ft["macro_f1_at_best"] <= 1.0
        assert ft["reference_weighted_auc"] == REFERENCE["fine_tuned_weighted_auc"]

    def test_mlp_metrics_present(self, report):
        mlp = report["mlp_head"]
        assert mlp["test_weighted_auc"] is not None
        assert mlp["reference_test_weighted_auc"] == 0.760

    def test_distill_stage_present(self, report):
        # round-3 VERDICT next #4: the quality pipeline carries the
        # distillation A/B — fidelity, serving rate, downstream AUC
        d = report["distilled_student"]
        assert d["student"]["n_hid"] == 8
        assert -1.0 <= d["holdout_cosine"] <= 1.0
        ab = d["serving_ab"]
        assert ab["teacher_docs_per_sec"] > 0
        assert ab["student_docs_per_sec"] > 0
        dm = d["downstream_mlp"]
        assert dm["student_test_weighted_auc"] is not None
        # the delta vs the mlp stage's teacher AUC is computed, not null
        assert dm["auc_delta_vs_teacher"] is not None

    def test_universal_metrics_present(self, report):
        uni = report["universal_kind_model"]
        assert uni["tower"] == "gru"
        assert 0.0 <= uni["test_accuracy"] <= 1.0
        assert set(uni["derived_thresholds"]) == {"bug", "feature", "question"}
        assert uni["reference_thresholds"]["question"] == 0.60
        # thresholds are also APPLIED, not just derived
        at = uni["at_derived_thresholds"]
        assert set(at["per_class"]) == {"bug", "feature", "question"}
        assert 0.0 <= at["coverage"] <= 1.0

    def test_universal_noisy_kind_substage(self, report):
        # round-3 VERDICT weak #5: the threshold logic must face a regime
        # with real precision/recall trade-offs; softmax probs on the
        # noisy_kind preset cluster near the prior, so derived thresholds
        # cannot degenerate to ~1e-5 like on the easy corpus
        noisy = report["universal_kind_model"]["noisy_kind"]
        th = noisy["derived_thresholds"]
        assert set(th) == {"bug", "feature", "question"}
        for v in th.values():
            assert 0.01 <= v <= 0.99
        assert "at_derived_thresholds" in noisy
        assert "at_reference_thresholds" in noisy
        assert noisy["at_reference_thresholds"]["thresholds"]["question"] == 0.60
        # both truth views are reported
        assert noisy["test_vs_emitted"]["n"] == noisy["test_vs_true"]["n"]

    def test_out_file_written(self, micro_cfg, report):
        on_disk = json.loads((micro_cfg.workdir / "QUALITY.json").read_text())
        assert on_disk["corpus"]["vocab_size"] == report["corpus"]["vocab_size"]

    def test_resume_skips_done_stages(self, micro_cfg, report):
        # all stage markers exist -> a re-run does no work (fast) and
        # returns the same report
        import time

        t0 = time.time()
        again = run_quality(micro_cfg)
        assert time.time() - t0 < 5.0
        assert again["lm"]["val_perplexity"] == report["lm"]["val_perplexity"]

    def test_stage_markers_on_disk(self, micro_cfg, report):
        for s in ("gen", "lm", "ft", "mlp", "universal", "report"):
            assert (micro_cfg.workdir / f"stage_{s}.json").exists(), s

    @pytest.mark.slow  # re-runs ft+downstream stages (~40s): integration
    # semantics, not a numerical pin — tier-1 keeps the cheap marker/
    # resume checks above
    def test_force_cascades_to_downstream_stages(self, micro_cfg, report):
        # forcing ft must also re-run mlp (downstream) but not gen/lm —
        # otherwise the report silently mixes stale numbers
        def mtime(s):
            return (micro_cfg.workdir / f"stage_{s}.json").stat().st_mtime_ns

        before = {s: mtime(s) for s in ("gen", "lm", "ft", "mlp")}
        run_quality(micro_cfg, force=["ft"])
        after = {s: mtime(s) for s in ("gen", "lm", "ft", "mlp")}
        assert after["gen"] == before["gen"] and after["lm"] == before["lm"]
        assert after["ft"] > before["ft"] and after["mlp"] > before["mlp"]

    @pytest.mark.slow  # re-runs distill+universal+oracle (~35s): same
    # integration family as the cascade test above
    def test_legacy_workdir_gains_new_stage_on_resume(self, micro_cfg, report):
        # The round-3 on-chip workdir predates the distill stage: a resume
        # must run ONLY the missing stage plus its downstream cascade —
        # never re-pay the finished lm/ft/mlp stages (this is exactly what
        # the on-chip pipeline's stage 3 does to /tmp/quality_r03)
        def mtime(s):
            return (micro_cfg.workdir / f"stage_{s}.json").stat().st_mtime_ns

        (micro_cfg.workdir / "stage_distill.json").unlink()
        before = {s: mtime(s) for s in ("gen", "lm", "ft", "mlp",
                                        "universal", "oracle")}
        out = run_quality(micro_cfg)
        after = {s: mtime(s) for s in ("gen", "lm", "ft", "mlp",
                                       "universal", "oracle")}
        for s in ("gen", "lm", "ft", "mlp"):
            assert after[s] == before[s], f"{s} should not re-run"
        assert (micro_cfg.workdir / "stage_distill.json").exists()
        assert after["universal"] > before["universal"]  # cascade
        assert after["oracle"] > before["oracle"]
        assert out["distilled_student"]["serving_ab"] is not None


class TestSweepRefit:
    """sweep_refit closes the search->flagship loop (VERDICT r2 item 5)."""

    BEST = {
        "best_params": {"lr": 2e-3, "bptt": 63, "emb_sz": 800, "n_hid": 2400,
                        "n_layers": 4, "drop_mult": 0.8, "bs": 96},
        "best_metric": 5.9, "metric": "val_loss", "n_trials": 8,
        "statuses": {"done": 6, "stopped": 2, "failed": 0},
    }

    def test_refit_argv_maps_params(self, tmp_path):
        from code_intelligence_tpu.quality.sweep_refit import refit_argv

        argv = refit_argv(self.BEST["best_params"], tmp_path / "c",
                          tmp_path / "m", cycle_len=3)
        s = " ".join(argv)
        assert "--lr 0.002" in s and "--bptt 63" in s and "--n_hid 2400" in s
        assert "--bs 96" in s and "--cycle_len 3" in s and "--resume" in s
        # drop_mult scales all five reference dropout rates (train.py:68-70)
        assert "--weight_p 0.16000000000000003" in s or "--weight_p 0.16 " in s + " "
        assert "--input_p 0.2 " in s + " "  # 0.25 * 0.8, not the unscaled 0.25
        assert "--bf16" in s

    def test_refit_argv_int_casts_and_arch(self, tmp_path):
        from code_intelligence_tpu.quality.sweep_refit import refit_argv
        from code_intelligence_tpu.training.cli import build_parser

        # float-valued integer hyperparams (a yaml with float bounds samples
        # floats) must not break the training CLI's type=int argparse
        params = {"n_hid": 3321.7, "emb_sz": 800.0, "bptt": 63.9,
                  "n_layers": 4.0, "lr": 2e-3}
        argv = refit_argv(params, tmp_path / "c", tmp_path / "m", cycle_len=1,
                          arch={"qrnn": True, "qrnn_pallas": True})
        s = " ".join(argv)
        assert "--n_hid 3321" in s and "--bptt 63 " in s + " "
        assert "--qrnn " in s + " " and "--qrnn_pallas" in s
        assert "--lstm_pallas" not in s
        build_parser().parse_args(argv)  # argparse accepts the whole argv

    @pytest.mark.parametrize("arch", [
        {"lstm_pallas": True},  # a best.json from before PR 31
        {"qrnn": False, "qrnn_pallas": False, "lstm_pallas": True},
        {}, None])
    def test_refit_leaves_the_lstm_cell_to_the_train_step(self, tmp_path,
                                                           arch):
        # no flag steers it (training/loop.py::train_cell_is_resident):
        # what an older sweep recorded under "lstm_pallas" is not passed on
        from code_intelligence_tpu.quality.sweep_refit import refit_argv
        from code_intelligence_tpu.training.cli import build_parser

        argv = refit_argv({"lr": 2e-3}, tmp_path / "c", tmp_path / "m",
                          cycle_len=1, arch=arch)
        assert not any("pallas" in a for a in argv)
        build_parser().parse_args(argv)

    def test_refit_fallbacks_match_sweep_trial_not_flagship(self, tmp_path):
        # ADVICE r3 (medium): a sweep yaml that omits a model dim must refit
        # at the TRIAL's fallback (sweep/cli.py: emb_sz=400, n_hid=1152,
        # n_layers=3), not the training CLI's flagship defaults (800/2500/4)
        from code_intelligence_tpu.quality.sweep_refit import refit_argv

        argv = refit_argv({"lr": 2e-3}, tmp_path / "c", tmp_path / "m",
                          cycle_len=1)
        s = " ".join(argv)
        assert "--emb_sz 400" in s and "--n_hid 1152" in s
        assert "--n_layers 3" in s and "--bptt 67" in s
        assert "--wd 0.01" in s  # sweep-trial fallback, explicit
        assert "--lr 0.002" in s  # sampled value still wins

    def test_refit_model_dir_keyed_by_winner(self, tmp_path):
        from code_intelligence_tpu.quality.sweep_refit import refit_model_dir

        a = refit_model_dir(tmp_path, {"n_hid": 2400}, {})
        b = refit_model_dir(tmp_path, {"n_hid": 3000}, {})
        c = refit_model_dir(tmp_path, {"n_hid": 2400}, {"qrnn": True})
        assert a != b and a != c and b != c
        assert a == refit_model_dir(tmp_path, {"n_hid": 2400}, {})  # resumable

    def test_section_reports_delta_and_merges(self, tmp_path):
        from code_intelligence_tpu.quality.sweep_refit import (
            build_sweep_section, merge_into_report)

        flagship = {"val_perplexity": 462.6}
        refit = {"val_perplexity": 430.1, "val_loss": 6.064, "val_accuracy": 0.23}
        sec = build_sweep_section(self.BEST, flagship, refit,
                                  elapsed_s=12.0, platform="tpu")
        assert sec["refit"]["delta_val_perplexity"] == pytest.approx(-32.5)
        assert sec["best_params"]["n_hid"] == 2400
        report = tmp_path / "Q.json"
        report.write_text(json.dumps({"lm": flagship}))
        merged = merge_into_report(report, sec)
        assert merged["sweep"]["refit"]["val_perplexity"] == 430.1
        assert json.loads(report.read_text())["sweep"]["n_trials"] == 8

    def test_section_without_refit(self):
        from code_intelligence_tpu.quality.sweep_refit import build_sweep_section

        sec = build_sweep_section(self.BEST, {}, None)
        assert sec["refit"] is None and sec["best_trial_metric"] == 5.9
