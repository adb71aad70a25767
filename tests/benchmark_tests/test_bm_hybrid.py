"""The hybrid (Mamba-2 + attention) configuration through the benchmark,
tiny, on the CPU: a whole run of its driver against its plain reference
with documents that cross chunk programs; the check's controls (a
zeroed carry, weights rounded to int8 levels, the SSM state carried in
bfloat16) each read not correct; the new per-layer readers on known
inputs; the arithmetic of ``harness/flops_hybrid.py``."""

import json
from pathlib import Path

import numpy as np
import pytest

import bm_util
from benchmark import run
from benchmark.harness import flops_hybrid, xplane_scopes
from benchmark.harness.spans import HostSpan, SpanLog

ROOT = bm_util.ROOT
TRACE = Path(__file__).parent / "data" / "tiny.xplane.pb"

TINY_HYBRID = {
    "vocab_size": 600, "hidden_size": 64, "num_hidden_layers": 4,
    "layer_types": ["mamba", "mamba", "attention", "mamba"],
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "shared_intermediate_size": 128, "mamba_n_heads": 8, "mamba_d_head": 16,
    "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_n_groups": 1,
    "mamba_expand": 2, "mamba_chunk_size": 8, "embedding_multiplier": 12,
    "residual_multiplier": 0.22, "attention_multiplier": 0.0625,
    "rms_norm_eps": 1e-5, "logits_scaling": 8}
LIMITS = {f"rel_rms_{t}{s}": 1e-6 for t in ("mean", "max", "last")
          for s in ("", "_carried")}
LIMITS.update(nonfinite=0, nonfinite_rows=0)


def tiny_hybrid_benchmark(tmp: Path, per_layer=()) -> Path:
    """``bm_util``'s copy of the benchmark with a tiny hybrid
    configuration, its cell and a manifest that names them, as files."""
    bench = bm_util.tiny_benchmark(tmp)
    bm_util.write(bench / "configs" / "tiny_hybrid.json", dict(
        TINY_HYBRID, name="tiny_hybrid", architecture="granite_hybrid",
        dtype="float32", state_dtype="float32",
        serve={"scheduler": "groups", "batch_size": 4,
               "buckets": [16, 32], "kv_positions": 128},
        weights={"dist": "student_t", "df": 4}, reduced=[]))
    bm_util.write(bench / "cells" / "tiny_hybrid_cell.json", {
        "name": "tiny_hybrid_cell", "config": "tiny_hybrid",
        "mix": "tiny_docs", "chips": 1, "driver": "bulk_encoder",
        "reduced": [], "check": {"sample": 6, "block_rows": 3,
                                 "limits": LIMITS}})
    manifest = json.loads((tmp / "BENCHMARK.json").read_text())
    manifest["configs"] = [{"name": "tiny_hybrid", "source": "test",
                            "file": "benchmark/configs/tiny_hybrid.json",
                            "reduced": [], "why": "test"}]
    manifest["workloads"] = [{"name": "tiny_hybrid_cell",
                              "config": "tiny_hybrid", "traffic": "tiny_docs",
                              "chips": 1, "why": "test"}]
    manifest["per_layer"] = [dict(m, moves="docs_per_s") for m in per_layer]
    bm_util.write(tmp / "BENCHMARK.json", manifest)
    return bench


def main(tmp, *extra, **kw):
    return run.main(["--workload", "tiny_hybrid_cell", "--seed",
                     str(2**31 + 26), "--seconds", "0.2", *extra],
                    root=tmp, **kw)


@pytest.fixture
def gate(monkeypatch):
    monkeypatch.setattr(run, "require_device", bm_util.cpu_gate)


def numbers(line):
    return {c["name"]: c["value"] for c in line["compared"]}


def test_hybrid_cell_runs_and_agrees_with_its_reference(tmp_path, gate):
    """The mix's longest document (96 tokens) takes three chunk programs
    of 32 at a scan chunk of 8: carried SSM state, conv tail and KV cache
    are all inside the comparison, at float32 tightness."""
    per_layer = [
        {"name": "carried_state_mb_per_row", "unit": "MB", "better": "lower",
         "source": "program_counter", "layer": "bulk batching"},
        {"name": "padded_lane_pct", "unit": "%", "better": "lower",
         "source": "program_counter", "layer": "bulk batching"},
        {"name": "ssd_scan_share_pct", "unit": "%", "better": "lower",
         "source": "device_trace", "layer": "encoder forward"},
        {"name": "ssd_scan_roofline", "unit": "%", "better": "higher",
         "source": "device_trace", "layer": "kernels"},
        {"name": "hybrid_fwd_roofline", "unit": "%", "better": "higher",
         "source": "device_trace", "layer": "kernels"}]
    tiny_hybrid_benchmark(tmp_path, per_layer)
    line = main(tmp_path, "--trace", "0")
    assert line["correct"] and line["failed"] == 0, line["compared"]
    got = numbers(line)
    assert set(LIMITS) <= set(got)          # some sampled row was carried
    assert got["rel_rms_mean_carried"] < 5e-7
    assert line["counters"]["compiles_in_window"] == 0

    traced = main(tmp_path, "--trace", "1")
    assert traced["correct"]
    import jax.numpy as jnp
    from code_intelligence_tpu.models import (
        GraniteHybridEncoder, make_config)
    enc = GraniteHybridEncoder(make_config(
        "granite_hybrid", TINY_HYBRID, kv_positions=128), jnp.float32)
    # a group of more than one chunk carries the whole cache
    assert traced["metrics"]["carried_state_mb_per_row"]["value"] == \
        pytest.approx(enc.state_bytes_per_row(128) / 1e6)
    assert 0 < traced["metrics"]["padded_lane_pct"]["value"] < 100
    # no device plane in a CPU capture: the scope readers find nothing
    # and their metrics are left out, not reported as zero
    assert not {"ssd_scan_share_pct", "ssd_scan_roofline",
                "hybrid_fwd_roofline"} & set(traced["metrics"])


@pytest.mark.parametrize("control", ["int8_weights", "bf16_state",
                                     "zeroed_carry"])
def test_controls_are_not_correct(tmp_path, gate, monkeypatch, control):
    tiny_hybrid_benchmark(tmp_path)
    overrides = None
    if control == "int8_weights":
        overrides = {"precision": "int8"}
    elif control == "bf16_state":
        overrides = {"state_dtype": "bfloat16"}
    else:
        # the carry dropped between chunk programs, where it is handed
        # back: every program starts from nothing
        import jax
        import jax.numpy as jnp
        from code_intelligence_tpu.models import GraniteHybridEncoder

        real = GraniteHybridEncoder.encode

        def forgetful(self, params, tokens, states):
            out, new = real(self, params, tokens, states)
            return out, jax.tree.map(jnp.zeros_like, new)

        monkeypatch.setattr(GraniteHybridEncoder, "encode", forgetful)
    line = main(tmp_path, overrides=overrides)
    assert not line["correct"]
    bad = {c["name"] for c in line["compared"] if not c["inside"]}
    assert any(name.endswith("_carried") for name in bad)
    # float32 sound runs sit at 2e-7; a bfloat16 carry at 1e-5, int8
    # weights and a dropped carry far above
    floor = {"bf16_state": 5e-6, "int8_weights": 1e-3, "zeroed_carry": 1e-2}
    assert numbers(line)["rel_rms_mean_carried"] > floor[control]


def test_scope_reader_on_the_recorded_trace():
    """The wire-format reader against the trace recorded on the chip
    (PR 23): nine leaf ops, three of them the program's one matmul,
    whose scope path is the ``tf_op`` of its metadata."""
    ops = xplane_scopes.op_seconds(str(TRACE))
    assert len(ops) == 9
    named = [(scope, s) for scope, s in ops if scope]
    assert {scope for scope, _ in named} == {"jit(tiny_step)/dot_general:"}
    assert len(named) == 3 and all(1e-6 < s < 2e-6 for _, s in named)
    total = xplane_scopes.seconds_under(str(TRACE), [r"(^|/)dot_general"])
    assert total == pytest.approx(sum(s for _, s in named))
    assert xplane_scopes.seconds_under(str(TRACE), [r"mamba_\d+"]) == 0.0


def _reader_ctx(spans, modules, path=str(TRACE)):
    from benchmark.harness import cell as cells, readers

    ctx = readers.ReaderContext()
    ctx.config = json.loads(
        (ROOT / "benchmark/configs/granite_4_0_h_micro.json").read_text())
    ctx.spans = ctx.traced_spans = SpanLog()
    ctx.spans.spans = spans
    ctx.reduced["modules"] = modules
    ctx.result = {"xplane_path": path}
    ctx.device_kind = "TPU v5 lite"
    return ctx, cells.load_layer_reader


def test_layer_readers_on_known_inputs(capsys):
    groups = [HostSpan("engine.group", 0, 1, {
        "rows": 16, "batch": 16, "bucket": 512, "chunks": 4,
        "valid_tokens": 10866, "lane_steps": 32768,
        "state_bytes": 16 * 93214720, "kv_positions": 2048}),
        HostSpan("engine.group", 1, 2, {
            "rows": 16, "batch": 16, "bucket": 64, "chunks": 1,
            "valid_tokens": 399, "lane_steps": 1024,
            "state_bytes": 16 * 76961792, "kv_positions": 64})]
    docs = [HostSpan("engine.tokenize", 0, 0, {"n_tokens": n})
            for n in (1716, 1157, 25)]
    ctx, load = _reader_ctx(groups + docs, {"jit_fwd": [0.5, 0.25]})

    spec, read = load("carried_state_mb_per_row")
    assert read(ctx, spec) == pytest.approx(93.21472)

    # scopes of the recorded trace stand in for the scan's
    spec, read = load("ssd_scan_share_pct")
    spec = dict(spec, scopes=[r"(^|/)dot_general"])
    assert read(ctx, spec) == pytest.approx(100 * 3.644766e-06 / 0.75)
    spec, read = load("attention_share_pct")
    assert read(ctx, spec) is None          # nothing under attention_*

    spec, read = load("ssd_scan_roofline")
    value = read(ctx, dict(spec, scopes=[r"(^|/)dot_general"]))
    model = ctx.config
    moved = 36 * (33792 * flops_hybrid.scan_bytes_per_token(model)
                  + 16 * 5 * flops_hybrid.scan_state_bytes_per_row(model))
    assert value == pytest.approx(100 * (moved / 819e9) / 3.644766e-06)
    assert "memory-bound" in capsys.readouterr().out

    spec, read = load("hybrid_fwd_roofline")
    need = flops_hybrid.encoder_flops(
        model, [(10866, 512), (399, 64)], [1716, 1157, 25])
    assert read(ctx, spec) == pytest.approx(100 * (need / 197e12) / 0.75)

    # a program without the spans or scopes gives nothing, not an error
    empty, _ = _reader_ctx([], {}, path=None)
    for name in ("hybrid_fwd_roofline", "ssd_scan_roofline",
                 "ssd_scan_share_pct", "attention_share_pct",
                 "carried_state_mb_per_row"):
        spec, read = load(name)
        assert read(empty, spec) is None


def test_flops_hybrid_arithmetic():
    model = json.loads(
        (ROOT / "benchmark/configs/granite_4_0_h_micro.json").read_text())
    assert flops_hybrid.layer_counts(model) == (36, 4)
    assert flops_hybrid.mamba_matmul_params(model) == \
        2048 * 8512 + 4096 * 2048 + 2048 * 16384 + 8192 * 2048 == 76152832
    assert flops_hybrid.attention_matmul_params(model) == \
        2 * 2048 * 2048 + 2 * 2048 * 512 + 50331648 == 60817408
    assert flops_hybrid.matmul_params(model) == 2984771584
    assert flops_hybrid.weight_bytes(model) == 5969543168
    # Q = 256: 256*128 + 256*4096 + 4*4096*128; Q = 64 for a short bucket
    assert flops_hybrid.scan_flops_per_token(model, 512) == 3178496.0
    assert flops_hybrid.scan_flops_per_token(model, 64) == \
        64 * 128 + 64 * 4096 + 2097152
    assert flops_hybrid.scan_bytes_per_token(model) == \
        (4096 + 256) * 2 + 256 + 16384 == 25344
    assert flops_hybrid.scan_state_bytes_per_row(model) == 2 * 2097152
    # one document of 3 tokens: 1 + 2 + 3 query-key pairs, 4 layers
    assert flops_hybrid.attention_flops(model, [3]) == 4 * 2048 * 6 * 4
    assert flops_hybrid.encoder_flops(model, [(3, 32)], [3]) == \
        3 * (2 * 2984771584 + 36 * flops_hybrid.scan_flops_per_token(
            model, 32)) + 196608


def test_configuration_holds_the_catalog_rows_numbers():
    """Every key of the published ``config.json`` sits unchanged at the
    top level of the configuration's file; nothing is reduced."""
    body = json.loads(
        (ROOT / "benchmark/configs/granite_4_0_h_micro.json").read_text())
    assert body["reduced"] == [] and body["architecture"] == "granite_hybrid"
    assert (body["num_hidden_layers"], body["hidden_size"],
            body["vocab_size"]) == (40, 2048, 100352)
    kinds = body["layer_types"]
    assert [i for i, k in enumerate(kinds) if k == "attention"] == \
        [5, 15, 25, 35] and kinds.count("mamba") == 36
    from code_intelligence_tpu.models import build_encoder, make_config
    enc = build_encoder(make_config("granite_hybrid", body, kv_positions=body[
        "serve"]["kv_positions"]))
    assert [n for k, _, n in enc.config.runs() if k == "mamba"] == \
        [5, 9, 9, 9, 4]
    assert enc.state_bytes_per_row(2048) == 36 * (2097152 + 26112) \
        + 4 * 2048 * 2048 == 93214720
    assert enc.out_dim == 2048
