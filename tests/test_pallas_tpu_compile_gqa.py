"""Mosaic takes `ops/attention.py::gqa_cached`'s kernel at Granite's and
SmallThinker's shapes, and no Mosaic call appears where the rule says XLA
(`tests/pallas_tpu_compile.py` has the how and the why; Trinity's shapes
are in `tests/test_pallas_tpu_compile_gqa_trinity.py`); and the EVA
stage's widest chunk program, which has no kernel of ours, fits the chip
beside a second group's state.
"""

import pytest

from pallas_tpu_compile import _gqa_text, one_chip  # noqa: F401


# `granite_bulk_mixed`: its multi-chunk group (16 and 2 rows against the
# 2048-position cache) takes the kernel at head_dim 64
@pytest.mark.parametrize("rows", [16, 2])
def test_the_attention_kernel_compiles_at_granites_shape(
        one_chip, monkeypatch, rows):
    text = _gqa_text(monkeypatch, one_chip, rows, 512, 2048, None, 32, 8, 64)
    assert "tpu_custom_call" in text


# where the rule says XLA no Mosaic call appears: a single-chunk group
# (one key block) of either model
@pytest.mark.parametrize("T,Hq,d", [(512, 48, 128), (64, 48, 128),
                                    (256, 32, 64)])
def test_a_cache_of_one_key_block_stays_on_the_xla_core(
        one_chip, monkeypatch, T, Hq, d):
    text = _gqa_text(monkeypatch, one_chip, 16, T, T, None, Hq, 8, d)
    assert "tpu_custom_call" not in text


# `smallthinker_bulk_long_tail`: 28 / 4 heads, query blocks of 256: the
# short group's caches, the rings and the global cache at the widest
# batch, the ring at the narrowest too
@pytest.mark.parametrize("rows,S,window", [
    (16, 4096, 4096), (16, 4096, None), (16, 4608, 4096), (16, 16384, None),
    (2, 4608, 4096)])
def test_mosaic_takes_the_kernel_at_seven_heads_a_group(
        one_chip, monkeypatch, rows, S, window):
    text = _gqa_text(monkeypatch, one_chip, rows, 512, S, window, 28, 4, 128)
    assert "tpu_custom_call" in text


# `qwen3_next_bulk_long_tail`: 16 / 2 heads of 256 (eight query heads a
# key/value head, query blocks of 256: a tile of 2048 rows by 1024 keys
# at 256 lanes), the global cache of the long group at the widest and the
# narrowest batch and the short group's own-length caches
@pytest.mark.parametrize("rows,S", [
    (16, 16384), (2, 16384), (16, 3072), (16, 4096)])
def test_mosaic_takes_the_kernel_at_head_256_and_eight_heads_a_group(
        one_chip, monkeypatch, rows, S):
    text = _gqa_text(monkeypatch, one_chip, rows, 512, S, None, 16, 2, 256)
    assert "tpu_custom_call" in text


# `evabyte_bulk_threads_32kb`: no kernel of ours yet, but the fullest
# chip of the benchmark: the (8, 512) chunk program of the 8-layer stage
# against a group's state at 32,768 positions fits the v5e beside a
# second group's state (two are alive while the newer is enqueued)
def test_the_eva_stages_widest_program_fits_beside_a_second_groups_state(
        one_chip):
    import json
    from pathlib import Path

    import jax
    import jax.numpy as jnp

    from benchmark.reference import evabyte as ref
    from code_intelligence_tpu.models import build_encoder, make_config

    model = json.loads((Path(__file__).resolve().parents[1] / "benchmark"
                        / "configs/evabyte_6_5b_pp4_stage0.json").read_text())
    enc = build_encoder(make_config(
        "evabyte", model, kv_positions=model["serve"]["kv_positions"]))
    rows = model["serve"]["batch_size"]

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(lambda k: ref.init_params(
        k, model, model["weights"], jnp.bfloat16), jax.random.PRNGKey(0)))
    states = on_chip(jax.eval_shape(lambda: enc.init_states(rows, 32768)))
    tokens = jax.ShapeDtypeStruct((rows, 512), jnp.int32, sharding=one_chip)
    lengths = jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=one_chip)
    compiled = jax.jit(
        lambda p, t, s, n: enc.encode(p, t, s, lengths=n),
        donate_argnums=(2,)).lower(params, tokens, states, lengths).compile()
    assert "tpu_custom_call" not in compiled.as_text()
    memory = compiled.memory_analysis()
    state = rows * enc.state_bytes_per_row(32768)
    assert state == 8 * 536870912
    # weights and one group's state in, the state aliased out
    assert memory.argument_size_in_bytes - state == pytest.approx(
        2 * model["parameters"]["held"], rel=1e-3)
    assert memory.alias_size_in_bytes >= state
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes \
        + state < 0.85 * 16909336064
