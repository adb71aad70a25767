"""Mosaic takes `ops/eva.py::eva_cached`'s kernel at the shapes of
`evabyte_bulk_threads_32kb` (32 heads of 128, chunk programs of 512
against the 2,048-slot block cache, summaries allocated for 32,768 and
8,192 positions, the group's rows narrowed 8, 2, 1), and no Mosaic call
appears where the rule says XLA (`tests/pallas_tpu_compile.py` has the how
and the why); and the stage's widest chunk program, its eight cores on the
kernel, fits the chip beside a second group's state.
"""

import jax
import jax.numpy as jnp
import pytest

from pallas_tpu_compile import one_chip  # noqa: F401

H, D, T, W = 32, 128, 512, 2048


def _eva_text(monkeypatch, one_chip, rows, S, dtype=jnp.bfloat16, slots=W):
    """The compiled text of one ``eva_cached`` call as the encoder makes
    it (float32 queries and keys from the rotary, the caches in
    ``state_dtype``); the rule asks the backend, so the test answers for
    it."""
    from code_intelligence_tpu.ops.eva import eva_cached

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def core(q, k, v, k_block, v_block, k_sum, v_sum, pos):
        return eva_cached(q, k, v, k_block, v_block, k_sum, v_sum, pos,
                          D ** -0.5, 2048, 16, mxu_dtype=dtype)

    def on_chip(shape, of):
        return jax.ShapeDtypeStruct(shape, of, sharding=one_chip)

    args = [on_chip((rows, T, H, D), jnp.float32)] * 3 \
        + [on_chip((rows, H, slots, D), dtype)] * 2 \
        + [on_chip((rows, H, S, D), dtype)] * 2
    return jax.jit(core).lower(*args, on_chip((), jnp.int32)) \
        .compile().as_text()


@pytest.mark.parametrize("rows", [8, 2, 1])
@pytest.mark.parametrize("S", [2048, 512])
def test_mosaic_takes_the_joint_core_at_the_cells_shapes(
        one_chip, monkeypatch, rows, S):
    text = _eva_text(monkeypatch, one_chip, rows, S)
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "eva_core" in text
    # the running softmax is the kernel's: no loop over key blocks left
    assert " while(" not in text


def test_float32_operands_stay_on_the_xla_core(one_chip, monkeypatch):
    text = _eva_text(monkeypatch, one_chip, 8, 2048, dtype=jnp.float32)
    assert "tpu_custom_call" not in text and "eva_core" not in text


def test_a_document_one_program_holds_stays_on_the_xla_core(
        one_chip, monkeypatch):
    text = _eva_text(monkeypatch, one_chip, 8, T // 16, slots=T)
    assert "tpu_custom_call" not in text and "eva_core" not in text


# the fullest chip of the benchmark: the (8, 512) chunk program of the
# 8-layer stage, every layer's core on the kernel, against a group's state
# at 32,768 positions fits the v5e beside a second group's state (two are
# alive while the newer is enqueued), and the block caches the kernel
# writes are the donated ones (nothing copies 4.29 GB of state)
def test_the_eva_stages_widest_program_fits_beside_a_second_groups_state(
        one_chip, monkeypatch):
    import json
    from pathlib import Path

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
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = jax.jit(
        lambda p, t, s, n: enc.encode(p, t, s, lengths=n),
        donate_argnums=(2,)).lower(params, tokens, states, lengths).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') \
        == model["num_hidden_layers"] == 8
    assert " while(" not in text
    memory = compiled.memory_analysis()
    state = rows * enc.state_bytes_per_row(32768)
    assert state == 8 * 536870912
    # weights and one group's state in, the state aliased out
    assert memory.argument_size_in_bytes - state == pytest.approx(
        2 * model["parameters"]["held"], rel=1e-3)
    assert memory.alias_size_in_bytes >= state
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes \
        + state < 0.85 * 16909336064
