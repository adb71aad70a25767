"""Mosaic takes the latent attention core (`ops/mla.py::mla_cached`) and the
two delta-rule cores (`ops/kda.py::kda_scan`, `ops/gdn.py::gdn_scan`) at
their cells' shapes, and no Mosaic call appears where the rules say XLA
(`tests/pallas_tpu_compile.py` has the how and the why).
"""

import jax
import jax.numpy as jnp
import pytest

from pallas_tpu_compile import one_chip  # noqa: F401


def _mla_text(monkeypatch, one_chip, rows, T, S, dtype=jnp.bfloat16, H=128):
    """The compiled text of one ``mla_cached`` call as the encoder makes
    it at the published sizes (``H`` heads of 128 + 64 | 128, rank 512);
    the rule asks the backend, so the test answers for it."""
    from code_intelligence_tpu.ops.mla import mla_cached

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    nope, rope, v, rank = 128, 64, 128, 512

    def core(q_nope, q_pe, latent, cache, w_kvb, pos):
        return mla_cached(q_nope, q_pe, latent, cache, pos, w_kvb, 0.1352, v,
                          mxu_dtype=dtype)

    shapes = [((rows, T, H, nope), dtype), ((rows, T, H, rope), jnp.float32),
              ((rows, T, rank + rope), jnp.float32),
              ((rows, S, rank + rope), dtype), ((rank, H * (nope + v)), dtype),
              ((), jnp.int32)]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(core).lower(*args).compile().as_text()


# `deepseek_v3_bulk_mixed`: the multi-chunk group's programs (16 and 2
# rows against the 2048-position cache) and the single-chunk groups whose
# bucket the rule sends to the kernel
@pytest.mark.parametrize("rows,T,S", [(16, 512, 2048), (2, 512, 2048),
                                      (16, 512, 512), (16, 256, 256)])
def test_the_latent_kernel_compiles_at_deepseeks_shapes(
        one_chip, monkeypatch, rows, T, S):
    text = _mla_text(monkeypatch, one_chip, rows, T, S)
    assert "tpu_custom_call" in text and "mla_cached_core" in text
    # one body a shape: no static prefixes to switch over
    assert "conditional" not in text


# `ling_bulk_long_tail`: 32 heads against the long group's cache of 16,384
# positions (32 key blocks of 512) and the short group's of 4,096, at the
# rows its programs narrow to
@pytest.mark.parametrize("rows,S", [(16, 16384), (2, 16384), (16, 4096)])
def test_the_latent_kernel_compiles_at_the_long_caches(
        one_chip, monkeypatch, rows, S):
    text = _mla_text(monkeypatch, one_chip, rows, 512, S, H=32)
    assert "tpu_custom_call" in text and "mla_cached_core" in text
    assert "conditional" not in text


# `longcat_bulk_long_tail`: 64 heads (eight steps of 8 heads) against the
# long group's caches of 16,384 positions and the short group's of 4,096,
# at rows its programs narrow to
@pytest.mark.parametrize("rows,S", [(16, 16384), (2, 16384), (8, 4096)])
def test_the_latent_kernel_compiles_at_sixty_four_heads(
        one_chip, monkeypatch, rows, S):
    text = _mla_text(monkeypatch, one_chip, rows, 512, S, H=64)
    assert "tpu_custom_call" in text and "mla_cached_core" in text
    assert "conditional" not in text


# ops/kda.py::kda_scan at the same cell's shapes (32 heads of 128 | 128,
# chunks of 64 in sub-blocks of 16, float32 operands as the encoder holds
# them, bfloat16 in-chunk products), at the rows its programs narrow to
def _kda_compiled(one_chip, rows, mxu_dtype=jnp.bfloat16):
    from code_intelligence_tpu.ops.kda import kda_scan

    T, H, d = 512, 32, 128
    shapes = [(rows, T, H, d)] * 4 + [(rows, T, H), (rows, H, d, d)]
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in shapes]
    return jax.jit(lambda *a: kda_scan(
        *a, chunk=64, mxu_dtype=mxu_dtype)).lower(*args).compile()


# the kernel: Mosaic takes the strided loads of a head's chunk, the rolls,
# the float32 products and the blocks' VMEM; nothing is copied around it
# (the `(b, T * H, d)` view of the operands is the same bytes)
@pytest.mark.parametrize("rows", [16, 2])
def test_the_delta_rule_kernel_compiles_at_the_cells_shapes(
        one_chip, monkeypatch, rows):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = _kda_compiled(one_chip, rows)
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "kda_scan_core" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1024 ** 2


# the XLA scan, what the rule picks off the TPU (here) and for float32:
# no Mosaic call, and the chip's compiler takes a full program's
# temporaries
@pytest.mark.parametrize("rows", [16, 2])
def test_the_delta_rule_recurrence_compiles_at_the_cells_shapes(
        one_chip, rows):
    compiled = _kda_compiled(one_chip, rows)
    assert "tpu_custom_call" not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * 1024 ** 3


def test_the_delta_rule_stays_on_xla_in_float32(one_chip, monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = _kda_compiled(one_chip, 2, jnp.float32)
    assert "tpu_custom_call" not in compiled.as_text()


# ops/gdn.py::gdn_scan at `qwen3_next_bulk_long_tail`'s shapes (32 value
# heads on 16 key heads of 128 | 128, chunks of 64, float32 operands as
# the encoder holds them, bfloat16 in-chunk products)
def _gdn_compiled(one_chip, rows, mxu_dtype=jnp.bfloat16):
    from code_intelligence_tpu.ops.gdn import gdn_scan

    T, Hk, Hv, d = 512, 16, 32, 128
    shapes = [(rows, T, Hk, d)] * 2 + [(rows, T, Hv, d)] \
        + [(rows, T, Hv)] * 2 + [(rows, Hv, d, d)]
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in shapes]
    return jax.jit(lambda *a: gdn_scan(
        *a, chunk=64, mxu_dtype=mxu_dtype)).lower(*args).compile()


# the kernel at every row count the cell's set-up compiles (16 narrowing
# to 8, 4, 2): Mosaic takes the strided loads of a key head's and its two
# value heads' chunks, the turned decays and the blocks' VMEM; the `(b, T
# * H, d)` view of the operands is the same bytes, so nothing the size of
# an operand is copied around it
@pytest.mark.parametrize("rows", [16, 8, 4, 2])
def test_the_scalar_decay_kernel_compiles_at_the_cells_shapes(
        one_chip, monkeypatch, rows):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = _gdn_compiled(one_chip, rows)
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "gdn_scan_core" in text
    assert "while" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1024 ** 2


def test_the_scalar_decay_rule_stays_on_xla_in_float32(one_chip, monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = _gdn_compiled(one_chip, 2, jnp.float32)
    text = compiled.as_text()
    assert "tpu_custom_call" not in text and "while" in text


# where the rule says XLA no Mosaic call appears: the single-chunk groups
# of buckets 128 and under, and float32 operands
@pytest.mark.parametrize("T,S,dtype", [(128, 128, jnp.bfloat16),
                                       (64, 64, jnp.bfloat16),
                                       (256, 256, jnp.float32)])
def test_the_latent_core_stays_on_xla_where_the_rule_says_so(
        one_chip, monkeypatch, T, S, dtype):
    text = _mla_text(monkeypatch, one_chip, 16, T, S, dtype)
    assert "tpu_custom_call" not in text


def _experts_text(monkeypatch, one_chip, N, top_k, count, n_experts, E, F,
                  dtype=jnp.bfloat16):
    """The compiled text of one ``routed_experts`` call over ``N`` tokens
    as the expert encoders make it; the rule asks the backend, so the
    test answers for it."""
    from code_intelligence_tpu.ops import moe

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def layer(x, experts, weights, w_in, w_out):
        return moe.routed_experts(x, experts, weights, w_in, w_out, 0,
                                  n_experts)

    shapes = [((N, E), jnp.float32), ((N, top_k), jnp.int32),
              ((N, top_k), jnp.float32), ((count, E, 2 * F), dtype),
              ((count, F, E), dtype)]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(layer).lower(*args).compile().as_text()


# the held experts of the five expert cells (`ops/gmm.py`), at their
# widest program (16 rows of 512 tokens) and at the two rows the long
# group narrows to: SmallThinker's one pass over 6 N rows, the four
# shares' rounds of N
@pytest.mark.parametrize("N,top_k,count,n_experts,E,F", [
    (8192, 6, 64, 64, 2560, 768), (1024, 6, 64, 64, 2560, 768),
    (8192, 8, 128, 512, 2560, 768), (1024, 8, 128, 512, 2560, 768),
    (8192, 8, 16, 256, 7168, 2048), (1024, 8, 16, 256, 7168, 2048),
    (8192, 4, 32, 256, 3072, 3072), (8192, 12, 16, 768, 6144, 2048),
], ids=["smallthinker", "smallthinker_2_rows", "ling", "ling_2_rows",
        "deepseek", "deepseek_2_rows", "trinity", "longcat"])
def test_the_grouped_matmul_kernels_compile_at_the_cells_widths(
        one_chip, monkeypatch, N, top_k, count, n_experts, E, F):
    text = _experts_text(monkeypatch, one_chip, N, top_k, count, n_experts,
                         E, F)
    assert "gated_gmm" in text and "ragged-dot" not in text
    # the two products, and nothing else of the layer, are Mosaic's
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 2


def test_the_grouped_matmuls_stay_on_xla_in_float32(one_chip, monkeypatch):
    text = _experts_text(monkeypatch, one_chip, 1024, 6, 64, 64, 2560, 768,
                         jnp.float32)
    assert "gated_gmm" not in text and "ragged-dot" in text
