"""The tiles `_pick_tiles` chooses compile for the chip, at the shapes the
repo runs.

A tile is a function of the shapes alone (tests/test_pallas_lstm.py), so
what the pickers return is what the chip is handed: here Mosaic, the TPU's
own compiler, is asked to take it, for a v5e that is described and not
attached (`/opt/skills/guides/on-chip-measurement` §2). Interpret mode
cannot refuse a slice that is not aligned to the tiling or a kernel that
asks for more VMEM than it may use; this can. Nothing runs and nothing is
timed. One file, and the topology is described inside a fixture: only the
worker that is given this file loads the TPU's library.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from code_intelligence_tpu.ops.pallas_lstm import (
    fused_lstm_backward,
    fused_lstm_forward,
    lstm_layer_fused,
)

H = 2500  # the flagship's hidden size: W_hh is 50 MB of bfloat16


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


# (T, B): a bulk chunk program at the cells' 200 rows and at the 100 and 25
# its last group narrows to, and the server's default batch
@pytest.mark.parametrize("t,b", [(512, 200), (512, 100), (512, 25), (64, 32)])
def test_the_inference_forward_compiles_at_the_picked_tile(one_chip, t, b):
    text = _compiled_text(
        lambda x, w, h, c: fused_lstm_forward(x, w, h, c, interpret=False),
        one_chip, (t, b, 4 * H), (4 * H, H), (b, H), (b, H))
    assert "tpu_custom_call" in text


def test_the_training_forward_and_backward_compile_at_the_picked_tiles(
        one_chip):
    t, b = 67, 104  # the reference's bptt and batch
    fwd = _compiled_text(
        lambda x, w, h, c: fused_lstm_forward(x, w, h, c, with_gates=True,
                                              interpret=False),
        one_chip, (t, b, 4 * H), (4 * H, H), (b, H), (b, H))
    assert "tpu_custom_call" in fwd
    bwd = _compiled_text(
        lambda g, cp, do, w, dh, dc: fused_lstm_backward(
            g, cp, do, w, dh, dc, interpret=False),
        one_chip, (t, b, 4 * H), (t, b, H), (t, b, H), (4 * H, H), (b, H),
        (b, H))
    assert "tpu_custom_call" in bwd


# (B, H, in, dtype): the train cell's two layer shapes, a batch that splits
# into two tiles, and a float32 layer the rule also calls resident
@pytest.mark.parametrize("b,h,in_dim,dtype", [
    (104, H, 800, jnp.bfloat16), (104, 800, H, jnp.bfloat16),
    (200, H, H, jnp.bfloat16), (104, 800, H, jnp.float32)])
def test_a_layers_gradient_compiles_outside_the_train_step(
        one_chip, monkeypatch, b, h, in_dim, dtype):
    """`jax.grad` of ONE resident layer: the program in which the adjoint
    at its whole-batch tile ran out of VMEM under the inference kernels'
    limit while `train_steps` compiled (PR 31): XLA keeps the kernel's
    small operands in VMEM here. The layer asks the backend whether to
    interpret, so the test answers for it."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    t, g = 67, 4 * h

    def loss(x, h0, c0, w_ih, w_hh, bias):
        out, (h_t, _) = lstm_layer_fused(x, (h0, c0), w_ih, w_hh, bias)
        return (out.astype(jnp.float32) ** 2).mean() \
            + h_t.astype(jnp.float32).sum()

    args = [jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)
            for s in ((b, t, in_dim), (b, h), (b, h), (g, in_dim), (g, h),
                      (g,))]
    text = jax.jit(jax.grad(loss, argnums=(0, 3, 4, 5))).lower(
        *args).compile().as_text()
    assert text.count("tpu_custom_call") >= 2  # forward and adjoint


# -- ops/attention.py::gqa_cached at the attention cells' shapes ------------

def _gqa_text(monkeypatch, one_chip, rows, T, S, window, Hq, Hkv, d):
    """The compiled text of one ``gqa_cached`` call as the encoders make
    it; the rule asks the backend, so the test answers for it."""
    from code_intelligence_tpu.ops.attention import gqa_cached

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def core(q, k, v, k_cache, v_cache, pos):
        return gqa_cached(q, k, v, k_cache, v_cache, pos, d ** -0.5,
                          window=window)

    args = [jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)
            for s in ((rows, T, Hq, d), (rows, T, Hkv, d), (rows, T, Hkv, d),
                      (rows, Hkv, S, d), (rows, Hkv, S, d))]
    pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    return jax.jit(core).lower(*args, pos).compile().as_text()


# `trinity_bulk_long_tail`: the short group's caches, the long group's
# rings and its global cache, at every batch the group narrows to
@pytest.mark.parametrize("rows", [16, 8, 4, 2])
@pytest.mark.parametrize("S,window", [(4096, 4096), (4096, None),
                                      (4608, 4096), (16384, None)])
def test_the_attention_kernel_compiles_at_trinitys_shapes(
        one_chip, monkeypatch, rows, S, window):
    text = _gqa_text(monkeypatch, one_chip, rows, 512, S, window, 48, 8, 128)
    assert "tpu_custom_call" in text


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


# -- ops/mla.py::mla_cached at the latent-attention cell's shapes ------------

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


# where the rule says XLA no Mosaic call appears: the single-chunk groups
# of buckets 128 and under, and float32 operands
@pytest.mark.parametrize("T,S,dtype", [(128, 128, jnp.bfloat16),
                                       (64, 64, jnp.bfloat16),
                                       (256, 256, jnp.float32)])
def test_the_latent_core_stays_on_xla_where_the_rule_says_so(
        one_chip, monkeypatch, T, S, dtype):
    text = _mla_text(monkeypatch, one_chip, 16, T, S, dtype)
    assert "tpu_custom_call" not in text
