"""Mosaic takes `ops/attention.py::gqa_cached`'s kernel at Granite's and
SmallThinker's shapes, and no Mosaic call appears where the rule says XLA
(`tests/pallas_tpu_compile.py` has the how and the why; Trinity's shapes
are in `tests/test_pallas_tpu_compile_gqa_trinity.py`; the EVA stage's
in `tests/test_pallas_tpu_compile_eva.py`).
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
