"""Mosaic takes `ops/attention.py::gqa_cached`'s kernel at Trinity's shapes,
the family with the most cases (`tests/pallas_tpu_compile.py` has the how
and the why; Granite's and SmallThinker's are in
`tests/test_pallas_tpu_compile_gqa.py`).
"""

import pytest

from pallas_tpu_compile import _gqa_text, one_chip  # noqa: F401


# `trinity_bulk_long_tail`: the short group's caches, the long group's
# rings and its global cache, at every batch the group narrows to
@pytest.mark.parametrize("rows", [16, 8, 4, 2])
@pytest.mark.parametrize("S,window", [(4096, 4096), (4096, None),
                                      (4608, 4096), (16384, None)])
def test_the_attention_kernel_compiles_at_trinitys_shapes(
        one_chip, monkeypatch, rows, S, window):
    text = _gqa_text(monkeypatch, one_chip, rows, 512, S, window, 48, 8, 128)
    assert "tpu_custom_call" in text
