"""The seam beneath the large encoders (`models/blocks.py`): no model's
file imports another model's, the carried counts are read and written by
name in one layout, and the cache that grows with the document is sized
by one rule.
"""

import ast
from pathlib import Path

import numpy as np
import pytest

import jax.numpy as jnp

from code_intelligence_tpu.models import blocks, build_encoder, make_config
from code_intelligence_tpu.ops import moe

MODELS = Path(blocks.__file__).resolve().parent
ARCHITECTURES = ("granite_hybrid", "deepseek_v3", "afmoe", "bailing_hybrid",
                 "smallthinker", "longcat_flash", "awd_lstm")


def _imported(source: str):
    """Every dotted name a source imports, wherever the statement sits:
    ``from a import b`` as ``a.b`` (``b`` may be a module), a relative
    import without its dots."""
    for node in ast.walk(ast.parse(source)):
        if isinstance(node, ast.Import):
            yield from (alias.name for alias in node.names)
        elif isinstance(node, ast.ImportFrom):
            base = f"{node.module}." if node.module else ""
            yield from (base + alias.name for alias in node.names)


def test_the_import_reader_sees_every_form():
    assert sorted(_imported(
        "import a.b\nfrom c.d import e, f\nfrom . import g\n"
        "def h():\n    from ..i import j\n")) == [
        "a.b", "c.d.e", "c.d.f", "g", "i.j"]


@pytest.mark.parametrize("module", ARCHITECTURES + ("blocks",
                                                    "windowed_caches"))
def test_no_model_imports_another_models_file(module):
    """`models/contract.py` is the one module that imports them all: what
    two models share lives in `models/blocks.py`, beneath both."""
    named = {part for name in _imported((MODELS / f"{module}.py").read_text())
             for part in name.split(".")}
    assert not named & (set(ARCHITECTURES) - {module})


# -- the counts ---------------------------------------------------------------

TINY = dict(num_hidden_layers=2, vocab_size=50, hidden_size=16,
            kv_positions=16)
EXPERT_MODELS = {
    "deepseek_v3": dict(
        TINY, first_k_dense_replace=1, n_routed_experts=4, n_group=2),
    "afmoe": dict(
        TINY, num_dense_layers=1, num_experts=4, chunk_positions=4,
        layer_types=["sliding_attention", "full_attention"],
        sliding_window=4),
    "bailing_hybrid": dict(
        TINY, first_k_dense_replace=1, layer_group_size=2, num_experts=4,
        n_group=2),
    "smallthinker": dict(
        TINY, moe_num_primary_experts=4, rope_layout=[0, 1],
        sliding_window_layout=[0, 1], chunk_positions=4,
        sliding_window_size=4),
    "longcat_flash": dict(
        vocab_size=50, hidden_size=16, kv_positions=16, num_layers=2,
        n_routed_experts=4, zero_expert_num=2, moe_topk=2),
}
# an encoder's own slots: (sums, totals, sets)
OWN_SLOTS = {
    "deepseek_v3": ((), (), ("attention_kernel_layers",
                             "expert_kernel_layers")),
    "afmoe": ((), (), ("attention_kernel_layers", "expert_kernel_layers")),
    "bailing_hybrid": ((), (), ("kda_kernel_layers",
                                "attention_kernel_layers",
                                "expert_kernel_layers")),
    "smallthinker": (("expert_rounds",), (), ("attention_kernel_layers",
                                              "expert_kernel_layers")),
    "longcat_flash": (("expert_rounds",), ("zero_choices", "valid_choices"),
                      ("attention_kernel_layers", "expert_kernel_layers")),
}


@pytest.mark.parametrize("architecture", sorted(EXPERT_MODELS))
def test_an_encoders_counts_are_one_layout_read_by_name(architecture):
    enc = build_encoder(make_config(architecture,
                                    EXPERT_MODELS[architecture]))
    counts = enc.counts
    sums, totals, sets = OWN_SLOTS[architecture]
    assert counts.names == moe.COUNTERS + sums + totals + sets
    vector = enc.state_counters(enc.init_states(1))
    assert vector.shape == (len(counts.names),)
    assert vector.dtype == jnp.int32
    assert not np.asarray(vector).any()

    one = jnp.int32(1)
    own = {name: 10 + i for i, name in enumerate(sums + totals + sets)}
    once = counts.update(vector, 6 * one, 4 * one, one, **own)
    twice = counts.update(once, 6 * one, 4 * one, one, **own)
    # a sum adds up over a group's programs, a set slot is the last
    # program's answer
    assert [counts.total([np.asarray(twice)], name)
            for name in moe.COUNTERS] == [12, 8, 2]
    for name in sums + totals:
        assert counts.total([np.asarray(twice)], name) == 2 * own[name]
    for name in sets:
        assert counts.total([np.asarray(twice)], name) == own[name]

    # two groups of a flush: every set slot under its own name, averaged
    # over the groups; every sum of the encoder's own as a mean a layer a
    # program, every total as it is
    attrs = enc.counter_attrs([np.asarray(once), np.asarray(twice)])
    assert {"routed_rows", "moe_programs", "expert_rows_max",
            "expert_rows_mean", *sets, *totals,
            *(f"{name}_mean" for name in sums)} <= set(attrs)
    assert attrs["routed_rows"] == 18 and attrs["moe_programs"] == 3
    for name in totals:
        assert attrs[name] == 3 * own[name]
    for name in sets:
        assert attrs[name] == own[name]
    assert enc.counter_attrs([]) == {}


def test_a_slot_is_written_by_its_name_and_a_missing_one_is_an_error():
    counts = blocks.Counts(sums=("a",), sets=("b", "c"))
    zero = jnp.int32(0)
    got = counts.update(counts.zeros(), zero, zero, zero, c=3, a=1, b=2)
    assert np.asarray(got).tolist() == [0, 0, 0, 1, 2, 3]
    # totals lie between the sums and the sets and add up like the sums
    both = blocks.Counts(sums=("a",), totals=("t",), sets=("b",))
    got = both.update(both.update(both.zeros(), zero, zero, zero,
                                  a=1, t=5, b=2), zero, zero, zero,
                      a=1, t=5, b=7)
    assert np.asarray(got).tolist() == [0, 0, 0, 2, 10, 7]
    for own in ({"a": 1, "b": 2}, {"b": 2, "c": 3}, {"a": 1, "B": 2, "c": 3}):
        with pytest.raises(KeyError):
            counts.update(counts.zeros(), zero, zero, zero, **own)


@pytest.mark.parametrize("held, n, want", [
    (None, 8, (0, 8)), ([4, 4], 8, (4, 4)), ((4, 5), 8, ValueError),
    ((-1, 2), 8, ValueError), ((0, 0), 8, ValueError)])
def test_the_experts_held_lie_within_the_routers(held, n, want):
    if want is ValueError:
        with pytest.raises(ValueError, match="lies outside the router's 8"):
            blocks.held_experts(held, n)
    else:
        assert blocks.held_experts(held, n) == want


# -- the cache that grows -----------------------------------------------------

GROWING = {
    "granite_hybrid": ("key/value", dict(
        vocab_size=50, hidden_size=16, num_hidden_layers=2,
        layer_types=["mamba", "attention"], num_attention_heads=2,
        num_key_value_heads=1, mamba_n_heads=4, mamba_d_head=8)),
    "deepseek_v3": ("latent", EXPERT_MODELS["deepseek_v3"]),
    "longcat_flash": ("latent", EXPERT_MODELS["longcat_flash"]),
}


@pytest.mark.parametrize("positions, allocated", [
    (None, 2048), (512, 512), (513, 2048), (2049, ValueError)],
    ids=["none", "short", "long", "too_long"])
@pytest.mark.parametrize("architecture", sorted(GROWING))
def test_a_growing_cache_is_sized_by_one_rule(architecture, positions,
                                              allocated):
    kind, model = GROWING[architecture]
    enc = build_encoder(make_config(architecture,
                                    dict(model, kv_positions=2048)))
    assert isinstance(enc, blocks.GrowingCache)
    assert enc.window_positions(positions) == 0
    if allocated is ValueError:
        with pytest.raises(ValueError, match=f"{kind} cache of "
                                             "kv_positions=2048"):
            enc.cache_positions(positions)
    else:
        assert enc.cache_positions(positions) == allocated
