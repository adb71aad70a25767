"""Rows that have finished leave a multi-chunk group (PR 27): between
the chunk programs of a group ``InferenceEngine._embed_group_device``
narrows the batch to the smallest of ``batch_size`` halved up to three
times that holds the rows still alive, carrying the kept suffix of the
state and the pool on the device.

One set of cases over the LSTM, the QRNN and the small hybrid (B = 8,
buckets 8 and 16, so the grid is 8, 4, 2, 1): every row against the
same document embedded alone and against an engine whose grid is
forced to ``(B,)``; the rows that left before the batch first narrowed
are bit-identical to the wide path's; a second identical call compiles
nothing and moves nothing to or from the host but tokens, lengths and
the pooled rows.
"""

import types

import jax
import numpy as np
import pytest

from benchmark.harness import traffic
from benchmark.harness.context import CompileCounter
from benchmark.reference import granite_hybrid as ref
from test_granite_hybrid import MODEL, awd_engine

from code_intelligence_tpu.inference import InferenceEngine
from code_intelligence_tpu.models import make_config
from code_intelligence_tpu.text import SPECIALS, Vocab
from code_intelligence_tpu.utils.tracing import Tracer

B, BUCKETS, CHUNK = 8, (8, 16), 16
GRID = (8, 4, 2, 1)  # B, B/2, B/4, B/8

# a group's document lengths, ascending -> the rows its chunk programs run
CASES = {
    # documents that end exactly on a chunk boundary (16, 32, 48)
    "boundaries": ([3, 16, 16, 20, 32, 32, 40, 48], [8, 8, 2]),
    "one_long_among_short": ([2, 3, 4, 5, 6, 7, 8, 60], [8, 1, 1, 1]),
    "all_alive_to_the_end": ([50, 51, 52, 53, 54, 55, 56, 57], [8, 8, 8, 8]),
    "staircase": ([4, 9, 17, 18, 33, 35, 49, 64], [8, 8, 4, 2]),
    "fewer_documents_than_rows": ([5, 20, 40], [8, 2, 1]),
    "single_chunk": ([1, 2, 3, 5, 8, 9, 11, 16], [8]),
}


def rows_run(lengths, bucket, grid):
    """The rows each chunk program of one group runs, by hand: the
    whole batch (``grid[0]``) for the first, then the smallest of the
    halving grid that holds the documents still going."""
    chunks = max(1, -(-max(lengths) // bucket))
    return [grid[0]] + [min(b for b in grid if b >= sum(
        n > ci * bucket for n in lengths)) for ci in range(1, chunks)]


def test_the_cases_are_counted_by_hand():
    assert {k: rows_run(n, CHUNK, GRID) for k, (n, _) in CASES.items()} \
        == {k: rows for k, (_, rows) in CASES.items()}


def build(kind, vocab):
    if kind == "hybrid":
        params = ref.init_params(jax.random.PRNGKey(27), MODEL,
                                 {"dist": "student_t", "df": 4})
        cfg = make_config("granite_hybrid", MODEL, kv_positions=128)
        return InferenceEngine(params, cfg, vocab, buckets=BUCKETS,
                               batch_size=B)
    return awd_engine(kind == "qrnn", vocab, buckets=BUCKETS, batch_size=B)


@pytest.fixture(scope="module", params=["lstm", "qrnn", "hybrid"])
def engines(request):
    """``(narrowing, wide)``: the engine as it is, and one whose grid
    is forced to ``(B,)`` (every chunk program at ``batch_size``)."""
    vocab = Vocab(traffic.vocab_words(SPECIALS, 300))
    narrowing, wide = build(request.param, vocab), build(request.param, vocab)
    wide._batch_for = lambda rows: wide.batch_size
    return narrowing, wide


def documents(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(20, 300, n).astype(np.int32) for n in lengths]


@pytest.mark.parametrize("case", list(CASES))
def test_narrowed_rows_are_the_documents_own(engines, case):
    narrowing, wide = engines
    lengths, ran = CASES[case]
    seqs = documents(lengths)
    # handed over out of order: rows come back by document index
    order = np.random.default_rng(1).permutation(len(seqs))
    got = narrowing.embed_ids_batch([seqs[i] for i in order])
    got = got[np.argsort(order)]
    assert np.isfinite(got).all()

    alone = np.stack([narrowing.embed_ids_batch([s])[0] for s in seqs])
    np.testing.assert_allclose(got, alone, rtol=1e-4, atol=2e-5)
    want = wide.embed_ids_batch(seqs)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=2e-5)

    # every program a document ran through before the batch first
    # narrowed is the wide path's own, and no later program touches its
    # pool row: bit for bit
    first_narrow = next((ci for ci, b in enumerate(ran) if b < B), len(ran))
    left_early = [i for i, n in enumerate(lengths)
                  if n <= first_narrow * CHUNK]
    assert left_early or case == "all_alive_to_the_end"
    np.testing.assert_array_equal(got[left_early], want[left_early])
    if ran == [B] * len(ran):
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("case", list(CASES))
def test_counts_say_what_the_device_ran(engines, case):
    narrowing, wide = engines
    lengths, ran = CASES[case]
    seqs = documents(lengths)
    pools, counts = narrowing._embed_group_device(seqs)
    bucket = counts["bucket"]
    assert counts["chunks"] == len(ran) and counts["batch"] == B
    assert counts["rows"] == len(lengths)
    assert counts["lane_steps"] == B * bucket * len(ran)
    assert counts["lane_steps_run"] == sum(ran) * bucket
    assert counts["row_chunks_dropped"] == B * len(ran) - sum(ran)
    # the pool comes back as its pieces in row order: B rows in all
    assert sum(p[3].shape[0] for p in pools) == B
    narrowings = sum(a != b for a, b in zip(ran, ran[1:]))
    assert len(pools) == 1 + narrowings
    # the group as enqueued keeps its meaning on both engines
    _, as_wide = wide._embed_group_device(seqs)
    assert as_wide["row_chunks_dropped"] == 0
    assert as_wide["lane_steps_run"] == as_wide["lane_steps"]
    for key in ("rows", "batch", "bucket", "chunks", "valid_tokens",
                "lane_steps", "state_bytes", "kv_positions"):
        assert counts[key] == as_wide[key], key


@pytest.mark.parametrize("case", list(CASES))
def test_one_program_span_a_chunk_program(engines, case):
    """PR 34: under a traced document each chunk program records one
    ``engine.program`` with the rows it ran at, and the group's sums are
    the sums over them; the same group with no context records none and
    counts the same."""
    narrowing, _ = engines
    lengths, ran = CASES[case]
    seqs = documents(lengths)
    tracer = Tracer()
    root = tracer.start_span("bench.doc")
    _, counts = narrowing._embed_group_device(seqs, None, root.context)
    root.end()
    programs = [s["attrs"] for s in tracer.traces()[0]["spans"]
                if s["name"] == "engine.program"]
    assert [a["rows"] for a in programs] == ran
    assert all(a["batch"] == B and a["bucket"] == counts["bucket"]
               for a in programs)
    # the k-th program holds each document's k-th chunk
    bucket = counts["bucket"]
    assert [a["valid_tokens"] for a in programs] == [
        sum(min(max(n - k * bucket, 0), bucket) for n in lengths)
        for k in range(len(ran))]
    assert sum(a["lane_steps"] for a in programs) == counts["lane_steps_run"]
    assert sum(a["valid_tokens"] for a in programs) == counts["valid_tokens"]
    assert sum(a["lane_steps"] * (k + 1) for k, a in enumerate(programs)) \
        == counts["cache_steps_run"]
    _, untraced = narrowing._embed_group_device(seqs)
    assert untraced == counts


def test_unsorted_group_is_refused(engines):
    with pytest.raises(ValueError, match="ascending"):
        engines[0]._embed_group_device(documents([9, 3]))


def test_batch_axes_come_from_the_contract(engines):
    eng = engines[0]
    axes = eng._state_batch_axes
    three = jax.tree.leaves(jax.eval_shape(
        lambda: eng.encoder.init_states(3, CHUNK)))
    assert len(axes) == len(three)
    for ax, leaf in zip(axes, three):
        if ax is None:
            assert 3 not in leaf.shape or leaf.ndim == 0
        else:
            assert leaf.shape[ax] == 3
    assert any(ax is not None for ax in axes)


def test_steady_state_compiles_and_transfers_nothing_new(engines,
                                                         monkeypatch):
    """Every case once, then all of them again under the auditors: no
    backend compile (``jax.monitoring``), and every narrowing step under
    ``jax.transfer_guard("disallow")``: state and pool stay on the
    device (the guard cannot span the call: ``init_states``' own
    ``jnp.zeros`` moves a scalar, as it did before)."""
    eng = engines[0]
    calls = [documents(n, seed=2) for n, _ in CASES.values()]
    expected = [eng.embed_ids_batch(seqs) for seqs in calls]
    narrow, narrowed = eng._narrow, []

    def guarded(h_states, pool_state, keep):
        narrowed.append(keep)
        with jax.transfer_guard("disallow"):
            return narrow(h_states, pool_state, keep)

    monkeypatch.setattr(eng, "_narrow", guarded)
    compiles = CompileCounter()  # what ``compiles_in_window`` reads
    audited = [eng.embed_ids_batch(seqs) for seqs in calls]
    assert compiles.new() == 0
    for a, e in zip(audited, expected):
        np.testing.assert_array_equal(a, e)
    assert narrowed == [b for _, rows in CASES.values()
                        for a, b in zip(rows, rows[1:]) if b < a]
    # the grid bounds what a batch size may compile: three sizes below it
    assert {b for b, _ in eng._fwd_cache} == set(GRID)


@pytest.mark.parametrize("batch_size,grid", [
    (200, [200, 100, 50, 25]), (16, [16, 8, 4, 2]), (32, [32, 16, 8, 4]),
    (3, [3, 2, 1, 1]), (1, [1, 1, 1, 1])])
def test_the_grid_is_the_batch_halved_three_times(batch_size, grid):
    eng = types.SimpleNamespace(batch_size=batch_size)
    for rows in range(1, batch_size + 1):
        assert InferenceEngine._batch_for(eng, rows) \
            == min(b for b in grid if b >= rows)
