"""The program's own names for its time (PR 24): the groups path of
``InferenceEngine.embed_issues`` records text-rule, tokenise, group and
device-wait spans that tile a traced call and cost an untraced one no
per-document clock read; since PR 25 a multi-group call prepares its
documents shortest first and enqueues each group as soon as it exists,
and two counts on those spans say whether that engaged; ``LMTrainer.fit`` delivers every dispatch as a
trace of its own, however long the fit; and the compiled forward and
train step carry ``jax.named_scope`` names for each of their parts."""

import re
import time
import types

import jax
import numpy as np
import pytest

from test_group_narrowing import rows_run
from test_inference import worded_issues as worded
from test_slot_scheduler import make_engine
from test_training import repeating_corpus, tiny_model

from code_intelligence_tpu.data import LMStreamLoader
from code_intelligence_tpu.inference import engine as engine_mod
from code_intelligence_tpu.parallel import make_mesh
from code_intelligence_tpu.training import LMTrainer, TrainConfig
from code_intelligence_tpu.utils import tracing
from code_intelligence_tpu.utils.tracing import Tracer

B, BUCKETS = 4, (8, 16)
GRID = (4, 2, 1)  # B, B/2, B/4 (and B/8 rounded up, 1 again)
PHASES = ("engine.text_rules", "engine.tokenize", "engine.group",
          "engine.finalize")


def issues(lengths):
    """One issue per entry, ``n`` body words each (plus the field marks
    the text rules add)."""
    return [{"title": f"w{i % 7}", "body": " ".join(
        f"w{(i + j) % 140}" for j in range(n))} for i, n in enumerate(lengths)]


def sorted_slab_lane_steps(n_tokens, run=False):
    """What the length-sorted slabs of ``B`` cost, by hand: as enqueued
    (every chunk program at ``B`` rows: ``lane_steps``), or with ``run``
    what the device is asked to run (``lane_steps_run``)."""
    n_tokens, lanes = sorted(n_tokens), 0
    for i in range(0, len(n_tokens), B):
        slab = n_tokens[i:i + B]
        bucket = next((b for b in BUCKETS if slab[-1] <= b), BUCKETS[-1])
        ran = rows_run(slab, bucket, GRID)
        lanes += (sum(ran) if run else B * len(ran)) * bucket
    return lanes


def lane_steps_of(groups, key="lane_steps"):
    return sum(g.get("attrs", g)[key] for g in groups)


def traced_call(engine, docs):
    """``embed_issues`` the way the benchmark's bulk driver calls it: one
    root span a document on a tracer of its own, every finished trace
    through ``on_trace``. Returns the spans by name (as the traces
    rendered them, on the wall clock) and the call's wall interval."""
    tracer = Tracer(max_live=4 * len(docs))
    got = []
    tracer.on_trace(got.append)
    roots = [tracer.start_span("bench.doc") for _ in docs]
    t0 = time.time()
    engine.embed_issues(docs, scheduler="groups",
                        ctxs=[r.context for r in roots])
    t1 = time.time()
    for r in roots:
        r.end()
    assert len(got) == len(docs)
    assert all(t["dropped_spans"] == 0 for t in got)
    by_name = {}
    for doc, t in enumerate(got):
        for s in t["spans"]:
            lo = t["start_unix"] + s["start_s"]
            by_name.setdefault(s["name"], []).append(
                dict(s, doc=doc, lo=lo, hi=lo + s["duration_s"]))
    return by_name, (t0, t1)


@pytest.fixture(scope="module")
def engine():
    eng = make_engine(batch_size=B, buckets=BUCKETS)
    eng.embed_issues(issues([3, 12, 40]), scheduler="groups")  # compile
    return eng


class TestGroupsPathSpans:
    def test_counts_per_document_per_group_per_flush(self, engine):
        n, groups = 10, 3  # 4 + 4 + 2 documents
        spans, _ = traced_call(engine, issues([2, 30, 5, 9, 1, 14, 3, 40, 7, 4]))
        assert len(spans["engine.text_rules"]) == n
        assert len(spans["engine.tokenize"]) == n
        assert len(spans["engine.group_embed"]) == n
        assert len(spans["engine.group"]) == groups
        assert len(spans["engine.finalize"]) == 1
        assert spans["engine.finalize"][0]["attrs"]["groups"] == groups
        assert all(s["attrs"]["n_chars"] > 0
                   for s in spans["engine.text_rules"])
        # one group span per group, each on a document of that group;
        # the flush on the call's first document
        assert len({s["doc"] for s in spans["engine.group"]}) == groups
        assert spans["engine.finalize"][0]["doc"] == 0

    @pytest.mark.parametrize("lengths,chunks", [
        ([1, 2, 3, 2], 1),            # one pass at the smallest bucket
        ([14, 2, 20, 35], 5),         # streams chunk_len windows
    ], ids=["short", "streamed"])
    def test_group_counts_are_what_the_device_runs(self, engine, lengths,
                                                   chunks):
        spans, _ = traced_call(engine, issues(lengths))
        (group,) = spans["engine.group"]
        a = group["attrs"]
        n_tokens = [s["attrs"]["n_tokens"] for s in spans["engine.tokenize"]]
        assert a["rows"] == len(lengths) and a["batch"] == B
        assert a["valid_tokens"] == sum(n_tokens)
        assert a["bucket"] in BUCKETS
        assert a["chunks"] == -(-max(n_tokens) // a["bucket"]) == chunks
        assert a["lane_steps"] == B * a["bucket"] * a["chunks"]
        ran = rows_run(n_tokens, a["bucket"], GRID)
        assert len(ran) == chunks
        assert a["lane_steps_run"] == sum(ran) * a["bucket"]
        assert a["row_chunks_dropped"] == B * chunks - sum(ran)
        assert (a["row_chunks_dropped"] > 0) == (chunks > 1)

    @pytest.mark.parametrize("lengths,ran", [
        ([1, 2, 3, 4], [4]),                 # a single chunk
        ([33, 34, 35, 36], [4, 4, 4]),       # every row alive to the end
        ([5, 12, 20, 40], [4, 2, 1]),        # 4, 2, 1 alive
        ([16, 16, 32, 48], [4, 2, 1]),       # ending ON the boundaries
        ([9, 40], [4, 1, 1]),                # two documents in four rows
    ], ids=["single", "all_alive", "4_2_1", "boundaries", "partial"])
    def test_row_chunks_dropped_is_the_hand_count(self, engine, lengths,
                                                  ran):
        rng = np.random.RandomState(7)
        _, a = engine._embed_group_device(
            [rng.randint(20, 150, n).astype(np.int32) for n in lengths])
        assert rows_run(lengths, a["bucket"], GRID) == ran
        assert a["chunks"] == len(ran) and a["batch"] == B
        assert a["lane_steps"] == B * a["bucket"] * len(ran)
        assert a["lane_steps_run"] == sum(ran) * a["bucket"]
        assert a["row_chunks_dropped"] == sum(B - r for r in ran)

    def test_groups_hold_the_length_sorted_documents(self, engine):
        spans, _ = traced_call(
            engine, issues([2, 30, 5, 9, 1, 14, 3, 40, 7, 4]))
        n_tokens = sorted(s["attrs"]["n_tokens"]
                          for s in spans["engine.tokenize"])
        want = [sum(n_tokens[i:i + B]) for i in range(0, len(n_tokens), B)]
        got = [s["attrs"]["valid_tokens"] for s in sorted(
            spans["engine.group"], key=lambda s: s["lo"])]
        assert got == want

    def test_spans_tile_the_call(self, engine):
        rng = np.random.RandomState(3)
        spans, (t0, t1) = traced_call(
            engine, issues(rng.randint(40, 200, 40).tolist()))
        # the named set is complete: a text-rule and a tokenise span a
        # document, ten groups of four, one flush
        assert [len(spans[name]) for name in PHASES] == [40, 40, 10, 1]
        phases = sorted((s for name in PHASES for s in spans[name]),
                        key=lambda s: s["lo"])
        # every one inside the call (its ends are read off another clock
        # than the spans': 1 ms), and none overlaps the next (rendered
        # times are rounded to 1 us)
        assert t0 - 1e-3 <= phases[0]["lo"]
        assert max(s["hi"] for s in phases) <= t1 + 1e-3
        for a, b in zip(phases, phases[1:]):
            assert b["lo"] >= a["hi"] - 5e-6, (a["name"], b["name"])
        # groups and flushes lie inside the group_embed interval of the
        # documents prepared first; every document's own preparation lies
        # before its own interval, and (ten groups) inside other documents'
        whole = min(spans["engine.group_embed"], key=lambda s: s["lo"])
        for s in spans["engine.group"] + spans["engine.finalize"]:
            assert whole["lo"] - 5e-6 <= s["lo"] and s["hi"] <= whole["hi"] + 5e-6
        starts = {s["doc"]: s["lo"] for s in spans["engine.group_embed"]}
        for s in spans["engine.text_rules"] + spans["engine.tokenize"]:
            assert s["hi"] <= starts[s["doc"]] + 5e-6
        assert sum(s["lo"] > whole["lo"] for s in spans["engine.tokenize"]) \
            == len(starts) - (B + B // 4)

    MARKDOWN = {"title": "Crash in `parse()` with **nested** lists",
                "body": "# Steps\n\n- run `make all`\n- see "
                        "[the log](https://example.com/log)\n\n```\n"
                        "Traceback: boom\n```\n\n> it failed &amp; "
                        "hung!!!!! <br> cc @dev kind/bug"}
    PLAIN = {"title": "Crash when saving a file",
             "body": "The editor stops responding when I save a large file "
                     "twice in a row and nothing is written to the log"}
    PASSES = 17  # the regex scans one application of the chain could make

    @pytest.mark.parametrize("doc,text_run", [
        (MARKDOWN, 14),
        (PLAIN, 0),
    ], ids=["markdown", "plain"])
    def test_rule_passes_ride_on_both_tokenise_spans(self, engine, doc,
                                                     text_run):
        """``rule_passes``: the scans the chain could make over title and
        body; ``rule_passes_run``: the scans the guards let through. The
        pair rides on ``engine.text_rules``, which
        ``pre_rule_passes_run_pct`` reads; the chain's second application
        under ``engine.tokenize`` is not counted (since PR 34: no metric
        read it)."""
        spans, _ = traced_call(engine, [doc, doc])
        assert len(spans["engine.text_rules"]) == 2
        for s in spans["engine.text_rules"]:
            a = s["attrs"]
            assert a["rule_passes"] == 2 * self.PASSES
            assert a["rule_passes_run"] == text_run, a
        assert len(spans["engine.tokenize"]) == 2
        for s in spans["engine.tokenize"]:
            assert set(s["attrs"]) == {"n_tokens", "n_tokens_overlapped"}
        assert engine_mod.text_rules._tally.counts is None  # closed again

    def test_untraced_call_records_nothing_and_reads_no_clock_per_doc(
            self, engine, monkeypatch):
        reads, finished = [], []
        monkeypatch.setattr(engine_mod, "time", types.SimpleNamespace(
            perf_counter=lambda: reads.append(1) or time.perf_counter()))
        monkeypatch.setattr(Tracer, "_finish_span",
                            lambda self, span: finished.append(span.name))
        # nor does it count the pre-rules' scans
        monkeypatch.setattr(engine_mod.text_rules, "counting_passes",
                            lambda: reads.append("counter"))
        assert tracing.current_context() is None
        # 11 groups, one flush; the last group is one document of 105
        # tokens, streamed through seven chunk programs of 16
        docs = issues([3] * 40 + [50])
        programs = []
        fwd = engine._fwd
        monkeypatch.setattr(engine, "_fwd", lambda b, l: programs.append(
            (b, l)) or fwd(b, l))
        rows = engine.embed_issues(docs, scheduler="groups")
        assert rows.shape == (41, engine.embed_dim)
        assert finished == []
        assert len(programs) == 10 + 7
        # two reads a group and two a flush, none per document, none per
        # preparation slab and none per chunk program
        assert len(reads) == 2 * 11 + 2 < len(docs)

    def test_ambient_trace_gets_the_same_spans(self, engine):
        tracer = Tracer()
        with tracer.span("request"):
            engine.embed_issues(issues([3, 20, 6, 2, 9]), scheduler="groups")
        names = [s["name"] for s in tracer.traces()[0]["spans"]]
        assert names.count("engine.text_rules") == 5
        assert names.count("engine.group") == 2
        assert names.count("engine.finalize") == 1


def group_under_a_trace(engine, lengths):
    """One group straight into ``_embed_group_device`` under a root span
    of its own, the way ``_embed_groups`` hands it the group's first
    traced document: the group's counts and its ``engine.program`` spans
    in the order they were recorded."""
    tracer = Tracer()
    root = tracer.start_span("bench.doc")
    rng = np.random.RandomState(7)
    _, counts = engine._embed_group_device(
        [rng.randint(20, 150, n).astype(np.int32) for n in lengths],
        None, root.context)
    root.end()
    (trace,) = tracer.traces()
    return counts, [s for s in trace["spans"]
                    if s["name"] == "engine.program"]


class TestProgramSpans:
    """One ``engine.program`` a chunk program (PR 34), a child of its
    group's ``engine.group``: the shape the program ran at and what it
    held, so that a capture's ``jit_fwd_b<rows>_l<bucket>`` modules can be
    laid against them."""

    @pytest.mark.parametrize("lengths,programs", [
        # (rows, bucket, valid_tokens) of each chunk program, by hand
        ([5, 12, 20, 40], [(4, 16, 5 + 12 + 16 + 16), (2, 16, 4 + 16),
                           (1, 16, 8)]),
        ([1, 2, 3, 4], [(4, 8, 10)]),
        ([33, 34, 35, 36], [(4, 16, 64), (4, 16, 64), (4, 16, 1 + 2 + 3 + 4)]),
        ([9, 40], [(4, 16, 9 + 16), (1, 16, 16), (1, 16, 8)]),
    ], ids=["narrowed", "single_chunk", "all_alive", "partial"])
    def test_one_span_a_chunk_program_with_the_hand_counts(
            self, engine, lengths, programs):
        counts, spans = group_under_a_trace(engine, lengths)
        got = [s["attrs"] for s in spans]
        assert [(a["rows"], a["bucket"], a["valid_tokens"]) for a in got] \
            == programs
        for a in got:
            # a shape's first call also says what it paid to compile
            # (tests/test_compile_ledger.py)
            assert set(a) - {"compile_s"} == {
                "rows", "batch", "bucket", "valid_tokens", "lane_steps"}
            assert a["batch"] == B
            assert a["lane_steps"] == a["rows"] * a["bucket"]
        # the group's sums are the sums over its programs; the k-th
        # program's rows have reached (k + 1) buckets of positions
        assert counts["chunks"] == len(got)
        assert counts["lane_steps_run"] == sum(a["lane_steps"] for a in got)
        assert counts["valid_tokens"] == sum(a["valid_tokens"] for a in got)
        assert counts["cache_steps_run"] == sum(
            a["lane_steps"] * (k + 1) for k, a in enumerate(got))
        assert counts["row_chunks_dropped"] == sum(
            a["batch"] - a["rows"] for a in got)

    def test_a_program_lies_inside_its_groups_span_on_its_groups_trace(
            self, engine):
        spans, _ = traced_call(
            engine, issues([2, 30, 5, 9, 1, 14, 3, 40, 7, 4, 50, 22]))
        groups = spans["engine.group"]
        assert len(groups) == 3
        assert len(spans["engine.program"]) == sum(
            g["attrs"]["chunks"] for g in groups) > len(groups)
        for g in groups:
            mine = sorted((p for p in spans["engine.program"]
                           if p["doc"] == g["doc"]), key=lambda p: p["lo"])
            assert len(mine) == g["attrs"]["chunks"]
            assert mine[0]["attrs"]["rows"] == B
            # rendered times are rounded to 1 us
            assert g["lo"] - 5e-6 <= mine[0]["lo"]
            assert mine[-1]["hi"] <= g["hi"] + 5e-6
            for a, b in zip(mine, mine[1:]):
                assert b["lo"] >= a["hi"] - 5e-6
                assert b["attrs"]["rows"] <= a["attrs"]["rows"]
            # the group has self time: the blocks are filled outside them
            assert sum(p["hi"] - p["lo"] for p in mine) < g["hi"] - g["lo"]
            assert g["attrs"]["lane_steps_run"] == sum(
                p["attrs"]["lane_steps"] for p in mine)
            assert g["attrs"]["valid_tokens"] == sum(
                p["attrs"]["valid_tokens"] for p in mine)

    def test_a_group_without_a_traced_document_records_no_program(
            self, engine):
        _, counts = engine._embed_group_device(
            [np.arange(20, 60, dtype=np.int32)], [], None)
        assert counts["chunks"] == 3  # and nothing raised without a context

    @pytest.mark.parametrize("shape", [(4, 8), (2, 16)], ids=str)
    def test_a_compiled_forward_is_named_by_its_shape(self, engine, shape):
        b, l = shape
        lowered = engine._fwd(b, l).lower(
            engine._enc_params, np.zeros((b, l), np.int32),
            np.zeros((b,), np.int32),
            tuple(jax.tree.leaves(engine.encoder.init_states(b, l))),
            engine._init_pool_state(b))
        names = re.findall(r"module @(\w+)", lowered.as_text())
        assert names == [f"jit_fwd_b{b}_l{l}"]
        # every reader of the benchmark finds the forwards by "fwd"
        assert "fwd" in names[0] and "narrow" not in names[0]

class TestStreamedGroups:
    """A call of more than ``B + B // 4`` documents is prepared shortest
    first (by raw size) and its groups go out as they fill."""

    LOOKAHEAD = B + B // 4
    LENGTHS = [2, 30, 5, 9, 1, 14, 3, 40, 7, 4, 22, 11, 6, 17]  # 4 groups

    def test_lane_steps_are_the_sorted_slabs_when_the_proxy_orders_well(
            self, engine):
        spans, _ = traced_call(engine, worded(self.LENGTHS))
        n_tokens = [s["attrs"]["n_tokens"] for s in spans["engine.tokenize"]]
        groups = sorted(spans["engine.group"], key=lambda s: s["lo"])
        assert lane_steps_of(groups) == sorted_slab_lane_steps(n_tokens)
        assert lane_steps_of(groups, "lane_steps_run") \
            == sorted_slab_lane_steps(n_tokens, run=True)
        assert [g["attrs"]["late_docs"] for g in groups] == [0, 0, 0, 0]
        assert [g["attrs"]["rows"] for g in groups] == [4, 4, 4, 2]

    def test_an_adversarial_proxy_costs_padding_within_a_bound(self, engine):
        spans, _ = traced_call(engine, worded(self.LENGTHS, adversarial=True))
        n_tokens = [s["attrs"]["n_tokens"] for s in spans["engine.tokenize"]]
        groups = sorted(spans["engine.group"], key=lambda s: s["lo"])
        lanes = lane_steps_of(groups)
        # the bound: every group padded as the call's longest document is
        assert sorted_slab_lane_steps(n_tokens) < lanes <= len(groups) * \
            sorted_slab_lane_steps([max(n_tokens)])
        # rows that leave early take lane-steps off that, whatever the
        # order was (an order that spreads the long documents over the
        # groups can even run fewer than the sorted slabs do)
        assert sum(n_tokens) < lane_steps_of(groups, "lane_steps_run") \
            == lanes - BUCKETS[-1] * lane_steps_of(
                groups, "row_chunks_dropped") < lanes
        assert groups[0]["attrs"]["late_docs"] == 0
        assert sum(g["attrs"]["late_docs"] for g in groups) > 0
        assert sum(g["attrs"]["valid_tokens"] for g in groups) == sum(n_tokens)

    def test_overlapped_tokens_count_from_the_first_enqueue(self, engine):
        spans, _ = traced_call(engine, worded(self.LENGTHS))
        first_group = min(s["lo"] for s in spans["engine.group"])
        toks = sorted(spans["engine.tokenize"], key=lambda s: s["lo"])
        for k, s in enumerate(toks):
            a = s["attrs"]
            before = k < self.LOOKAHEAD
            assert (s["hi"] <= first_group + 5e-6) == before
            assert a["n_tokens_overlapped"] == (0 if before else a["n_tokens"])
        # shortest first: the raw size ordered the preparation
        assert [s["attrs"]["n_tokens"] for s in toks] == sorted(
            s["attrs"]["n_tokens"] for s in toks)

    @pytest.mark.parametrize("n", [1, B, B + B // 4],
                             ids=["one", "batch", "lookahead"])
    def test_a_call_that_never_fills_the_buffer_is_prepared_whole(
            self, engine, n):
        spans, _ = traced_call(engine, worded(self.LENGTHS[:n]))
        groups = spans["engine.group"]
        assert len(spans["engine.text_rules"]) == n
        assert len(spans["engine.tokenize"]) == n
        assert len(spans["engine.group_embed"]) == n
        assert len(groups) == -(-n // B)
        assert len(spans["engine.finalize"]) == 1
        # all of the call's host work before its first group, one
        # group_embed interval for every document, as it always was
        assert max(s["hi"] for s in spans["engine.tokenize"]) \
            <= min(g["lo"] for g in groups) + 5e-6
        embed_from = [s["lo"] for s in spans["engine.group_embed"]]
        assert max(embed_from) - min(embed_from) < 5e-6
        assert all(s["attrs"]["n_tokens_overlapped"] == 0
                   for s in spans["engine.tokenize"])
        assert all(g["attrs"]["late_docs"] == 0 for g in groups)
        n_tokens = [s["attrs"]["n_tokens"] for s in spans["engine.tokenize"]]
        assert lane_steps_of(groups) == sorted_slab_lane_steps(n_tokens)
        assert lane_steps_of(groups, "lane_steps_run") \
            == sorted_slab_lane_steps(n_tokens, run=True)

    def test_ids_fed_in_true_length_order_are_never_late(self, engine):
        rng = np.random.RandomState(5)
        seqs = [rng.randint(20, 150, n).astype(np.int32)
                for n in rng.randint(1, 60, 23)]
        tracer = Tracer()
        with tracer.span("request"):
            engine.embed_ids_batch(
                seqs, scheduler="groups",
                ctxs=[tracing.current_context()] * len(seqs))
        groups = [s["attrs"] for s in tracer.traces()[0]["spans"]
                  if s["name"] == "engine.group"]
        assert len(groups) == 6
        assert lane_steps_of(groups) \
            == sorted_slab_lane_steps([len(s) for s in seqs])
        assert lane_steps_of(groups, "lane_steps_run") \
            == sorted_slab_lane_steps([len(s) for s in seqs], run=True) \
            < lane_steps_of(groups)
        assert all(g["late_docs"] == 0 for g in groups)


class TestFitTraces:
    def test_every_dispatch_is_delivered_as_it_finishes(self, monkeypatch):
        monkeypatch.setattr(tracing, "MAX_SPANS_PER_TRACE", 8)
        tracer = Tracer()
        monkeypatch.setattr(tracing, "_default", tracer)
        k = 2
        mesh = make_mesh({"data": 1}, devices=jax.devices()[:1])
        tcfg = TrainConfig(batch_size=8, bptt=6, lr=5e-3, cycle_len=1,
                           steps_per_dispatch=k)
        trainer = LMTrainer(tiny_model(), tcfg, mesh=mesh, steps_per_epoch=41)
        dl = LMStreamLoader(repeating_corpus(n=8 * (6 * 41 + 1)), 8, 6,
                            shuffle_offsets=False)
        vl = LMStreamLoader(repeating_corpus(n=8 * (6 * 4 + 1), seed=1), 8, 6,
                            shuffle_offsets=False)
        windows = len(dl)
        assert windows // k >= 20 and windows % k == 1

        got = []
        tracer.on_trace(got.append)
        late = []

        class Watch:
            seen = 0

            def on_train_begin(self, tr): ...

            def on_step_end(self, step, metrics):
                # every step reported so far lies in a delivered trace
                Watch.seen += 1
                done = sum(k if t["root"] == "train.dispatch" else 1
                           for t in got
                           if t["root"] in ("train.dispatch", "train.step"))
                if done < Watch.seen:
                    late.append(step)

            def on_epoch_end(self, epoch, metrics, state, tr):
                Watch.at_epoch_end = [t["root"] for t in got]

            def on_train_end(self, h): ...

        trainer.fit(dl, vl, epochs=1, callbacks=[Watch()],
                    rng=jax.random.PRNGKey(0))
        assert late == []
        roots = [t["root"] for t in got]
        assert roots.count("train.dispatch") == windows // k
        assert roots.count("train.step") == 1     # the tail window
        assert roots.count("train.eval") == 1
        assert roots.count("train.epoch") == 1 and roots[-1] == "train.fit"
        # all but the epoch's and the fit's own record before the epoch ended
        assert Watch.at_epoch_end == roots[:-2]
        assert all(t["dropped_spans"] == 0 for t in got)
        fit = got[-1]
        for t in got[:-1]:
            attrs = t["spans"][0]["attrs"]
            assert attrs["fit_id"] == fit["trace_id"] and attrs["epoch"] == 0
        first = next(t for t in got if t["root"] == "train.dispatch")
        assert first["spans"][0]["attrs"]["compile"] is True
        assert first["spans"][0]["attrs"]["windows"] == k


def scope_names(lowered):
    """Every name on the op_name paths of a lowered program's location
    metadata: ``jit(fwd)/AWDLSTMEncoder/lstm_0/while/...``, and under a
    gradient ``jit(train_step)/transpose(jvp(loss))/...``."""
    text = lowered.as_text(debug_info=True)
    return {part for path in re.findall(r'"(jit\([^"]*)"', text)
            for part in re.split(r"[/()]", path)}


class TestNamedScopes:
    @pytest.mark.parametrize("qrnn", [False, True], ids=["lstm", "qrnn"])
    def test_forward_names_its_parts(self, qrnn):
        from code_intelligence_tpu.models import (
            AWDLSTMConfig, AWDLSTMEncoder, init_lstm_states)
        from code_intelligence_tpu.text import SPECIALS, Vocab

        cfg = AWDLSTMConfig(vocab_size=200, emb_sz=8, n_hid=12, n_layers=3,
                            qrnn=qrnn)
        states = init_lstm_states(cfg, B)
        params = AWDLSTMEncoder(cfg).init(
            {"params": jax.random.PRNGKey(0)}, np.zeros((1, 4), np.int32),
            init_lstm_states(cfg, 1))["params"]
        eng = engine_mod.InferenceEngine(
            params, cfg, Vocab(SPECIALS + [f"w{i}" for i in range(150)]),
            buckets=BUCKETS, batch_size=B)
        lowered = eng._fwd(B, 8).lower(
            eng._enc_params, np.zeros((B, 8), np.int32),
            np.zeros((B,), np.int32), tuple(jax.tree.leaves(states)),
            eng._init_pool_state(B))
        kind = "qrnn" if qrnn else "lstm"
        assert {"embedding", "pool", f"{kind}_0", f"{kind}_1",
                f"{kind}_2"} <= scope_names(lowered)

    def test_train_step_names_its_parts(self):
        mesh = make_mesh({"data": 1}, devices=jax.devices()[:1])
        trainer = LMTrainer(tiny_model(), TrainConfig(batch_size=8, bptt=6),
                            mesh=mesh)
        state = trainer.init_state(jax.random.PRNGKey(0), local_batch_size=8)
        x = np.zeros((8, 6), np.int32)
        with mesh:
            lowered = trainer._make_train_step().lower(state, x, x)
        assert {"embedding", "lstm_0", "lstm_1", "decoder", "loss",
                "optimizer"} <= scope_names(lowered)
