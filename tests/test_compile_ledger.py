"""The process's ONE compile ledger (PR 49): ``flight_recorder.
XLAAccountant`` fed by ``jax.monitoring``. Every compile of the process
is a named record on the wall clock, a stage at a time (``trace`` |
``lower`` | ``compile``), with what the persistent cache said of it; an
``instrument()``-ed step's ahead-of-time compile claims the listener's
entry instead of adding one; ``CompileWatch`` and ``recompile_guard``
read the same ledger; and the span that paid for a compile says so
(``compile_s`` on ``engine.program`` and ``train.dispatch``)."""

import json
import os
import subprocess
import sys
import threading
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_program_spans import B, BUCKETS, group_under_a_trace
from test_slot_scheduler import make_engine
from test_training import repeating_corpus, tiny_model

from code_intelligence_tpu.analysis import runtime as audit
from code_intelligence_tpu.data import LMStreamLoader
from code_intelligence_tpu.parallel import make_mesh
from code_intelligence_tpu.training import LMTrainer, TrainConfig
from code_intelligence_tpu.utils import flight_recorder, tracing
from code_intelligence_tpu.utils.flight_recorder import (
    XLAAccountant, debug_flight_response, get_accountant, union_seconds)
from code_intelligence_tpu.utils.metrics import Registry
from code_intelligence_tpu.utils.tracing import Tracer

ROOT = Path(__file__).resolve().parents[1]
STAGES = ("trace", "lower", "compile")
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@pytest.fixture
def acct():
    """A private, listening accountant: the global one holds every
    compile of the test process."""
    a = XLAAccountant()
    assert a.listen() and a.listen()  # idempotent
    return a


def fresh(name, c=None):
    """A jitted function nothing has traced yet, under ``name``; a new
    constant makes a program no cache has seen."""
    c = time.time() if c is None else c

    def f(x):
        return jnp.tanh(x) * c

    f.__name__ = f.__qualname__ = name
    return jax.jit(f)


def of(records, fn):
    return [r for r in records if r["fn"] == fn]


class TestStageRecords:
    def test_a_jitted_call_leaves_three_named_records_inside_its_interval(
            self, acct):
        f = fresh("fwd_b2_l8")
        x = jnp.ones(3)
        t0 = time.time()
        np.asarray(f(x))
        t1 = time.time()
        mine = of(acct.stage_records(), "fwd_b2_l8")
        assert [r["stage"] for r in mine] == list(STAGES)
        # ordered, on the wall clock, inside the call
        edges = [t for r in mine for t in (r["start_unix"], r["end_unix"])]
        assert edges == sorted(edges)
        assert t0 <= edges[0] and edges[-1] <= t1
        assert {r["thread"] for r in mine} == {threading.get_ident()}
        assert mine[-1]["cache"] in ("hit", "miss", "off")
        assert mine[-1]["retrieval_s"] >= 0.0
        # a warmed call adds nothing
        mark = acct.stages_mark()
        np.asarray(f(x))
        assert acct.stages_mark() == mark and acct.stage_records(mark) == []

    def test_one_entry_a_compile_with_its_own_stage_seconds(self, acct):
        mark = acct.compiles_mark()
        np.asarray(fresh("fwd_b4_l16")(jnp.ones(5)))
        (c,) = of(acct.report(mark), "fwd_b4_l16")
        recs = {r["stage"]: r["end_unix"] - r["start_unix"]
                for r in of(acct.stage_records(), "fwd_b4_l16")}
        assert c["stage_s"] == {s: pytest.approx(recs[s], abs=2e-6)
                                for s in STAGES}
        assert c["compile_seconds"] == pytest.approx(sum(recs.values()),
                                                     abs=1e-5)
        assert c["cache"] in ("hit", "miss", "off")
        assert (c["shape"], c["flops"], c["hbm_bytes"]) == ("", 0.0, 0)
        assert acct.count("fwd_b4_l16") == 1
        assert acct.compiles_mark() >= mark + 1

    def test_a_function_traced_inside_another_is_part_of_its_record(
            self, acct):
        inner = fresh("inner_fn")

        def outer(x):
            return inner(x) + 1

        outer.__name__ = outer.__qualname__ = "outer_fn"
        mark = acct.stages_mark()
        np.asarray(jax.jit(outer)(jnp.ones(7)))
        recs = acct.stage_records(mark)
        # tanh, multiply, inner_fn, add: each a jitted function traced
        # inside outer_fn's tracing, none a record of its own
        assert [(r["fn"], r["stage"]) for r in recs
                if r["fn"] not in ("convert_element_type",
                                   "broadcast_in_dim")] \
            == [("outer_fn", s) for s in STAGES]
        assert acct.count("inner_fn") == 0 and acct.count("outer_fn") == 1

    def test_only_the_traces_a_record_contained_are_folded_into_it(self):
        a = XLAAccountant()
        a._on_stage(TRACE_EVENT, 5.0, 6.0, fun_name="before")
        a._on_stage(TRACE_EVENT, 10.5, 10.7, fun_name="multiply")
        t = threading.Thread(target=a._on_stage, args=(
            TRACE_EVENT, 10.6, 10.8), kwargs={"fun_name": "elsewhere"})
        t.start()
        t.join()
        a._on_stage(TRACE_EVENT, 11.0, 11.5, fun_name="inner")
        a._on_stage(TRACE_EVENT, 11.6, 11.8, fun_name="add")
        a._on_stage(TRACE_EVENT, 10.0, 12.0, fun_name="outer")
        # what ran on this thread since `outer` began went into it; an
        # earlier trace, and another thread's (and what it shields), stay
        assert [r["fn"] for r in a.stage_records()] == [
            "before", "multiply", "elsewhere", "outer"]
        assert [r["seq"] for r in a.stage_records()] == [1, 2, 3, 6]
        assert [r["fn"] for r in a.stage_records(since=3)] == ["outer"]
        assert a.stages_mark() == 6
        # lowering traces too (a jax.random call lowers through
        # threefry's adds and xors, by the thousand): into the lowering
        a._on_stage(LOWER_EVENT, 12.0, 12.1, fun_name="jit(small)")
        a._on_stage(COMPILE_EVENT, 12.1, 12.2, fun_name="jit(small)")
        for i in range(50):
            a._on_stage(TRACE_EVENT, 12.5 + i / 100, 12.501 + i / 100,
                        fun_name="bitwise_xor")
        a._on_stage(LOWER_EVENT, 12.3, 14.0, fun_name="jit(outer)")
        a._on_stage(COMPILE_EVENT, 14.0, 15.0, fun_name="jit(outer)")
        assert [(r["fn"], r["stage"]) for r in a.stage_records(since=6)] == [
            ("small", "lower"), ("small", "compile"),
            ("outer", "lower"), ("outer", "compile")]
        # and the program's entry still finds its own trace and lowering
        assert a.report()[-1]["stage_s"] == {
            "trace": 2.0, "lower": pytest.approx(1.7), "compile": 1.0}

    def test_both_rings_are_bounded_and_marks_outlive_them(self):
        a = XLAAccountant(capacity=8)
        for i in range(20):
            a._on_stage(TRACE_EVENT, 100.0 + i, 100.5 + i, fun_name=f"f{i}")
            a._on_stage(COMPILE_EVENT, 101.0 + i, 101.5 + i,
                        fun_name=f"jit(f{i})")
        assert a.stages_mark() == 40 and a.compiles_mark() == 20
        assert len(a.stage_records()) == 8 and len(a.report()) == 8
        assert [c["fn"] for c in a.report()] == [f"f{i}"
                                                 for i in range(12, 20)]
        # a mark taken before the ring turned over gives what is left
        assert len(a.stage_records(since=4)) == 8
        assert [r["fn"] for r in a.stage_records(since=38)] == ["f19", "f19"]
        assert [c["seq"] for c in a.report(since=17)] == [18, 19, 20]
        assert a.report(since=20) == [] and a.stage_records(since=40) == []
        assert a.count("f0") == 1  # counted ever, retained or not

    @pytest.mark.parametrize("name,want", [
        ("jit(fwd_b16_l512)", "fwd_b16_l512"), ("fwd_b16_l512", "fwd_b16_l512"),
        ("jit(<lambda>)", "<lambda>"), ("", ""),
    ])
    def test_the_three_stages_of_a_program_share_a_key(self, name, want):
        a = XLAAccountant()
        a._on_stage(COMPILE_EVENT, 1.0, 2.0, fun_name=name)
        assert [r["fn"] for r in a.stage_records()] == [want]

    def test_other_events_are_not_stage_records(self):
        a = XLAAccountant()
        a._on_stage("/jax/core/something_else", 1.0, 2.0, fun_name="f")
        a._on_duration("/jax/core/something_else", 0.5)
        a._on_cache_event("/jax/compilation_cache/tasks_using_cache")
        assert a.stage_records() == [] and a.report() == []

    @pytest.mark.parametrize("intervals,want", [
        ([], 0.0),
        ([(1.0, 2.0)], 1.0),
        ([(1.0, 4.0), (2.0, 3.0)], 3.0),              # nested: once
        ([(1.0, 3.0), (2.0, 5.0)], 4.0),              # overlapping
        ([(5.0, 6.0), (1.0, 2.0)], 2.0),              # apart, any order
        ([(1.0, 2.0), (1.0, 2.0), (2.0, 2.5)], 1.5),  # twice, touching
    ])
    def test_union_seconds(self, intervals, want):
        assert union_seconds(intervals) == pytest.approx(want)


class TestCacheVerdict:
    @pytest.mark.parametrize("events,want", [
        ([], ("off", 0.0)),
        (["/jax/compilation_cache/compile_requests_use_cache"], ("off", 0.0)),
        (["/jax/compilation_cache/cache_misses"], ("miss", 0.0)),
        (["/jax/compilation_cache/cache_hits"], ("hit", 0.25)),
    ], ids=["no-event", "asked-only", "written", "found"])
    def test_the_cache_event_before_the_backend_stage_names_it(self, events,
                                                               want):
        a = XLAAccountant()
        for e in events:
            a._on_cache_event(e)
            if e.endswith("cache_hits"):
                a._on_duration(
                    "/jax/compilation_cache/cache_retrieval_time_sec", 0.25)
        a._on_stage(COMPILE_EVENT, 1.0, 2.0, fun_name="jit(f)")
        # ... and is spent: the next program starts from nothing
        a._on_stage(COMPILE_EVENT, 3.0, 4.0, fun_name="jit(g)")
        f, g = a.report()
        assert (f["cache"], f["retrieval_s"]) == want
        assert (g["cache"], g["retrieval_s"]) == ("off", 0.0)

    def test_a_verdict_belongs_to_the_thread_that_heard_it(self):
        a = XLAAccountant()
        a._on_cache_event("/jax/compilation_cache/cache_hits")
        t = threading.Thread(target=a._on_stage, args=(
            COMPILE_EVENT, 1.0, 2.0), kwargs={"fun_name": "jit(other)"})
        t.start()
        t.join()
        a._on_stage(COMPILE_EVENT, 1.0, 2.0, fun_name="jit(mine)")
        assert {c["fn"]: c["cache"] for c in a.report()} == {
            "other": "off", "mine": "hit"}

    def test_first_compile_misses_and_the_second_is_found(self, tmp_path):
        """A persistent cache of its own, so a process of its own."""
        code = """
import json, time, jax, jax.numpy as jnp
from code_intelligence_tpu.utils import flight_recorder
acct = flight_recorder.get_accountant()
assert acct.listen()
def fwd_b2_l8(x):
    return jnp.tanh(x) * 3.0
f = jax.jit(fwd_b2_l8)
jax.device_get(f(jnp.ones(3)))
jax.clear_caches()
jax.device_get(f(jnp.ones(3)))
print(json.dumps([c for c in acct.report() if c["fn"] == "fwd_b2_l8"]))
"""
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
                   JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
                   JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="-1",
                   PYTHONPATH=str(ROOT))
        env.pop("XLA_FLAGS", None)
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=300)
        assert out.returncode == 0, out.stderr[-2000:]
        first, second = json.loads(out.stdout.strip().splitlines()[-1])
        assert (first["cache"], first["retrieval_s"]) == ("miss", 0.0)
        assert second["cache"] == "hit" and second["retrieval_s"] > 0
        # the trace and the lowering are paid again, whatever the cache holds
        assert second["stage_s"]["trace"] > 0 and second["stage_s"]["lower"] > 0


class TestInstrumentedCompileClaimsItsEntry:
    def test_one_entry_with_flops_hbm_and_stage_seconds(self, acct):
        reg = Registry()
        acct.bind_registry(reg)

        def matmul(x, y):
            return x @ y

        g = acct.wrap(jax.jit(matmul), "unit.matmul")
        x = np.ones((32, 32), np.float32)
        mark = acct.compiles_mark()
        g(x, x)
        g(x, x)
        named = [c for c in acct.report(mark)
                 if c["fn"] in ("unit.matmul", "matmul")]
        (c,) = named
        assert c["fn"] == "unit.matmul" and c["program"] == "matmul"
        assert c["flops"] > 0 and c["hbm_bytes"] > 0 and "32x32" in c["shape"]
        assert all(c["stage_s"][s] > 0 for s in STAGES)
        assert c["compile_seconds"] >= c["stage_s"]["compile"]
        assert (acct.count("unit.matmul"), acct.count("matmul")) == (1, 0)
        text = reg.render()
        assert 'compiles_total{fn="unit.matmul"} 1.0' in text
        assert 'compiles_total{fn="matmul"}' not in text
        assert 'compile_seconds{fn="unit.matmul"' in text
        assert 'compiled_hbm_bytes{fn="unit.matmul"' in text

    def test_without_a_listener_the_entry_is_appended_as_before(self):
        a = XLAAccountant()  # never listens
        g = a.wrap(jax.jit(lambda x: x * 2 + 1), "unit.quiet")
        g(np.arange(8, dtype=np.float32))
        (c,) = a.report()
        assert c["fn"] == "unit.quiet" and "program" not in c
        assert c["compile_seconds"] > 0 and a.stage_records() == []

    def test_misses_are_counted_by_function(self):
        a, reg = XLAAccountant(), Registry()
        a.bind_registry(reg)
        for fn, event in [("f", "cache_misses"), ("f", "cache_hits"),
                          ("g", "cache_misses"), ("f", "cache_misses")]:
            a._on_cache_event("/jax/compilation_cache/" + event)
            a._on_stage(COMPILE_EVENT, 1.0, 2.0, fun_name=f"jit({fn})")
        text = reg.render()
        assert 'compile_cache_misses_total{fn="f"} 2.0' in text
        assert 'compile_cache_misses_total{fn="g"} 1.0' in text
        assert 'compiles_total{fn="f"} 3.0' in text
        # a registry bound late is told everything that was retained
        late = Registry()
        a.registry = None
        a.bind_registry(late)
        assert 'compile_cache_misses_total{fn="f"} 2.0' in late.render()

    def test_debug_flight_serves_every_program(self, acct):
        np.asarray(fresh("fwd_b8_l32")(jnp.ones(9)))
        code, body, _ = debug_flight_response(None, acct)
        compiles = json.loads(body)["compiles"]
        (c,) = of(compiles, "fwd_b8_l32")
        assert code == 200 and set(c["stage_s"]) == set(STAGES)


class TestAnObserverNeverADependency:
    def test_a_listener_that_raises_is_swallowed(self, acct, monkeypatch):
        def boom(*a, **kw):
            raise RuntimeError("listener died")

        for name in ("_on_stage", "_on_cache_event", "_on_duration"):
            monkeypatch.setattr(acct, name, boom)
        got = fresh("fwd_b2_l64")(jnp.ones(4))
        assert np.isfinite(np.asarray(got)).all()
        assert acct.stage_records() == []

    def test_disabled_by_the_environment(self, monkeypatch):
        monkeypatch.setenv("CI_TPU_NO_XLA_ACCOUNTING", "1")
        a = XLAAccountant()
        assert a.listen() is False
        np.asarray(fresh("fwd_b2_l128")(jnp.ones(4)))
        assert a.stage_records() == [] and a.report() == []

    def test_without_jax_nothing_is_registered(self, monkeypatch):
        monkeypatch.setitem(sys.modules, "jax", None)  # import jax fails
        a = XLAAccountant()
        assert a.listen() is False

    def test_a_dead_accountant_is_forgotten(self):
        a = XLAAccountant()
        a.listen()
        n = len(flight_recorder._listening)
        del a
        b = XLAAccountant()
        b.listen()
        assert len(flight_recorder._listening) <= n

    def test_the_global_accountant_listens_once_an_engine_exists(self):
        make_engine(batch_size=B, buckets=BUCKETS)
        assert any(ref() is get_accountant()
                   for ref in flight_recorder._listening)


class TestTheSpanThatPaidSaysSo:
    def test_engine_program_on_a_shapes_first_traced_call_only(self):
        engine = make_engine(batch_size=B, buckets=BUCKETS)
        acct = get_accountant()
        mark = acct.stages_mark()
        _, first = group_under_a_trace(engine, [1, 2, 3, 4])
        _, again = group_under_a_trace(engine, [1, 2, 3, 4])
        (a,), (b,) = [s["attrs"] for s in first], [s["attrs"] for s in again]
        assert 0 < a["compile_s"] <= first[0]["duration_s"]
        assert "compile_s" not in b
        # the forward is in the ledger by its shape name, once, three stages
        name = f"fwd_b{B}_l{BUCKETS[0]}"
        assert [r["stage"] for r in of(acct.stage_records(mark), name)] \
            == list(STAGES)
        assert len(of(acct.report(), name)) >= 1

    def test_an_untraced_call_reads_no_ledger(self, monkeypatch):
        engine = make_engine(batch_size=B, buckets=BUCKETS)

        def never(*a, **kw):
            raise AssertionError("the untraced branch touched the ledger")

        monkeypatch.setattr(engine._compiles, "stages_mark", never)
        monkeypatch.setattr(engine._compiles, "compile_attrs", never)
        rng = np.random.RandomState(3)
        engine._embed_group_device(
            [rng.randint(20, 150, n).astype(np.int32) for n in (3, 5, 9)])

    def test_compile_attrs_counts_this_threads_records_once(self):
        a = XLAAccountant()
        assert a.compile_attrs(a.stages_mark()) == {}
        mark = a.stages_mark()
        a._on_stage(TRACE_EVENT, 11.0, 12.0, fun_name="inner")  # nested
        a._on_stage(TRACE_EVENT, 10.0, 13.0, fun_name="outer")
        a._on_stage(COMPILE_EVENT, 13.0, 15.0, fun_name="jit(outer)")
        t = threading.Thread(target=a._on_stage, args=(
            COMPILE_EVENT, 0.0, 100.0), kwargs={"fun_name": "jit(elsewhere)"})
        t.start()
        t.join()
        assert a.compile_attrs(mark) == {"compile_s": 5.0}
        assert a.compile_attrs(a.stages_mark()) == {}

    def test_train_dispatch_that_compiled(self, monkeypatch):
        tracer = Tracer()
        monkeypatch.setattr(tracing, "_default", tracer)
        got = []
        tracer.on_trace(got.append)
        k = 2
        mesh = make_mesh({"data": 1}, devices=jax.devices()[:1])
        tcfg = TrainConfig(batch_size=8, bptt=6, lr=5e-3, cycle_len=1,
                           steps_per_dispatch=k)
        trainer = LMTrainer(tiny_model(), tcfg, mesh=mesh, steps_per_epoch=6)
        dl = LMStreamLoader(repeating_corpus(n=8 * (6 * 6 + 1)), 8, 6,
                            shuffle_offsets=False)
        trainer.fit(dl, None, epochs=1, rng=jax.random.PRNGKey(0))
        dispatches = [t["spans"][0] for t in got
                      if t["root"] == "train.dispatch"]
        first, rest = dispatches[0], dispatches[1:]
        assert first["attrs"]["compile"] is True
        assert 0 < first["attrs"]["compile_s"] <= first["duration_s"]
        assert rest and all("compile_s" not in s["attrs"] for s in rest)
        # ONE entry for the step, under its instrumented name
        (c,) = [c for c in get_accountant().report()[-8:]
                if c["fn"] == "train.steps"][-1:]
        assert c["flops"] > 0 and c["stage_s"]["trace"] > 0

    def test_set_attrs_without_an_open_span_is_nothing(self):
        tracing.set_attrs(compile_s=1.0)  # no span on this thread
        tracer = Tracer()
        with tracer.span("outer") as outer:
            with tracer.span("inner") as inner:
                tracing.set_attrs(compile_s=2.0)
            tracing.set_attrs()
        assert inner.attrs == {"compile_s": 2.0} and outer.attrs == {}


class TestTheAuditsReadTheSameLedger:
    def test_compile_watch_names_the_stray_compile(self, acct):
        step = acct.wrap(jax.jit(lambda x: x * 2.0 + 1.0), "watched.step")
        x = jnp.ones((4, 4))
        stray = fresh("stray_program")
        y = jnp.ones(11)
        np.asarray(step(x))
        watch = audit.CompileWatch(fn="watched.step", accountant=acct)
        with pytest.raises(audit.CompileWatchViolation) as e:
            with watch.steady_state():
                np.asarray(step(x))
                np.asarray(stray(y))
        msg = str(e.value)
        assert "stray_program (trace " in msg and "lower " in msg \
            and "cache " in msg and "recompile(s) of" not in msg
        assert [c["fn"] for c in watch.stray_compiles] == ["stray_program"]
        assert watch.new_compiles == {}

    def test_compile_watch_still_names_the_watched_steps_recompile(self,
                                                                   acct):
        step = acct.wrap(jax.jit(lambda x: x * 2.0 + 1.0), "watched.step2")
        x, other = jnp.ones((4, 4)), jnp.ones((4, 5))
        np.asarray(step(x))
        watch = audit.CompileWatch(fn="watched.step2", accountant=acct)
        with pytest.raises(audit.CompileWatchViolation,
                           match=r"1 steady-state recompile\(s\) of "
                                 r"watched.step2 \[4x5@"):
            with watch.steady_state():
                np.asarray(step(other))
        assert watch.stray_compiles == []

    def test_a_warmed_scope_is_clean(self, acct):
        step = acct.wrap(jax.jit(lambda x: x * 2.0 + 1.0), "watched.step3")
        x = jnp.ones((4, 4))
        np.asarray(step(x))
        reg = Registry()
        watch = audit.CompileWatch(fn="watched.step3", accountant=acct,
                                   registry=reg)
        with watch.steady_state():
            for _ in range(3):
                x = step(x)
            np.asarray(x)
        assert watch.new_compiles == {} and watch.stray_compiles == []
        assert "jit_recompiles_total 1.0" in reg.render()

    @pytest.mark.parametrize("fn", ["plain_program", None],
                             ids=["by-name", "every-function"])
    def test_recompile_guard_sees_a_program_nobody_instrumented(self, acct,
                                                                fn):
        f = fresh("plain_program")
        with pytest.raises(audit.RecompileBudgetExceeded,
                           match="plain_program: 1 new compiled shape"):
            with audit.recompile_guard(fn=fn, budget=0, accountant=acct):
                np.asarray(f(jnp.ones(13)))
        with audit.recompile_guard(fn=fn, budget=0, accountant=acct):
            np.asarray(f(jnp.ones(13)))  # warmed: nothing new

    def test_the_runtime_audits_keep_no_listener_of_their_own(self):
        src = (ROOT / "code_intelligence_tpu" / "analysis"
               / "runtime.py").read_text()
        assert "register_event" not in src
        hits = [p.name for p in (ROOT / "code_intelligence_tpu").rglob("*.py")
                if "monitoring.register_event" in p.read_text()]
        assert hits == ["flight_recorder.py"]
