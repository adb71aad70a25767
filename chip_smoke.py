#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One command, no arguments, everything generated from seeds:

    python3 chip_smoke.py

Drives the two device hot paths through the entry points a user calls, at
the flagship widths (vocab 60,000, emb 800, hidden 2500, 4 layers, 2400-d
output), with random weights:

* **train** — ``training.cli.main`` on a seeded Zipf corpus, reference
  defaults (``--bs 104 --bptt 67 --bf16``), two dispatches of
  ``steps_per_dispatch`` windows and one validation pass, on the cell
  the train step chooses for itself (``training/loop.py::
  train_cell_is_resident``: on one chip in bf16 the weights-resident
  Pallas cell in all four layers, and the lowered step is checked for its
  Mosaic call). Loss finite at every step and lower at the end, one
  compile per step program, checkpoint and ``encoder_export/`` written.
  (The XLA scan trains under **multichip**, whose loss has to agree with
  this one inside ``TRAIN_BAND``.)
* **serve** — the real HTTP server (``serving.server.build_server`` +
  ``serve_forever``) from the export the train leg wrote, CLI defaults;
  mixed-length GitHub-shaped documents over ``POST /text``. Every row is
  compared with a plain f32 full forward of the same encoder
  (``jax.default_matmul_precision("highest")``), as is the carried state
  of one long document chunked through the slots. One compiled step
  shape, zero compilations after warm-up. Repeated with ``--scheduler
  ragged --lstm_pallas`` and ``--precision int8 --scheduler ragged
  --lstm_pallas``.
* **kernels** — every ``pallas_call`` in the repo, compiled by Mosaic at
  the flagship shape it serves or trains at, against its XLA reference.
* **multichip** (only with >= 4 devices) — the trainer under
  ``--data_parallel 4`` and ``--data_parallel 2 --model_parallel 2`` and
  the server under ``--scheduler ragged --mesh data=4``, with per-device
  evidence that every chip holds its shard.

Any leg that raises or whose assertion fails makes the exit code non-zero.
Without a TPU the script exits non-zero before doing any work. The last
line of stdout is ``{"ok": true, "device": {...}}``.

Everything runs in this one process: a process that has touched JAX holds
the chip, and a child that needed it would fail or hang.
"""

from __future__ import annotations

import dataclasses
import json
import math
import shutil
import socket
import sys
import tempfile
import threading
import time
import urllib.request
from collections import Counter
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path


@dataclasses.dataclass(frozen=True)
class Widths:
    """One model + job size. The defaults are the flagship the repo
    supports (``benchmark/configs/awd_lstm_flagship.json``, reference
    ``train.py:42-46``); tests/test_chip_smoke.py calls the legs at a tiny
    size on CPU."""

    vocab: int = 60000
    emb: int = 800
    hid: int = 2500
    layers: int = 4
    bs: int = 104
    bptt: int = 67
    steps_per_dispatch: int = 20
    train_dispatches: int = 2
    serve_batch: int = 32
    n_docs: int = 36  # served over HTTP; + a bulk call that forces refills


FLAGSHIP = Widths()

# Bands, stated; every band is atol = rtol. Served rows vs the f32
# "highest" reference: bf16 has 8 mantissa bits and the recurrence runs
# ~500 steps through 4 layers (the worst |delta| measured on the v5e is in
# CHANGES.md, PR 21); int8 is the 0.05 band of RUNBOOK §28.
SERVE_BAND = {"bf16": 0.03, "int8": 0.05}
# Carried state, chunked through the slots vs ONE pass of the same weights
# on the plain XLA path in the same compute dtype: h, and c as the next
# step's output sees it, tanh(c). Not the f32 reference and not raw c: a
# bf16 cell state stops growing at 256 (its ulp there is 2, so adding
# <= 1 rounds away) where the f32 one reached 510 on this barely-trained
# model, and between two bf16 paths a saturated unit sits a few ulps —
# 6 to 8 in absolute terms — apart (PR 21 chip runs). A property of the
# bf16 carry on every path, not of the scheduler (ROADMAP S12).
STATE_BAND = 0.05
# one chip (resident cell) vs a mesh (XLA scan): LM loss after the same 40
# bf16 steps from the same seed
TRAIN_BAND = 0.05
# kernels vs their f32 "highest" XLA reference, as a fraction of the
# reference's max magnitude (bf16 inputs, f32 accumulation in-kernel)
KERNEL_BAND = {"lstm": 0.03, "lstm_grad": 0.06, "lstm_int8": 0.05,
               "qrnn": 0.01, "qrnn_grad": 0.02}


def log(msg: str) -> None:
    print(f"[chip_smoke +{time.perf_counter() - _T0:7.1f}s] {msg}",
          flush=True)


_T0 = time.perf_counter()


class CacheCounter:
    """Persistent-compile-cache hits and misses, from jax.monitoring."""

    def __init__(self):
        from jax import monitoring

        self.hits = 0
        self.misses = 0
        monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


# ---------------------------------------------------------------------------
# data: a seeded corpus and seeded GitHub-shaped documents
# ---------------------------------------------------------------------------


def make_vocab(w: Widths):
    """Exactly ``w.vocab`` entries: the specials, then the synthetic
    generator's word types (real words at the head, pronounceable
    pseudo-words in the tail — each survives tokenization as one token)."""
    from code_intelligence_tpu.data.synthetic import (
        SyntheticConfig, SyntheticIssueGenerator)
    from code_intelligence_tpu.text import SPECIALS, Vocab

    n_words = max(w.vocab, 4000)  # the generator's floor; extras -> xxunk
    gen = SyntheticIssueGenerator(SyntheticConfig(
        vocab_size=n_words, seed=0,
        n_topics_words=min(2200, (n_words - 1500) // 11)))
    words = [x for x in gen.words if x not in SPECIALS]
    vocab = Vocab(list(SPECIALS) + words[: w.vocab - len(SPECIALS)])
    assert len(vocab) == w.vocab, (len(vocab), w.vocab)
    return vocab, gen


def write_corpus(corpus_dir: Path, w: Widths, vocab) -> int:
    """Train/valid corpora of Zipf-distributed ids (so the loss has
    somewhere to fall), sized for exactly ``train_dispatches`` scanned
    dispatches and one scanned validation dispatch — no tail windows, so
    each step program compiles once. Returns ``--max_tokens``."""
    import numpy as np

    from code_intelligence_tpu.data.corpus import CorpusWriter
    from code_intelligence_tpu.text import SPECIALS

    rng = np.random.RandomState(0)
    n_special = len(SPECIALS)
    ranks = np.arange(1, w.vocab - n_special + 1, dtype=np.float64)
    p = 1.0 / np.power(ranks + 2.7, 1.07)
    p /= p.sum()

    def n_tokens(windows: int) -> int:
        return windows * w.bptt * w.bs + 1

    sizes = {"train": n_tokens(w.steps_per_dispatch * w.train_dispatches),
             "valid": n_tokens(w.steps_per_dispatch)}
    for split, total in sizes.items():
        writer = CorpusWriter(corpus_dir / split)
        left = total
        while left > 0:
            n = min(left, int(rng.randint(40, 400)))
            ids = rng.choice(len(p), size=n, p=p).astype(np.int32) + n_special
            ids[0] = vocab.bos_id
            writer.add_document(ids)
            left -= n
        writer.finalize(vocab)
    return sizes["train"]


def make_issues(gen, n: int) -> list:
    """``n`` GitHub-shaped issues (markdown bodies: fences, lists, links,
    @users) of mixed length, from the shortest the wire allows (empty
    title and body) to several chunks."""
    issues = [{"title": i.title, "body": i.body} for i in gen.issues(0, n)]
    issues[0] = {"title": "", "body": ""}
    issues[1] = {"title": "crash", "body": ""}
    long_body = "\n\n".join(i.body for i in gen.issues(1000, 4))
    issues[2] = {"title": issues[2]["title"], "body": long_body}
    return issues


# ---------------------------------------------------------------------------
# train leg
# ---------------------------------------------------------------------------


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _get(url: str, timeout: float = 60.0) -> bytes:
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.read()


def leg_train(work: Path, w: Widths, corpus_dir: Path, max_tokens: int,
              name: str, extra_args: tuple = (),
              expect_mosaic: bool = False) -> dict:
    """``training.cli.main`` for two dispatches + one validation pass."""
    from code_intelligence_tpu.training import cli
    from code_intelligence_tpu.utils import flight_recorder

    model_dir = work / name
    port = _free_port()
    argv = [
        "--corpus_dir", str(corpus_dir), "--model_dir", str(model_dir),
        "--bs", str(w.bs), "--bptt", str(w.bptt), "--bf16",
        "--emb_sz", str(w.emb), "--n_hid", str(w.hid),
        "--n_layers", str(w.layers),
        "--steps_per_dispatch", str(w.steps_per_dispatch),
        "--max_tokens", str(max_tokens), "--metrics_port", str(port),
        *extra_args,
    ]
    if "--data_parallel" not in extra_args:
        argv += ["--data_parallel", "1"]  # one chip even on a 4-chip host
    accountant = flight_recorder.get_accountant()
    before = accountant.compiles_mark()
    t0 = time.perf_counter()
    summary = cli.main(argv)
    wall = time.perf_counter() - t0

    # per-step telemetry from the program's own flight recorder
    flight = json.loads(_get(f"http://127.0.0.1:{port}/debug/flight?n=4096"))
    steps = [r for r in flight["records"] if r.get("kind") == "train"]
    losses = [r["loss"] for r in steps]
    n_steps = w.steps_per_dispatch * w.train_dispatches
    assert len(losses) == n_steps, (len(losses), n_steps)
    # the recorder serves a non-finite value as None or a string
    assert all(isinstance(x, float) and math.isfinite(x) for x in losses), \
        losses
    first = sum(losses[:5]) / 5
    last = sum(losses[-5:]) / 5
    assert last < first, f"{name}: loss did not fall ({first} -> {last})"
    assert math.isfinite(summary["val_loss"]), summary
    ledger = accountant.report(before)  # this leg's XLA compiles
    # the instrumented steps (they carry a shape label): the ledger also
    # holds every other program the leg compiled, by jax's name for it
    compiles = dict(Counter(c["fn"] for c in ledger if c["shape"]))
    # two scanned dispatches, one scanned validation: each program once,
    # and never the single-window programs (no tail windows by sizing)
    assert compiles.get("train.steps") == 1, compiles
    assert compiles.get("eval.steps") == 1, compiles
    assert "train.step" not in compiles and "eval.step" not in compiles, \
        compiles
    export = model_dir / "encoder_export"
    for f in ("encoder_params.npz", "model_config.json", "vocab.json"):
        assert (export / f).exists(), f"missing {export / f}"
    assert any((model_dir / "ckpt").iterdir()), "no orbax checkpoint"
    if expect_mosaic:
        assert _lowered_train_step_has_mosaic(w), \
            f"{name}: the one-chip bf16 train step has no Mosaic custom call"
    out = {"name": name, "first_loss": round(first, 4),
           "last_loss": round(last, 4),
           "val_loss": round(summary["val_loss"], 4),
           "wall_s": round(wall, 1), "compiles": compiles,
           # the second (compile-free) dispatch, per window; informational
           "steady_step_s": round(steps[-1]["step_time_s"], 4),
           # XLA cost analysis of the compiled (under a mesh: partitioned,
           # so per-device) scanned train program
           "train_steps_flops": next(
               c["flops"] for c in ledger if c["fn"] == "train.steps"),
           "export": str(export)}
    log(f"train[{name}] {json.dumps(out)}")
    return out


def _lowered_train_step_has_mosaic(w: Widths) -> bool:
    """Lower (not compile) the one-chip bf16 train step at these widths
    and look for the Mosaic custom call: the step chooses its own cell
    (``training/loop.py::train_cell_is_resident``) and takes the scan for
    any layer ``fits_resident`` refuses, so the leg checks that the
    kernel is what it got."""
    import jax
    import jax.numpy as jnp

    from code_intelligence_tpu.models import AWDLSTMConfig
    from code_intelligence_tpu.parallel import make_mesh
    from code_intelligence_tpu.training import LMTrainer, TrainConfig

    cfg = AWDLSTMConfig(vocab_size=w.vocab, emb_sz=w.emb, n_hid=w.hid,
                        n_layers=w.layers, dtype=jnp.bfloat16)
    trainer = LMTrainer(
        cfg, TrainConfig(batch_size=w.bs, bptt=w.bptt),
        mesh=make_mesh({"data": 1}, devices=jax.devices()[:1]))
    state = jax.eval_shape(trainer.init_state, jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((w.bs, w.bptt), jnp.int32)
    with trainer.mesh:
        text = trainer._make_train_step().lower(state, x, x).as_text()
    return "tpu_custom_call" in text


# ---------------------------------------------------------------------------
# serve leg
# ---------------------------------------------------------------------------


class Reference:
    """Plain one-pass f32 forward of the exported encoder, every document
    a row of one padded batch (rows of a recurrent encoder are
    independent, and it is causal, so padding after a row's end cannot
    reach its valid prefix). Pooling is redone here in numpy — nothing is
    shared with the scheduler's pooling code under test."""

    def __init__(self, export_dir: Path, id_seqs: list, multiple: int):
        import jax
        import jax.numpy as jnp
        import numpy as np

        from code_intelligence_tpu.models import (
            AWDLSTMEncoder, init_lstm_states)
        from code_intelligence_tpu.training.checkpoint import load_encoder

        params, cfg, _ = load_encoder(export_dir)
        cfg = dataclasses.replace(cfg, dtype=jnp.float32,
                                  lstm_use_pallas=False, precision="f32")
        enc = AWDLSTMEncoder(cfg)
        longest = max(len(s) for s in id_seqs)
        self.full_len = -(-longest // multiple) * multiple
        # the state document: exactly full_len tokens (a whole number of
        # chunks AND pages), so the state the slots carry after its last
        # chunk is the state after its last token
        base = id_seqs[int(np.argmax([len(s) for s in id_seqs]))]
        self.state_ids = np.resize(base, self.full_len).astype(np.int32)
        seqs = list(id_seqs) + [self.state_ids]
        tokens = np.full((len(seqs), self.full_len), cfg.pad_id, np.int32)
        for r, s in enumerate(seqs):
            tokens[r, : len(s)] = s

        @jax.jit
        def fwd(p, toks):
            return enc.apply(
                {"params": p}, toks, init_lstm_states(cfg, toks.shape[0]),
                deterministic=True)[0]

        with jax.default_matmul_precision("highest"):
            raw = jax.device_get(fwd(params, tokens))
        rows = []
        for r, s in enumerate(seqs):  # numericalize never returns empty
            h = raw[r, : len(s)].astype(np.float64)
            rows.append(np.concatenate([h.mean(0), h.max(0), h[-1]]))
        self.rows = np.asarray(rows[:-1], np.float32)
        self.state_row = np.asarray(rows[-1], np.float32)


def _within(got, want, band: float):
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    err = np.abs(got - want)
    ok = bool(np.all(err <= band + band * np.abs(want)))
    return ok, float(err.max())


def _post_text(port: int, issue: dict):
    import numpy as np

    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/text", data=json.dumps(issue).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=300) as resp:
        return np.frombuffer(resp.read(), "<f4")


def leg_serve(export_dir: Path, w: Widths, issues: list, name: str,
              extra_args: tuple = (), band: float = SERVE_BAND["bf16"],
              reference: "Reference | None" = None,
              expect_mosaic: bool = False,
              mesh_devices: int = 0) -> "Reference":
    """The real server over real HTTP, from the export the train leg
    wrote. Returns the reference so later serve legs reuse it. With
    ``mesh_devices`` the step must be spread over that many devices."""
    import jax
    import numpy as np

    from code_intelligence_tpu.analysis.runtime import CompileWatch
    from code_intelligence_tpu.models import AWDLSTMEncoder, init_lstm_states
    from code_intelligence_tpu.serving import server

    t0 = time.perf_counter()
    srv = server.build_server([
        "--model_dir", str(export_dir), "--host", "127.0.0.1",
        "--port", "0", "--batch_size", str(w.serve_batch), *extra_args])
    t_warm = time.perf_counter() - t0
    port = srv.server_address[1]
    thread = threading.Thread(target=srv.serve_forever, daemon=True)
    thread.start()
    try:
        engine = srv.engine
        sched = engine.slot_scheduler(ragged=srv.scheduler == "ragged")
        id_seqs = [engine.numericalize(_issue_text(i)) for i in issues]
        if reference is None:
            reference = Reference(export_dir, id_seqs, multiple=64)
            log(f"reference: {len(id_seqs)} docs, lengths "
                f"{min(map(len, id_seqs))}..{max(map(len, id_seqs))}, "
                f"padded to {reference.full_len}")
        if expect_mosaic:
            assert _lowered_step_has_mosaic(sched), \
                f"{name}: served step has no Mosaic custom call"

        # -- over HTTP, after warm-up: nothing may compile ---------------
        half = len(issues) // 2
        watch = CompileWatch(fn=sched._step_name)
        with watch.steady_state():
            rows = [_post_text(port, i) for i in issues[:half]]
            with ThreadPoolExecutor(8) as pool:  # concurrent clients
                rows += list(pool.map(lambda i: _post_text(port, i),
                                      issues[half:]))
            health = _get(f"http://127.0.0.1:{port}/healthz")
            metrics = _get(f"http://127.0.0.1:{port}/metrics").decode()
        rows = np.stack(rows)
        assert rows.shape == (len(issues), 3 * w.emb), rows.shape
        assert np.isfinite(rows).all(), f"{name}: non-finite embedding"
        ok, worst = _within(rows, reference.rows, band)
        assert ok, f"{name}: HTTP rows off the reference by {worst} " \
                   f"(band {band})"
        assert health, "empty /healthz"
        for metric in ("slot_occupancy", "slot_steps_per_doc"):
            assert metric in metrics, f"/metrics lacks {metric}"
        assert sched.compiled_step_shapes() == 1, \
            sched.compiled_step_shapes()

        # -- one bulk call with more documents than slots: mid-drain
        # refills and mixed lengths in one step (what a batcher window
        # hands the engine). Its finish-batch gathers have new shapes, so
        # it runs outside the zero-compile window; the STEP must not
        # recompile.
        bulk_n = w.serve_batch + w.serve_batch // 2
        bulk = [issues[k % len(issues)] for k in range(bulk_n)]
        with srv.model_lock:
            got = engine.embed_issues(bulk, scheduler=srv.scheduler)
        want = np.stack([reference.rows[k % len(issues)]
                         for k in range(bulk_n)])
        ok, worst_bulk = _within(got, want, band)
        assert ok, f"{name}: bulk rows off by {worst_bulk} (band {band})"

        # -- carried state, chunked through the slots vs one pass of the
        # engine's own weights (quantized if it serves int8) on the plain
        # XLA scan
        plain = AWDLSTMEncoder(
            dataclasses.replace(engine.config, lstm_use_pallas=False))
        one_pass = jax.jit(lambda p, toks: plain.apply(
            p, toks, init_lstm_states(engine.config, 1),
            deterministic=True)[2])
        want_state = jax.device_get(jax.tree.leaves(
            one_pass(engine._enc_params, reference.state_ids[None])))
        with srv.model_lock:
            page0 = int(sched._slot_page[0]) if hasattr(
                sched, "_slot_page") else 0
            got_row = sched.embed_ids([reference.state_ids])[0]
            leaves = [leaf[page0] for leaf in sched._h_leaves]
        got_state = jax.device_get(leaves)
        ok, worst_row = _within(got_row, reference.state_row, band)
        assert ok, f"{name}: state doc row off by {worst_row}"
        worst_state = 0.0
        for k, (got_leaf, want_leaf) in enumerate(zip(got_state, want_state)):
            got_leaf = np.asarray(got_leaf, np.float32)
            want_leaf = np.asarray(want_leaf[0], np.float32)
            if k % 2:  # leaves are (h, c) per layer: c through tanh
                got_leaf, want_leaf = np.tanh(got_leaf), np.tanh(want_leaf)
            ok, err = _within(got_leaf, want_leaf, STATE_BAND)
            assert ok, (
                f"{name}: carried state leaf {k} (layer {k // 2}, "
                f"{'h' if k % 2 == 0 else 'tanh c'}) off by {err} "
                f"(band {STATE_BAND})")
            worst_state = max(worst_state, err)
        assert sched.compiled_step_shapes() == 1
        if mesh_devices:
            _check_mesh_spread(port, sched, mesh_devices, name)
        log(f"serve[{name}] " + json.dumps({
            "warmup_s": round(t_warm, 1), "docs": len(issues),
            "bulk_docs": bulk_n, "band": band,
            "worst_row": round(max(worst, worst_bulk, worst_row), 5),
            "worst_state": round(worst_state, 5),
            "steps": sched.steps_run,
            "tokenizer": "native" if engine.tokenizer._use_native
            else "python",
            "compiled_step_shapes": sched.compiled_step_shapes()}))
    finally:
        srv.shutdown()
        srv.server_close()
        thread.join(timeout=30)
    return reference


def _check_mesh_spread(port: int, sched, n: int, name: str) -> None:
    """Every device of the serve mesh holds its rows of the state arenas
    (the server's own /debug/memory ledger) and runs its share of the
    step (per-device flops of the partitioned step against the same
    step compiled for one device)."""
    mem = json.loads(_get(f"http://127.0.0.1:{port}/debug/memory"))
    arenas = {d: int(r["owners"].get("slots.state_arenas", 0))
              for d, r in sorted(mem["snapshot"]["devices"].items())}
    assert len(arenas) == n, arenas
    assert min(arenas.values()) > 0 and \
        max(arenas.values()) <= 1.25 * min(arenas.values()), \
        f"{name}: state arenas not spread over the mesh: {arenas}"
    one_chip = type(sched)(sched.engine, mesh=None).step_cost_analysis()
    share = sched.step_cost_analysis()["flops"] * n / one_chip["flops"]
    assert share <= 2.0, \
        f"{name}: each device runs {share:.2f}x its share of the step"
    log(f"serve[{name}] state-arena bytes/device {arenas}, per-device "
        f"flops x n / one-chip flops = {share:.3f}")


def _issue_text(issue: dict) -> str:
    from code_intelligence_tpu.text import build_issue_text

    return build_issue_text(issue.get("title", ""), issue.get("body", ""))


def _lowered_step_has_mosaic(sched) -> bool:
    import jax
    import jax.numpy as jnp

    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype)

    params = sched._params if sched.mesh is not None \
        else sched.engine._enc_params
    text = sched._step_raw.lower(
        jax.tree.map(sds, params),
        jax.ShapeDtypeStruct(sched._staging_shape, jnp.int32),
        jax.tree.map(sds, sched._h_leaves), sds(sched._pool)).as_text()
    return "tpu_custom_call" in text


# ---------------------------------------------------------------------------
# kernel leg
# ---------------------------------------------------------------------------


def _rel_err(got, want) -> float:
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-6))


def kernel_checks(w: Widths, expect_mosaic: bool = True) -> list:
    """``(name, band, check)`` for each ``pallas_call`` in the repo, at
    the shape it serves or trains at; ``check()`` returns the error
    against the XLA reference (f32, "highest" matmul precision) as a
    fraction of the reference's max magnitude. On the chip the wrappers
    compile through Mosaic (``expect_mosaic`` checks the lowering says
    so); the CPU test runs the same code in interpret mode at a tiny
    size."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from code_intelligence_tpu.ops import pallas_lstm, pallas_qrnn
    from code_intelligence_tpu.ops.lstm import lstm_layer
    from code_intelligence_tpu.ops.qrnn import forget_mult
    from code_intelligence_tpu.ops.quantize import quantize_symmetric

    bf16, f32 = jnp.bfloat16, jnp.float32
    PAGE = 16  # the ragged scheduler's page_len at the serve default

    def mosaic(fn, *args):
        if expect_mosaic:
            assert "tpu_custom_call" in jax.jit(fn).lower(*args).as_text(), \
                "no Mosaic custom call in the lowering"

    def lstm_inputs(B, T, H, in_dim, seed):
        rng = np.random.RandomState(seed)
        k = 1.0 / np.sqrt(H)
        return dict(
            x=jnp.asarray(rng.randn(B, T, in_dim), bf16),
            w_ih=jnp.asarray(rng.uniform(-k, k, (4 * H, in_dim)), bf16),
            w_hh=jnp.asarray(rng.uniform(-k, k, (4 * H, H)), bf16),
            bias=jnp.asarray(rng.uniform(-k, k, (4 * H,)), bf16),
            h0=jnp.asarray(rng.randn(B, H) * 0.1, bf16),
            c0=jnp.asarray(rng.randn(B, H) * 0.1, bf16))

    def layer_args(a):
        return (a["x"], (a["h0"], a["c0"]), a["w_ih"], a["w_hh"], a["bias"])

    def ref_layer(a):
        a = {k: v.astype(f32) for k, v in a.items()}
        with jax.default_matmul_precision("highest"):
            return lstm_layer(*layer_args(a))

    def ragged_valid(B, T, seed):
        v = np.random.RandomState(seed).randint(0, T + 1, B)
        v[0], v[1] = T, 0  # one full row, one exhausted row
        return jnp.asarray(v, jnp.int32)

    def lstm_fwd(H, in_dim):
        a = lstm_inputs(w.bs, w.bptt, H, in_dim, seed=H)
        mosaic(pallas_lstm.lstm_layer_fused, *layer_args(a))
        out, (h_t, c_t) = jax.jit(pallas_lstm.lstm_layer_fused)(
            *layer_args(a))
        r_out, (r_h, r_c) = jax.jit(ref_layer)(a)
        return max(_rel_err(out, r_out), _rel_err(h_t, r_h),
                   _rel_err(c_t, r_c))

    def lstm_train(H, in_dim):
        # forward with gate residuals + the Pallas adjoint, through the
        # custom_vjp the trainer differentiates
        a = lstm_inputs(w.bs, w.bptt, H, in_dim, seed=H)
        cot = jnp.asarray(
            np.random.RandomState(1).randn(w.bs, w.bptt, H), f32)
        order = ("x", "h0", "c0", "w_ih", "w_hh", "bias")

        def loss(fn, x, h0, c0, w_ih, w_hh, bias):
            o, (h, c) = fn(x, (h0, c0), w_ih, w_hh, bias)
            return jnp.sum(o.astype(f32) * cot) + jnp.sum(
                h.astype(f32)) + jnp.sum(c.astype(f32))

        def fused_loss(*p):
            return loss(pallas_lstm.lstm_layer_fused, *p)

        def ref_loss(*p):
            with jax.default_matmul_precision("highest"):
                return loss(lstm_layer, *p)

        grad = jax.grad(fused_loss, argnums=tuple(range(6)))
        mosaic(grad, *(a[k] for k in order))
        g_fused = jax.jit(grad)(*(a[k] for k in order))
        g_ref = jax.jit(jax.grad(ref_loss, argnums=tuple(range(6))))(
            *(a[k].astype(f32) for k in order))
        return max(_rel_err(g, r) for g, r in zip(g_fused, g_ref))

    def lstm_ragged(H, in_dim, int8):
        B, T = w.serve_batch, PAGE
        a = lstm_inputs(B, T, H, in_dim, seed=H + 1)
        valid = ragged_valid(B, T, seed=2)
        live = np.arange(T)[None, :] < np.asarray(valid)[:, None]
        r_out, (r_h, r_c) = jax.jit(ref_layer)(a)
        r_out = np.where(live[:, :, None], np.asarray(r_out), 0.0)
        if int8:
            assert pallas_lstm.fits_resident_int8(H), H
            q_ih, s_ih = quantize_symmetric(
                np.asarray(a["w_ih"], np.float32), 0)
            q_hh, s_hh = quantize_symmetric(
                np.asarray(a["w_hh"], np.float32), 0)
            fn = pallas_lstm.lstm_layer_fused_ragged_int8
            args = (a["x"], (a["h0"], a["c0"]), jnp.asarray(q_ih),
                    jnp.asarray(s_ih), jnp.asarray(q_hh), jnp.asarray(s_hh),
                    a["bias"], valid)
        else:
            fn = pallas_lstm.lstm_layer_fused_ragged
            args = (*layer_args(a), valid)
        mosaic(fn, *args)
        out, (h_t, c_t) = jax.jit(fn)(*args)
        # contract: zeros past each row's valid length; the full row ends
        # on the dense reference's state; the exhausted row keeps its carry
        assert np.array_equal(np.asarray(h_t[1]), np.asarray(a["h0"][1]))
        assert np.array_equal(np.asarray(c_t[1]), np.asarray(a["c0"][1]))
        return max(_rel_err(out, r_out), _rel_err(h_t[0], r_h[0]),
                   _rel_err(c_t[0], r_c[0]))

    interpret = not expect_mosaic

    def qrnn_inputs(T, B, H, seed):
        rng = np.random.RandomState(seed)
        return (jnp.asarray(np.tanh(rng.randn(T, B, H)), bf16),
                jnp.asarray(1 / (1 + np.exp(-rng.randn(T, B, H))), bf16),
                jnp.asarray(rng.randn(B, H) * 0.1, bf16))

    def fm_pallas(z, f, h0, valid=None):  # time-major, as qrnn_layer feeds it
        return pallas_qrnn.forget_mult_pallas(
            z, f, h0, time_major=True, interpret=interpret, valid_lens=valid)

    def fm_ref(z, f, h0):
        return forget_mult(z.swapaxes(0, 1).astype(f32),
                           f.swapaxes(0, 1).astype(f32),
                           h0.astype(f32)).swapaxes(0, 1)

    def qrnn_fwd():
        z, f, h0 = qrnn_inputs(w.bptt, w.bs, w.hid, seed=3)
        mosaic(fm_pallas, z, f, h0)
        return _rel_err(jax.jit(fm_pallas)(z, f, h0),
                        jax.jit(fm_ref)(z, f, h0))

    def qrnn_bwd():
        z, f, h0 = qrnn_inputs(w.bptt, w.bs, w.hid, seed=3)
        cot = jnp.asarray(
            np.random.RandomState(5).randn(w.bptt, w.bs, w.hid), f32)
        grad = jax.grad(lambda *p: jnp.sum(fm_pallas(*p).astype(f32) * cot),
                        argnums=(0, 1, 2))
        mosaic(grad, z, f, h0)
        g_pl = jax.jit(grad)(z, f, h0)
        g_ref = jax.jit(jax.grad(
            lambda *p: jnp.sum(fm_ref(*p) * cot), argnums=(0, 1, 2)))(
            z.astype(f32), f.astype(f32), h0.astype(f32))
        return max(_rel_err(g, r) for g, r in zip(g_pl, g_ref))

    def qrnn_ragged():
        B, T = w.serve_batch, PAGE
        z, f, h0 = qrnn_inputs(T, B, w.hid, seed=4)
        valid = ragged_valid(B, T, seed=4)
        mosaic(fm_pallas, z, f, h0, valid)
        got = np.asarray(jax.jit(fm_pallas)(z, f, h0, valid), np.float32)
        want = np.asarray(jax.jit(fm_ref)(z, f, h0), np.float32)
        assert np.isfinite(got).all(), "non-finite values past valid length"
        live = (np.arange(T)[:, None] < np.asarray(valid)[None, :])[:, :, None]
        return _rel_err(np.where(live, got, 0.0), np.where(live, want, 0.0))

    checks = []
    # layers 1-2 are hid->hid; the last layer is hid->emb (decoder tying)
    for H, in_dim in ((w.hid, w.hid), (w.emb, w.hid)):
        assert pallas_lstm.fits_resident(H, 2), H
        checks += [
            (f"lstm_fwd_H{H}", KERNEL_BAND["lstm"],
             lambda H=H, i=in_dim: lstm_fwd(H, i)),
            (f"lstm_fwd_gates_bwd_H{H}", KERNEL_BAND["lstm_grad"],
             lambda H=H, i=in_dim: lstm_train(H, i)),
            (f"lstm_ragged_H{H}", KERNEL_BAND["lstm"],
             lambda H=H, i=in_dim: lstm_ragged(H, i, int8=False)),
            (f"lstm_ragged_int8_H{H}", KERNEL_BAND["lstm_int8"],
             lambda H=H, i=in_dim: lstm_ragged(H, i, int8=True)),
        ]
    checks += [
        (f"qrnn_fwd_H{w.hid}", KERNEL_BAND["qrnn"], qrnn_fwd),
        (f"qrnn_bwd_H{w.hid}", KERNEL_BAND["qrnn_grad"], qrnn_bwd),
        (f"qrnn_ragged_H{w.hid}", KERNEL_BAND["qrnn"], qrnn_ragged),
    ]
    return checks


def leg_kernels(w: Widths, expect_mosaic: bool = True) -> dict:
    report: dict = {}
    for name, band, check in kernel_checks(w, expect_mosaic):
        err = check()
        report[name] = round(err, 5)
        log(f"kernel {name}: rel err {err:.5f} (band {band})")
        assert err <= band, f"{name}: {err} > {band}"
    return report


# ---------------------------------------------------------------------------
# multi-device leg
# ---------------------------------------------------------------------------


class _MemorySampler:
    """Per-device live bytes (utils/memtrack.py) while a leg runs, above
    what was live when it started (earlier one-chip legs leave their
    arrays on device 0): the snapshot with the largest total is kept."""

    def __init__(self, period_s: float = 1.0):
        from code_intelligence_tpu.utils.memtrack import DeviceMemoryLedger

        self._ledger = DeviceMemoryLedger()
        self._period = period_s
        self._stop = threading.Event()
        self.peak: dict = {}
        self._thread = threading.Thread(target=self._run, daemon=True)

    def _run(self) -> None:
        while not self._stop.wait(self._period):
            snap = self._ledger.snapshot()
            if snap["total_bytes"] > self.peak.get("total_bytes", -1):
                self.peak = snap

    def __enter__(self):
        import gc

        gc.collect()
        self._base = {d: r["total_bytes"] for d, r in
                      self._ledger.snapshot()["devices"].items()}
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join(timeout=30)
        return False

    def per_device(self) -> dict:
        return {d: int(r["total_bytes"] - self._base.get(d, 0))
                for d, r in sorted(self.peak.get("devices", {}).items())}


def leg_multichip(work: Path, w: Widths, corpus_dir: Path, max_tokens: int,
                  issues: list, n_devices: int, one_chip: dict) -> dict:
    """Two train layouts and one serve mesh over all ``n_devices`` chips
    of the host, each with per-device evidence: live bytes on every
    device while it runs, and the partitioned program's per-device flops
    against ``one_chip`` (the ``train`` leg's result)."""
    single_chip_flops = one_chip["train_steps_flops"]
    single_chip_loss = one_chip["last_loss"]
    out: dict = {"devices_used": n_devices}
    half = n_devices // 2
    layouts = {
        f"dp{n_devices}": ("--data_parallel", str(n_devices)),
        f"dp{half}_mp2": ("--data_parallel", str(half),
                          "--model_parallel", "2"),
    }
    for name, args in layouts.items():
        with _MemorySampler() as mem:
            res = leg_train(work, w, corpus_dir, max_tokens, name, args)
        per_dev = mem.per_device()
        assert len(per_dev) == n_devices, per_dev
        lo, hi = min(per_dev.values()), max(per_dev.values())
        assert lo > 0 and hi <= 1.25 * lo, \
            f"{name}: live bytes not spread over the devices: {per_dev}"
        share = res["train_steps_flops"] * n_devices / single_chip_flops
        # 1.0 = every device does exactly 1/n of the one-chip program;
        # n = every device does all of it
        assert share <= 2.0, \
            f"{name}: each device runs {share:.2f}x its share of the flops"
        assert abs(res["last_loss"] - single_chip_loss) <= TRAIN_BAND, \
            f"{name}: loss {res['last_loss']} vs one chip {single_chip_loss}"
        out[name] = {**res, "live_bytes_per_device": per_dev,
                     "flops_share": round(share, 3)}
        log(f"multichip[{name}] live bytes/device {per_dev}, per-device "
            f"flops x n / one-chip flops = {share:.3f}")

    export = Path(out[f"dp{n_devices}"]["export"])
    leg_serve(export, w, issues, f"ragged_mesh_data{n_devices}",
              ("--scheduler", "ragged", "--mesh", f"data={n_devices}"),
              mesh_devices=n_devices)
    return out


# ---------------------------------------------------------------------------


def run(w: Widths = FLAGSHIP, legs: tuple = (
        "train", "serve_slots", "kernels", "serve_ragged", "serve_int8",
        "multichip"),
        expect_mosaic: bool = True, work: "Path | None" = None) -> dict:
    """Run the named legs in order. ``serve_*`` and ``multichip`` need
    ``train`` (its export, its loss, its flops)."""
    import jax

    results: dict = {}
    own_work = work is None
    work = Path(tempfile.mkdtemp(prefix="chip_smoke_")) if own_work \
        else Path(work)
    try:
        vocab, gen = make_vocab(w)
        corpus_dir = work / "corpus"
        max_tokens = write_corpus(corpus_dir, w, vocab)
        issues = make_issues(gen, w.n_docs)
        log(f"corpus: vocab {len(vocab)}, --max_tokens {max_tokens}")
        reference = None
        export = None
        for leg in legs:
            if leg == "train":
                results[leg] = leg_train(work, w, corpus_dir, max_tokens,
                                         "train", expect_mosaic=expect_mosaic)
                export = Path(results[leg]["export"])
                shutil.rmtree(work / "train" / "ckpt")  # ~2.3 GB at flagship
            elif leg == "serve_slots":
                reference = leg_serve(export, w, issues, "slots",
                                      reference=reference)
            elif leg == "serve_ragged":
                reference = leg_serve(
                    export, w, issues, "ragged_pallas",
                    ("--scheduler", "ragged", "--lstm_pallas"),
                    reference=reference, expect_mosaic=expect_mosaic)
            elif leg == "serve_int8":
                reference = leg_serve(
                    export, w, issues, "int8_ragged_pallas",
                    ("--precision", "int8", "--scheduler", "ragged",
                     "--lstm_pallas"), band=SERVE_BAND["int8"],
                    reference=reference, expect_mosaic=expect_mosaic)
            elif leg == "kernels":
                results[leg] = leg_kernels(w, expect_mosaic=expect_mosaic)
            elif leg == "multichip":
                n = len(jax.devices())
                if n >= 4:
                    results[leg] = leg_multichip(
                        work, w, corpus_dir, max_tokens, issues, n,
                        one_chip=results["train"])
                log(f"multichip: {n} device(s) visible, "
                    f"{n if n >= 4 else 0} used")
            else:
                raise ValueError(f"unknown leg {leg!r}")
    finally:
        if own_work:
            shutil.rmtree(work, ignore_errors=True)
    return results


def main() -> int:
    import jax
    import jaxlib

    from code_intelligence_tpu.utils import devices

    device = devices.require_tpu("chip_smoke.py")
    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:
        libtpu = "unknown"
    log(f"device {json.dumps(device)} jax {jax.__version__} "
        f"jaxlib {jaxlib.__version__} libtpu {libtpu}")
    cache_dir = devices.enable_compile_cache()
    cache = CacheCounter()
    log(f"compile cache: {cache_dir}")
    results = run()
    log(f"compile cache: {cache.hits} hits, {cache.misses} misses; "
        f"legs: {sorted(results)}")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
