"""graftcheck runtime auditors: what static analysis cannot see.

Three dynamic checks that piggyback on hooks the framework already has,
asserted inside tier-1 tests (and usable around any suspect scope):

* :class:`recompile_guard` — reads the flight-recorder
  ``XLAAccountant`` ledger (every compile of the process is an entry
  there, an ``InstrumentedJit``-wrapped step's under its instrumented
  name) and fails when a guarded scope compiles more new shapes than
  its declared budget.
  ``budget=0`` is the steady-state assertion: a warmed-up serve/train
  loop must never pay another compile.
* :func:`no_implicit_transfers` — ``jax.transfer_guard("disallow")`` as
  a context manager: any *implicit* host↔device transfer (a numpy array
  silently fed to a compiled callable, a traced value silently
  materialized) raises, while intentional, explicit transfers
  (``jnp.asarray``, ``jax.device_put``, ``jax.device_get``) still pass.
  The hot paths are written to be clean under it; tests pin that.
* :class:`memory_guard` — the byte-side sibling of ``recompile_guard``:
  snapshots the live device-buffer footprint (``jax.live_arrays()``,
  shared measurement with ``utils/memtrack.py``) on entry and fails at
  scope exit when the scope *grew* it past the declared budget.
  ``budget_bytes=0`` is the steady-state assertion: a warmed-up serve
  loop must never retain another buffer. Given a
  :class:`~code_intelligence_tpu.utils.memtrack.DeviceMemoryLedger`,
  the failure names the owning component(s) of the growth.
* :class:`CompileWatch` — the jaxcheck lint's runtime counterpart: a
  steady-state dispatch sentinel for one warmed-up step function.
  :meth:`CompileWatch.steady_state` marks the accountant ledger (the
  process's one ``jax.monitoring`` listener feeds it), patches the
  concrete ``jax.Array`` host-materialization surface (``.item()`` /
  ``__array__`` / ``__float__`` / ``__int__`` / ``__bool__``) plus
  ``jax.device_get`` / ``jax.device_put``, and fails at scope exit when
  the scope recompiled (the watched step, or any other program: both
  named by the ledger, with their stage seconds) or materialized device values on the host outside an
  explicit ``jax.device_get``. The CPU backend's d2h is zero-copy, so
  ``transfer_guard`` alone cannot see ``.item()`` there — the method
  patch is what makes the audit meaningful device-free. Transfer volume
  lands on ``jit_recompiles_total`` / ``h2d_d2h_bytes`` gauges via
  :meth:`CompileWatch.bind_registry`.
* :class:`LockOrderRecorder` — wraps locks (individually via ``wrap``
  or process-wide via ``patch()``, which temporarily replaces
  ``threading.Lock``/``RLock`` factories) and records the lock
  *acquisition graph*: an edge A→B for every acquire of B while A is
  held, keyed by the lock's creation site so all instances of one lock
  class aggregate. :meth:`assert_acyclic` fails on any cycle — the ABBA
  inversion that deadlocks under load but passes every fast test.
* :class:`LockCoverageAuditor` — the recorder extended into a
  ThreadSanitizer-lite: :meth:`audit` instruments registered shared
  objects' attribute accesses (class-level ``__getattribute__`` /
  ``__setattr__`` patch, filtered to registered instances) and records,
  per field, whether any recorded lock was held at each access.
  :meth:`coverage_report` names fields observed accessed BOTH with and
  without a lock, with at least one write, from more than one thread —
  runtime confirmation for the static ``unguarded-shared-field``
  findings (analysis/races.py) and a net for discipline the AST can't
  see (cross-object guarding, dynamic dispatch).

jax is imported lazily; the lint CLI path never touches it.
"""

from __future__ import annotations

import collections
import contextlib
import sys
import threading
from typing import Dict, List, Optional, Set, Tuple

# the REAL factories, captured at import time: auditor bookkeeping locks
# must never be recorded even when constructed inside a patch() scope
# (a recorded meta-lock would feed its own acquisitions back into the
# recorder — noise at best, re-entrant deadlock at worst)
_REAL_LOCK = threading.Lock
_REAL_RLOCK = threading.RLock


class RecompileBudgetExceeded(RuntimeError):
    """A guarded scope compiled more new XLA programs than declared."""


class CompileWatchViolation(RuntimeError):
    """A warmed-up scope recompiled or host-synced at steady state."""


class MemoryGrowthExceeded(RuntimeError):
    """A guarded scope grew the live device-buffer footprint past its
    declared budget (a retained buffer, i.e. a leak, at budget 0)."""


class LockOrderViolation(RuntimeError):
    """The recorded lock acquisition graph contains a cycle."""


class LockCoverageViolation(RuntimeError):
    """A shared field was accessed both with and without a lock held."""


# ---------------------------------------------------------------------------
# recompile guard (over the flight-recorder accountant ledger)
# ---------------------------------------------------------------------------


class recompile_guard:
    """Context manager asserting a compiled-shape budget over a scope.

    ``fn`` narrows the check to one instrumented function name (e.g.
    ``"slots.step"``, ``"train.steps"``); ``None`` applies the budget to
    every function in the ledger individually (every program of the
    process, once the accountant listens). ``budget`` is the number
    of NEW compiles allowed inside the scope (0 = steady state).

    The guard observes, it never blocks: compilation proceeds normally
    and the violation surfaces at scope exit (or an explicit
    :meth:`check`), listing the offending shapes so the failure message
    is actionable. If accounting is disabled
    (``CI_TPU_NO_XLA_ACCOUNTING=1``) or the wrapped step has fallen back
    to unaccounted passthrough, the guard sees nothing of that step.
    """

    def __init__(self, fn: Optional[str] = None, budget: int = 1,
                 accountant=None):
        self.fn = fn
        self.budget = int(budget)
        self._acct = accountant
        self._mark = 0

    def _accountant(self):
        if self._acct is None:
            from code_intelligence_tpu.utils import flight_recorder

            self._acct = flight_recorder.get_accountant()
        return self._acct

    def __enter__(self) -> "recompile_guard":
        self._mark = self._accountant().compiles_mark()
        return self

    def new_compiles(self) -> Dict[str, List[dict]]:
        """fn -> compile records that happened inside the scope."""
        out: Dict[str, List[dict]] = {}
        for c in self._accountant().report(self._mark):
            if self.fn is None or c["fn"] == self.fn:
                out.setdefault(c["fn"], []).append(c)
        return out

    def check(self) -> None:
        over = {name: fresh for name, fresh in self.new_compiles().items()
                if len(fresh) > self.budget}
        if over:
            detail = "; ".join(
                f"{name}: {len(fresh)} new compiled shape(s) "
                f"[{', '.join(c['shape'] for c in fresh)}]"
                for name, fresh in sorted(over.items()))
            raise RecompileBudgetExceeded(
                f"compiled-shape budget {self.budget} exceeded — {detail}")

    def __exit__(self, exc_type, exc, tb) -> bool:
        if exc_type is None:  # never mask the scope's own error
            self.check()
        return False


# ---------------------------------------------------------------------------
# transfer guard
# ---------------------------------------------------------------------------


@contextlib.contextmanager
def no_implicit_transfers():
    """``jax.transfer_guard("disallow")`` scope: implicit host↔device
    transfers raise; explicit ones (jnp.asarray / device_put /
    device_get) pass."""
    import jax

    with jax.transfer_guard("disallow"):
        yield


# ---------------------------------------------------------------------------
# compile watch (steady-state recompile / host-sync sentinel)
# ---------------------------------------------------------------------------


class _Sanctioned(threading.local):
    def __init__(self):
        self.active = False


class CompileWatch:
    """Steady-state dispatch sentinel: a warmed-up step scope must not
    recompile and must not materialize device values on the host except
    through an explicit ``jax.device_get``.

    ``fn`` names the instrumented step under watch (e.g.
    ``"slots.step"``) — recompile attribution comes from the
    flight-recorder accountant ledger, exactly like
    :class:`recompile_guard`; the same ledger names every OTHER program
    that compiled inside the scope (a stray un-instrumented ``jnp`` op
    compiling mid-loop), since the accountant's ``jax.monitoring``
    listener records every compile of the process.

    Host syncs are caught by patching the concrete ``jax.Array``
    class's materialization surface (``.item()``, ``__array__``,
    ``__float__``, ``__int__``, ``__bool__``) for the scope.
    ``jax.device_get`` is patched to raise a thread-local *sanctioned*
    flag around its own internal ``np.asarray`` so the one blessed exit
    ramp stays silent; everything else is an unsanctioned sync and
    fails the audit. This is deliberately stricter than
    ``transfer_guard("disallow")`` (also active over the scope): on the
    CPU backend d2h is zero-copy and the guard never fires for it, so
    the method patch is what makes the audit portable to device-free
    CI. ``jax.device_put`` is patched too, to meter h2d volume.

    Counters survive scope exit; :meth:`bind_registry` exports them as
    ``jit_recompiles_total`` (cumulative ledger compiles for the
    watched fn) and ``h2d_d2h_bytes`` (bytes moved inside watched
    scopes, labelled ``dir=h2d|d2h``).
    """

    def __init__(self, fn: Optional[str] = None, accountant=None,
                 registry=None):
        self.fn = fn
        self._acct = accountant
        self.registry = None
        self._sanct = _Sanctioned()
        self._meta = _REAL_LOCK()
        # scope results (persist after exit so tests can assert gauges)
        self.new_compiles: Dict[str, List[dict]] = {}
        self.stray_compiles: List[dict] = []  # of any other program
        self.h2d_bytes = 0
        self.d2h_bytes = 0
        self.host_syncs: List[Dict[str, object]] = []
        if registry is not None:
            self.bind_registry(registry)

    # -- wiring ---------------------------------------------------------

    def _accountant(self):
        if self._acct is None:
            from code_intelligence_tpu.utils import flight_recorder

            self._acct = flight_recorder.get_accountant()
        return self._acct

    def bind_registry(self, registry) -> None:
        """Export the watch's gauges on a ``utils.metrics.Registry``."""
        if registry is None or self.registry is registry:
            return
        registry.gauge(
            "jit_recompiles_total",
            "cumulative XLA compiles recorded for the watched step fn "
            "(flight-recorder ledger; growth after warmup = recompile)")
        registry.gauge(
            "h2d_d2h_bytes",
            "bytes moved across the host-device boundary inside "
            "CompileWatch steady-state scopes, by direction "
            "(dir=h2d via device_put, dir=d2h via device_get / host "
            "materialization)")
        self.registry = registry
        self._export()

    def _export(self) -> None:
        if self.registry is None:
            return
        acct = self._accountant()
        self.registry.set(
            "jit_recompiles_total",
            acct.compiles_mark() if self.fn is None else acct.count(self.fn))
        self.registry.set("h2d_d2h_bytes", self.h2d_bytes,
                          labels={"dir": "h2d"})
        self.registry.set("h2d_d2h_bytes", self.d2h_bytes,
                          labels={"dir": "d2h"})

    # -- accounting (called from the scope's patches) -------------------

    @staticmethod
    def _leaf_bytes(tree) -> int:
        import jax

        return int(sum(getattr(leaf, "nbytes", 0)
                       for leaf in jax.tree_util.tree_leaves(tree)))

    def _note_d2h(self, kind: str, arr) -> None:
        sanctioned = self._sanct.active
        nbytes = int(getattr(arr, "nbytes", 0))
        with self._meta:
            self.d2h_bytes += nbytes
            if not sanctioned:
                self.host_syncs.append({
                    "kind": kind,
                    "shape": f"{getattr(arr, 'dtype', '?')}"
                             f"{list(getattr(arr, 'shape', ()))}",
                    "nbytes": nbytes,
                })

    def _note_h2d(self, tree) -> None:
        nbytes = self._leaf_bytes(tree)
        with self._meta:
            self.h2d_bytes += nbytes

    # -- the audited scope ----------------------------------------------

    @contextlib.contextmanager
    def steady_state(self):
        """Audit the scope: zero new compiles (the watched step's or any
        other program's), zero unsanctioned host materializations. Raises
        :class:`CompileWatchViolation` at exit naming the watched fn."""
        import jax

        acct = self._accountant()
        acct.listen()
        # the concrete on-device array class; grabbed BEFORE the ledger
        # is marked (the asarray itself may compile a conversion program
        # on first use) and BEFORE patching
        array_cls = type(jax.numpy.asarray(0))
        mark = acct.compiles_mark()
        watch = self

        def _patched(kind: str, orig):
            def hook(arr, *a, **kw):
                watch._note_d2h(kind, arr)
                return orig(arr, *a, **kw)
            return hook

        real_methods = {name: getattr(array_cls, name) for name in
                        ("item", "__array__", "__float__", "__int__",
                         "__bool__")}
        real_device_get = jax.device_get
        real_device_put = jax.device_put

        def sanctioned_get(x, *a, **kw):
            prev = watch._sanct.active
            watch._sanct.active = True
            try:
                out = real_device_get(x, *a, **kw)
            finally:
                watch._sanct.active = prev
            # device_get is the blessed d2h ramp: meter it without
            # flagging (the __array__ hook under the flag added bytes
            # already only for array leaves it actually touched)
            return out

        def counted_put(x, *a, **kw):
            watch._note_h2d(x)
            prev = watch._sanct.active
            watch._sanct.active = True  # internal __array__ is plumbing
            try:
                return real_device_put(x, *a, **kw)
            finally:
                watch._sanct.active = prev

        for name, orig in real_methods.items():
            setattr(array_cls, name, _patched(name.strip("_"), orig))
        jax.device_get = sanctioned_get
        jax.device_put = counted_put
        try:
            with no_implicit_transfers():
                yield self
        finally:
            for name, orig in real_methods.items():
                setattr(array_cls, name, orig)
            jax.device_get = real_device_get
            jax.device_put = real_device_put
            self.new_compiles, self.stray_compiles = {}, []
            for c in acct.report(mark):
                if self.fn is None or c["fn"] == self.fn:
                    self.new_compiles.setdefault(c["fn"], []).append(c)
                else:
                    self.stray_compiles.append(c)
            self._export()
        self.check()

    def check(self) -> None:
        problems: List[str] = []
        for name, records in sorted(self.new_compiles.items()):
            shapes = ", ".join(c.get("shape", "?") for c in records)
            problems.append(
                f"{len(records)} steady-state recompile(s) of {name} "
                f"[{shapes}]")
        if self.stray_compiles:
            def told(c: dict) -> str:
                stage_s = c.get("stage_s", {})
                return (f"{c['fn']} (" + ", ".join(
                    f"{s} {stage_s.get(s, 0.0):.3f} s"
                    for s in ("trace", "lower", "compile"))
                    + f", cache {c.get('cache', 'off')})")
            problems.append(
                f"{len(self.stray_compiles)} other program(s) compiled "
                f"mid-loop: "
                + ", ".join(told(c) for c in self.stray_compiles[:4])
                + (f" (+{len(self.stray_compiles) - 4} more)"
                   if len(self.stray_compiles) > 4 else ""))
        if self.host_syncs:
            kinds = ", ".join(
                f"{s['kind']} {s['shape']}" for s in self.host_syncs[:4])
            more = (f" (+{len(self.host_syncs) - 4} more)"
                    if len(self.host_syncs) > 4 else "")
            problems.append(
                f"{len(self.host_syncs)} unsanctioned host "
                f"materialization(s): {kinds}{more} — route intentional "
                f"reads through jax.device_get")
        if problems:
            raise CompileWatchViolation(
                f"CompileWatch[{self.fn or '*'}]: " + "; ".join(problems))


# ---------------------------------------------------------------------------
# memory guard (over the live device-buffer footprint)
# ---------------------------------------------------------------------------


class memory_guard:
    """Context manager asserting a live-device-buffer growth budget.

    ``budget_bytes`` / ``budget_buffers`` bound the NET growth the scope
    may leave behind (0/0 = steady state: everything the scope allocates
    it must release). Like ``recompile_guard`` it observes, never
    blocks: allocation proceeds normally and the violation surfaces at
    scope exit (or an explicit :meth:`check`) as
    :class:`MemoryGrowthExceeded`. Shrinking is always fine.

    ``ledger`` (a ``utils.memtrack.DeviceMemoryLedger``) is optional
    attribution: when given, the failure message names the owner rows
    that grew — including the explicit ``unattributed`` row, which is
    where an unregistered leak (retained step outputs, a forgotten
    reference) lands by construction.

    Before claiming a violation the guard runs one ``gc.collect()`` and
    re-measures: buffers kept alive only by collectable reference
    cycles are garbage, not leaks, and must not fail the audit. The
    entry baseline is taken on a settled heap (one ``gc.collect()``)
    for the mirror-image reason: garbage pending collection at entry
    would inflate the baseline, and its mid-scope death would then mask
    a real leak of the same size.
    """

    def __init__(self, budget_bytes: int = 0, budget_buffers: int = 0,
                 ledger=None):
        self.budget_bytes = int(budget_bytes)
        self.budget_buffers = int(budget_buffers)
        self.ledger = ledger
        self._before_bytes = 0
        self._before_buffers = 0
        self._before_owners: Dict[str, int] = {}

    @staticmethod
    def _measure() -> Tuple[int, int]:
        from code_intelligence_tpu.utils.memtrack import live_buffer_totals

        return live_buffer_totals()

    def _owner_bytes(self) -> Dict[str, int]:
        snap = self.ledger.snapshot()
        out = {o: r["bytes"] for o, r in snap["owners"].items()}
        out["unattributed"] = snap["unattributed"]["bytes"]
        return out

    def __enter__(self) -> "memory_guard":
        # settle the heap before the baseline: garbage pending collection
        # at entry would inflate it, and its death mid-scope would then
        # cancel out (mask) a real leak of the same size
        import gc

        gc.collect()
        if self.ledger is not None:
            self._before_owners = self._owner_bytes()
        self._before_bytes, self._before_buffers = self._measure()
        return self

    def growth(self) -> Dict[str, int]:
        """Net ``{"bytes": ..., "buffers": ...}`` growth since entry."""
        b, n = self._measure()
        if (b - self._before_bytes > self.budget_bytes
                or n - self._before_buffers > self.budget_buffers):
            import gc

            gc.collect()
            b, n = self._measure()
        return {"bytes": b - self._before_bytes,
                "buffers": n - self._before_buffers}

    def check(self) -> None:
        g = self.growth()
        if (g["bytes"] <= self.budget_bytes
                and g["buffers"] <= self.budget_buffers):
            return
        detail = ""
        if self.ledger is not None:
            after = self._owner_bytes()
            grown = {o: after[o] - self._before_owners.get(o, 0)
                     for o in after
                     if after[o] - self._before_owners.get(o, 0) > 0}
            if grown:
                detail = " — owners: " + ", ".join(
                    f"{o} +{d}B" for o, d in sorted(
                        grown.items(), key=lambda kv: -kv[1]))
        raise MemoryGrowthExceeded(
            f"live-buffer budget ({self.budget_bytes}B / "
            f"{self.budget_buffers} buffers) exceeded — scope grew "
            f"{g['bytes']}B across {g['buffers']} retained "
            f"buffer(s){detail}")

    def __exit__(self, exc_type, exc, tb) -> bool:
        if exc_type is None:  # never mask the scope's own error
            self.check()
        return False


# ---------------------------------------------------------------------------
# lock-order recorder
# ---------------------------------------------------------------------------


class _HeldStack(threading.local):
    def __init__(self):
        self.names: List[str] = []


class _RecordedLock:
    """Drop-in lock proxy feeding acquisitions to a recorder."""

    def __init__(self, inner, name: str, recorder: "LockOrderRecorder"):
        self._inner = inner
        self._name = name
        self._recorder = recorder

    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        ok = self._inner.acquire(blocking, timeout)
        if ok:
            self._recorder._acquired(self._name)
        return ok

    def release(self) -> None:
        self._inner.release()
        self._recorder._released(self._name)

    def __getattr__(self, name):
        # full protocol passthrough: threading.Condition probes
        # _release_save/_acquire_restore/_is_owned for RLock-correct
        # reentrant wait semantics, and locked() exists on Lock but not
        # RLock — the proxy must mirror the wrapped object exactly or a
        # Condition on a patched RLock silently degrades (and deadlocks
        # a reentrant holder in wait()). The recorder's held-stack can
        # briefly under-count during a cv.wait() full-release; a blocked
        # waiter records nothing, so the graph stays truthful.
        return getattr(self._inner, name)

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.release()
        return False

    def __repr__(self) -> str:  # pragma: no cover - debug aid
        return f"<RecordedLock {self._name} of {self._inner!r}>"


def _creation_site(skip_frames: int = 2) -> Optional[str]:
    """``file.py:lineno`` of the IMMEDIATE frame constructing a lock.
    Returns None for stdlib/library-internal construction
    (threading.Event's inner Condition lock, queue.Queue's mutex, jax
    internals, ...) — those aren't lock classes the application orders,
    only noise. Immediate-caller only, never walk outward: attributing a
    stdlib-built lock to the application frame that happens to be
    further up the stack recorded threading's OWN bookkeeping locks and
    recursed (a _DummyThread's Event re-entering the recorder)."""
    f = sys._getframe(skip_frames)
    fname = f.f_code.co_filename
    if "threading" in fname or "/lib/python" in fname \
            or "importlib" in fname:
        return None
    return f"{fname.rsplit('/', 1)[-1]}:{f.f_lineno}"


class LockOrderRecorder:
    """Builds the cross-thread lock acquisition graph; fails on cycles.

    Edges are keyed by lock *name* (creation site under ``patch()``), so
    every instance of e.g. ``batcher.py:79`` aggregates into one node —
    the graph describes lock classes, which is what an ordering
    discipline is about. Re-acquiring an already-held name (RLock
    reentrancy) records no edge.
    """

    def __init__(self):
        self._graph: Dict[str, Dict[str, str]] = {}  # a -> {b: witness}
        self._meta = _REAL_LOCK()
        self._held = _HeldStack()
        self.acquisitions = 0

    # -- wiring ---------------------------------------------------------

    def wrap(self, lock, name: str) -> _RecordedLock:
        return _RecordedLock(lock, name, self)

    @contextlib.contextmanager
    def patch(self):
        """Temporarily replace ``threading.Lock``/``RLock`` so every lock
        *constructed inside the scope* from application code is recorded
        (stdlib-internal locks pass through unrecorded). Locks outlive
        the scope safely — the proxies hold real locks."""
        real_lock, real_rlock = threading.Lock, threading.RLock

        def make(factory):
            def build(*a, **kw):
                site = _creation_site()
                inner = factory(*a, **kw)
                if site is None:
                    return inner
                return _RecordedLock(inner, site, self)
            return build

        threading.Lock = make(real_lock)  # type: ignore[assignment]
        threading.RLock = make(real_rlock)  # type: ignore[assignment]
        try:
            yield self
        finally:
            threading.Lock = real_lock  # type: ignore[assignment]
            threading.RLock = real_rlock  # type: ignore[assignment]

    # -- recording (called from lock proxies) ---------------------------

    def _acquired(self, name: str) -> None:
        held = self._held.names
        # get_ident, NOT current_thread(): in a foreign (XLA worker)
        # thread current_thread() builds a _DummyThread whose Event
        # takes locks — recorder bookkeeping must never take recorded
        # locks itself
        witness = f"thread-{threading.get_ident()}"
        with self._meta:
            self.acquisitions += 1
            if name not in held:  # reentrant re-acquire records no edge
                for h in held:
                    if h != name:
                        self._graph.setdefault(h, {}).setdefault(
                            name, witness)
        held.append(name)

    def _released(self, name: str) -> None:
        held = self._held.names
        for i in range(len(held) - 1, -1, -1):
            if held[i] == name:
                del held[i]
                return

    # -- analysis -------------------------------------------------------

    def edges(self) -> List[Tuple[str, str]]:
        with self._meta:
            return sorted((a, b) for a, succ in self._graph.items()
                          for b in succ)

    def find_cycle(self) -> Optional[List[str]]:
        """One cycle as ``[a, b, ..., a]``, or None. Deterministic:
        nodes visited in sorted order."""
        with self._meta:
            graph = {a: sorted(succ) for a, succ in self._graph.items()}
        WHITE, GREY, BLACK = 0, 1, 2
        color = {n: WHITE for n in graph}
        stack: List[str] = []

        def dfs(n: str) -> Optional[List[str]]:
            color[n] = GREY
            stack.append(n)
            for m in graph.get(n, ()):
                if color.get(m, WHITE) == GREY:
                    return stack[stack.index(m):] + [m]
                if color.get(m, WHITE) == WHITE:
                    cyc = dfs(m)
                    if cyc:
                        return cyc
            stack.pop()
            color[n] = BLACK
            return None

        for n in sorted(graph):
            if color.get(n, WHITE) == WHITE:
                cyc = dfs(n)
                if cyc:
                    return cyc
        return None

    def assert_acyclic(self) -> None:
        cyc = self.find_cycle()
        if cyc:
            with self._meta:
                witnesses = [
                    f"{a} -> {b} ({self._graph.get(a, {}).get(b, '?')})"
                    for a, b in zip(cyc, cyc[1:])]
            raise LockOrderViolation(
                "lock acquisition cycle: " + " -> ".join(cyc)
                + "; witnesses: " + "; ".join(witnesses))


# ---------------------------------------------------------------------------
# lock-coverage auditor (ThreadSanitizer-lite)
# ---------------------------------------------------------------------------


class _FieldCoverage:
    """Per-(object, field) access tally. Mutated only under the
    auditor's coverage lock."""

    __slots__ = ("locked", "unlocked", "writes", "unlocked_writes",
                 "threads", "first_unlocked_kind", "container")

    def __init__(self):
        self.locked = 0
        self.unlocked = 0
        self.writes = 0
        self.unlocked_writes = 0
        self.threads: Set[int] = set()
        self.first_unlocked_kind = ""  # "read"/"write" — report color
        # the sampled value was a mutable container: a mere attribute
        # READ of it precedes mutation/iteration the sampler can't see
        # (self._q.append / list(self._q)), so mixed discipline counts
        # as racy even with zero observed __setattr__ writes
        self.container = False

    def as_dict(self) -> Dict[str, object]:
        return {"locked": self.locked, "unlocked": self.unlocked,
                "writes": self.writes,
                "unlocked_writes": self.unlocked_writes,
                "threads": len(self.threads),
                "container": self.container,
                "first_unlocked_kind": self.first_unlocked_kind}


class _Busy(threading.local):
    def __init__(self):
        self.active = False


class LockCoverageAuditor(LockOrderRecorder):
    """The lock-order recorder extended with per-field lock-coverage
    sampling — runtime confirmation for the static race lint.

    Usage (construct the auditor BEFORE entering ``patch()`` so its own
    bookkeeping locks stay unrecorded; ``patch()`` must wrap the
    construction of the objects under audit or no lock acquisition is
    visible)::

        auditor = LockCoverageAuditor()
        with auditor.patch():
            batcher = MicroBatcher(...)          # locks recorded
        with auditor.audit(batcher):             # fields sampled
            run_concurrent_load(batcher)
        auditor.assert_acyclic()                 # inherited
        auditor.assert_covered()                 # no mixed discipline

    ``audit()`` patches the registered objects' *classes*
    (``__getattribute__`` / ``__setattr__``) and samples every
    non-dunder, non-callable, non-lock attribute access on the
    registered instances, tagging each with whether the accessing
    thread currently holds ANY recorded lock. A field is **racy** when
    it was accessed both with and without a lock held, at least one
    access was a write, and more than one thread touched it — the
    mixed-discipline signature behind every lost-update/torn-iteration
    bug the static pass hunts. Register objects AFTER construction so
    single-threaded ``__init__`` writes don't count as unlocked traffic.

    This is a sampler, not a proof: a field the suite never exercises
    concurrently stays invisible, and lock-free-by-design fields (COW
    snapshots, monotonic latches) show up and belong in ``ignore``.
    """

    def __init__(self):
        super().__init__()
        self._cov_lock = _REAL_LOCK()
        self._cov: Dict[Tuple[str, str], _FieldCoverage] = {}
        self._instances: Dict[int, str] = {}
        self._keep: List[object] = []   # id() stability while auditing
        self._patched: Dict[type, Tuple[object, object]] = {}
        self._busy = _Busy()

    # -- wiring ---------------------------------------------------------

    def register(self, obj, name: Optional[str] = None) -> None:
        """Start sampling attribute accesses on ``obj`` (named
        ``name`` or its class name in the report)."""
        cls = type(obj)
        self._instances[id(obj)] = name or cls.__name__
        self._keep.append(obj)
        if any(c in self._patched for c in cls.__mro__):
            # an ancestor's hooks already see this instance's accesses
            # (MRO resolution reaches them); patching the subclass too
            # would chain the hooks and double-count every access
            return
        try:
            orig_get = cls.__dict__.get("__getattribute__")
            orig_set = cls.__dict__.get("__setattr__")
            auditor = self
            base_get = cls.__getattribute__
            base_set = cls.__setattr__

            def sampled_get(inst, attr):
                val = base_get(inst, attr)
                auditor._sample(inst, attr, val, write=False)
                return val

            def sampled_set(inst, attr, val):
                base_set(inst, attr, val)
                auditor._sample(inst, attr, val, write=True)

            cls.__getattribute__ = sampled_get  # type: ignore[assignment]
            cls.__setattr__ = sampled_set  # type: ignore[assignment]
        except TypeError as e:  # builtins/extension types
            raise TypeError(
                f"cannot audit {cls.__name__}: its attribute hooks are "
                f"not patchable (builtin/extension type)") from e
        self._patched[cls] = (orig_get, orig_set)

    def restore(self) -> None:
        """Undo every class patch and forget the registered instances
        (tallies are kept for reporting)."""
        for cls, (orig_get, orig_set) in self._patched.items():
            if orig_get is None:
                try:
                    del cls.__getattribute__
                except AttributeError:
                    pass
            else:
                cls.__getattribute__ = orig_get  # type: ignore[assignment]
            if orig_set is None:
                try:
                    del cls.__setattr__
                except AttributeError:
                    pass
            else:
                cls.__setattr__ = orig_set  # type: ignore[assignment]
        self._patched.clear()
        self._instances.clear()
        self._keep.clear()

    @contextlib.contextmanager
    def audit(self, *objs, names: Optional[Dict[int, str]] = None):
        """Sample attribute accesses on ``objs`` for the scope."""
        try:
            # register INSIDE the try: if a later object's class turns
            # out unpatchable, the finally must unwind the classes the
            # earlier registrations already instrumented
            for i, o in enumerate(objs):
                self.register(o, (names or {}).get(i))
            yield self
        finally:
            self.restore()

    # -- sampling -------------------------------------------------------

    _SKIP_TYPES: Tuple[type, ...] = ()  # filled lazily below

    def _skip_value(self, val) -> bool:
        if callable(val):
            return True
        skip = LockCoverageAuditor._SKIP_TYPES
        if not skip:
            skip = (type(threading.Lock()), type(threading.RLock()),
                    threading.Condition, threading.Event,
                    threading.Semaphore, threading.local, _RecordedLock)
            LockCoverageAuditor._SKIP_TYPES = skip
        return isinstance(val, skip)

    def _sample(self, inst, attr: str, val, write: bool) -> None:
        if attr.startswith("__") or self._busy.active:
            return
        name = self._instances.get(id(inst))
        if name is None or self._skip_value(val):
            return
        self._busy.active = True
        try:
            locked = bool(self._held.names)
            tid = threading.get_ident()
            is_container = isinstance(
                val, (list, dict, set, collections.deque, bytearray))
            with self._cov_lock:
                cov = self._cov.get((name, attr))
                if cov is None:
                    cov = self._cov[(name, attr)] = _FieldCoverage()
                if is_container:
                    cov.container = True
                if locked:
                    cov.locked += 1
                else:
                    cov.unlocked += 1
                    if not cov.first_unlocked_kind:
                        cov.first_unlocked_kind = (
                            "write" if write else "read")
                if write:
                    cov.writes += 1
                    if not locked:
                        cov.unlocked_writes += 1
                cov.threads.add(tid)
        finally:
            self._busy.active = False

    # -- reporting ------------------------------------------------------

    def samples(self) -> Dict[str, Dict[str, object]]:
        """Every sampled ``Object.field`` with its raw tallies."""
        with self._cov_lock:
            return {f"{name}.{attr}": cov.as_dict()
                    for (name, attr), cov in sorted(self._cov.items())}

    def coverage_report(self) -> List[Dict[str, object]]:
        """Fields with MIXED lock discipline: accessed both with and
        without a recorded lock held, from more than one thread, with
        at least one observed write — OR holding a mutable container,
        whose mutation/iteration happens through method calls the
        attribute sampler cannot see (``self._q.append`` is a read of
        ``_q`` plus a call), so mixed access alone is the race signal.
        Sorted worst-first (unlocked writes, then unlocked traffic)."""
        out: List[Dict[str, object]] = []
        with self._cov_lock:
            # read the tallies under the same lock _sample mutates them
            # with — this class of all classes must not tear its own rows
            for (name, attr), cov in sorted(self._cov.items()):
                if (cov.locked and cov.unlocked
                        and (cov.writes or cov.container)
                        and len(cov.threads) >= 2):
                    d = cov.as_dict()
                    d["field"] = f"{name}.{attr}"
                    out.append(d)
        out.sort(key=lambda d: (-int(d["unlocked_writes"]),
                                -int(d["unlocked"]), d["field"]))
        return out

    def assert_covered(self, ignore: Tuple[str, ...] = ()) -> None:
        """Fail on any mixed-discipline field not named in ``ignore``
        (entries are ``Object.field``; every ignore should carry a
        reason in the calling test, same bar as a lint noqa)."""
        racy = [d for d in self.coverage_report()
                if d["field"] not in ignore]
        if racy:
            detail = "; ".join(
                f"{d['field']} (locked={d['locked']}, "
                f"unlocked={d['unlocked']}, "
                f"unlocked_writes={d['unlocked_writes']}, "
                f"threads={d['threads']})"
                for d in racy)
            raise LockCoverageViolation(
                "mixed lock discipline on shared fields — " + detail)
