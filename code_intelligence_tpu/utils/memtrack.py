"""Device-memory observatory: live-buffer ledger, leak sentinel, and
capacity planner (RUNBOOK §31).

Every other observability plane in this repo measures *time* (tracing,
SLO digests, delivery phase durations); this one measures *bytes*. The
int8 serve path's headline claim is a >=3x resident-footprint drop, the
paged ragged scheduler is premised on page-occupancy accounting, and
the multi-tenant question ("how many tenants' heads fit beside the
encoder") is a capacity question — none of which is answerable from a
wall clock.

:class:`DeviceMemoryLedger` snapshots the process's live device buffers
(``jax.live_arrays()`` — CPU-backend provable, the same buffers a TPU
backend would report) and attributes them, per device, to *registered
owners*: named provider callables (``engine.params``,
``slots.state_arenas``, ``slots.paged_pool``, ...) that return the
arrays a component currently holds. Providers are callables rather than
raw arrays on purpose — schedulers rebuild their arenas on ``reset()``
and rollout swaps engines, and a ledger pinned to dead buffers would
silently attribute nothing. Whatever no owner claims lands in an
explicit ``unattributed`` row, so the table provably sums
(``sum(owners) + unattributed == total`` — the same honesty contract as
the SLO stage table's ``unattributed`` stage). High-watermarks are
tracked per owner and for the process total.

On top of the ledger:

* :class:`DeviceMemoryGrowthSentinel` — a latched ``device_memory_growth``
  sentinel on the flight-recorder
  :class:`~code_intelligence_tpu.utils.flight_recorder.SentinelBank`
  Trip vocabulary (the rollout manager's monitor consumes it with zero
  new plumbing). Feed it :meth:`DeviceMemoryLedger.sentinel_record`
  records; it trips once per sustained growth episode over the ledger's
  baseline and re-arms when the growth is released.
* :meth:`DeviceMemoryLedger.capacity_report` — the planner: given the
  ledger, a per-version footprint, and the paged-arena geometry, how
  many more model versions (or per-tenant heads) fit in the device
  budget — the input ROADMAP direction 4 needs.
* :func:`debug_memory_response` — the ``/debug/memory`` JSON body
  (server, worker, and the router's ``/fleet/memory`` rollup), which is
  also what ``perfwatch snapshot --memory`` serializes.

The steady-state *guard* built on the same measurement —
``analysis/runtime.py::memory_guard`` — lives with the other runtime
auditors (``recompile_guard``, ``no_implicit_transfers``) and shares
:func:`live_buffer_totals` below.
"""

from __future__ import annotations

import json
import logging
import threading
import time
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Tuple

from code_intelligence_tpu.utils.flight_recorder import Sentinel

log = logging.getLogger(__name__)

#: record kind the ledger emits and the sentinel keys on (the SLO
#: stream uses "slo", serve observations use "serve" — same vocabulary)
MEMORY_RECORD_KIND = "memory"

#: the catch-all owner row: live bytes no registered provider claims
UNATTRIBUTED = "unattributed"

#: per-device budget for the capacity planner when neither the caller
#: nor the device supplies one (a 16 GiB HBM class device). Only the CPU
#: backend lands here — there it is a planning fiction; an accelerator
#: reports its own ``memory_stats()["bytes_limit"]``
DEFAULT_DEVICE_BUDGET_BYTES = 16 * (1 << 30)


def device_budget_bytes() -> Optional[int]:
    """What the runtime says one device can hold
    (``memory_stats()["bytes_limit"]``: 16,909,336,064 B on the TPU v5e,
    PR 21 chip run), or None where the backend reports nothing (CPU)."""
    import jax

    stats = jax.devices()[0].memory_stats()
    limit = (stats or {}).get("bytes_limit")
    return int(limit) if limit else None


def _fmt_bytes(n: float) -> str:
    """Human bytes for sentinel/guard messages (exact ints elsewhere)."""
    n = float(n)
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(n) < 1024.0 or unit == "GiB":
            return f"{n:.1f}{unit}" if unit != "B" else f"{int(n)}B"
        n /= 1024.0
    return f"{n:.1f}GiB"


def _array_shards(arr) -> List[Tuple[str, int]]:
    """``(device, bytes)`` per addressable shard of one live array —
    physical per-device bytes (a replicated array costs every device its
    full copy; ``.nbytes`` alone would under-report that).

    Computed from sharding METADATA only (``shard_shape`` + the
    device→index map), never ``addressable_shards[i].data``: touching
    ``.data`` materialises per-shard view arrays that jax caches on the
    parent, so the measurement itself would grow ``jax.live_arrays()``
    and a ``memory_guard`` baseline would plant the very growth it then
    reports (views are an identity fast-path on a 1-device host, which
    is why only forced-multi-device sessions ever saw it)."""
    out: List[Tuple[str, int]] = []
    sharding = getattr(arr, "sharding", None)
    if sharding is not None:
        try:
            shape = tuple(arr.shape)
            per_shard = 1
            for d in sharding.shard_shape(shape):
                per_shard *= int(d)
            per_shard *= int(arr.dtype.itemsize)
            index_map = sharding.addressable_devices_indices_map(shape)
            for dev in index_map:
                out.append((str(dev), per_shard))
        except Exception:
            out = []
    if not out:
        try:
            dev = next(iter(arr.devices()))
        except Exception:
            dev = "unknown"
        out.append((str(dev), int(getattr(arr, "nbytes", 0) or 0)))
    return out


def live_buffer_totals() -> Tuple[int, int]:
    """``(total_bytes, n_arrays)`` over ``jax.live_arrays()`` — the one
    measurement the ledger and ``memory_guard`` share, so their numbers
    can never disagree about what "total" means."""
    import jax

    total = 0
    arrs = jax.live_arrays()
    for a in arrs:
        total += sum(b for _, b in _array_shards(a))
    return int(total), len(arrs)


class DeviceMemoryLedger:
    """Attributed live-device-buffer accounting for one process.

    Register owners with :meth:`register` (device arrays, via provider
    callables) and :meth:`register_host` (host-tier byte counters, e.g.
    the embed cache); read it with :meth:`snapshot`; feed the sentinel
    stream with :meth:`sentinel_record` against a :meth:`set_baseline`
    steady state; plan with :meth:`capacity_report`.
    """

    def __init__(self, registry=None,
                 now: Callable[[], float] = time.time):
        self._lock = threading.RLock()
        # insertion order is claim order: when two owners return the
        # same array, the FIRST registration wins (counted once — the
        # table must sum, so a buffer can have at most one owner)
        self._providers: "OrderedDict[str, Callable[[], Any]]" = OrderedDict()
        self._host_providers: "OrderedDict[str, Callable[[], int]]" = \
            OrderedDict()
        self._geometry: Dict[str, Any] = {}
        self._watermarks: Dict[str, int] = {}
        self._total_watermark = 0
        self._baseline: Optional[Dict[str, Any]] = None
        self._now = now
        self.registry = None
        if registry is not None:
            self.bind_registry(registry)

    # -- owner registration ------------------------------------------------

    def register(self, owner: str, provider: Callable[[], Any],
                 replace: bool = False) -> None:
        """Register ``owner`` as the claimant of whatever device arrays
        ``provider()`` returns (any pytree; non-array leaves and ``None``
        are ignored). Duplicate names raise unless ``replace`` — a
        silently shadowed owner would corrupt attribution."""
        with self._lock:
            if owner in self._providers and not replace:
                raise ValueError(f"memory owner {owner!r} already registered")
            self._providers[owner] = provider

    def unregister(self, owner: str) -> bool:
        with self._lock:
            self._watermarks.pop(owner, None)
            return self._providers.pop(owner, None) is not None

    def register_host(self, owner: str, provider: Callable[[], int],
                      replace: bool = False) -> None:
        """Register a HOST-tier byte counter (e.g. the embed cache's
        resident bytes). Host rows ride the snapshot for the capacity
        planner but never count against device totals — host RAM is not
        HBM."""
        with self._lock:
            if owner in self._host_providers and not replace:
                raise ValueError(
                    f"host memory owner {owner!r} already registered")
            self._host_providers[owner] = provider

    def owners(self) -> List[str]:
        with self._lock:
            return list(self._providers)

    def note_geometry(self, **geometry) -> None:
        """Attach arena geometry (``pages_total``, ``page_len``,
        ``page_bytes``, ...) for :meth:`capacity_report` — the paged
        scheduler calls this when it registers its owners."""
        with self._lock:
            self._geometry.update(geometry)

    # -- metrics -----------------------------------------------------------

    def bind_registry(self, registry) -> None:
        """Declare the ``hbm_*`` gauges; every :meth:`snapshot` call
        refreshes them (the /metrics scrape path snapshots first)."""
        if registry is None or self.registry is registry:
            return
        registry.gauge("hbm_total_bytes",
                       "live device-buffer bytes, all devices (ledger total)")
        registry.gauge("hbm_unattributed_bytes",
                       "live device bytes no registered owner claims")
        registry.gauge("hbm_watermark_bytes",
                       "high-watermark of hbm_total_bytes this process")
        registry.gauge("hbm_owner_bytes",
                       "live device bytes attributed to one registered "
                       "owner (label: owner)")
        self.registry = registry

    # -- snapshot ----------------------------------------------------------

    def snapshot(self) -> Dict[str, Any]:
        """One attributed pass over ``jax.live_arrays()``.

        The returned table sums exactly by construction: every live
        buffer lands in exactly one owner row or in ``unattributed``,
        and per-device rows are the same enumeration grouped by shard
        device.
        """
        import jax

        with self._lock:
            providers = list(self._providers.items())
            host_providers = list(self._host_providers.items())

        # claim map: id(array) -> owner, first registration wins
        claims: Dict[int, str] = {}
        provider_errors: Dict[str, str] = {}
        for owner, provider in providers:
            try:
                leaves = jax.tree_util.tree_leaves(provider())
            except Exception as e:  # a failed provider attributes nothing
                provider_errors[owner] = f"{type(e).__name__}: {e}"[:200]
                leaves = []
            for leaf in leaves:
                if hasattr(leaf, "addressable_shards") or hasattr(
                        leaf, "devices"):
                    claims.setdefault(id(leaf), owner)

        owner_rows: "OrderedDict[str, Dict[str, int]]" = OrderedDict(
            (owner, {"bytes": 0, "buffers": 0}) for owner, _ in providers)
        unatt = {"bytes": 0, "buffers": 0}
        devices: Dict[str, Dict[str, Any]] = {}
        total_bytes = 0
        total_buffers = 0
        for arr in jax.live_arrays():
            owner = claims.get(id(arr))
            row = owner_rows[owner] if owner is not None else unatt
            arr_bytes = 0
            for dev, nbytes in _array_shards(arr):
                arr_bytes += nbytes
                drow = devices.setdefault(
                    dev, {"total_bytes": 0, "owners": {}})
                drow["total_bytes"] += nbytes
                key = owner if owner is not None else UNATTRIBUTED
                drow["owners"][key] = drow["owners"].get(key, 0) + nbytes
            row["bytes"] += arr_bytes
            row["buffers"] += 1
            total_bytes += arr_bytes
            total_buffers += 1

        host: "OrderedDict[str, int]" = OrderedDict()
        for owner, provider in host_providers:
            try:
                host[owner] = int(provider())
            except Exception as e:
                provider_errors[owner] = f"{type(e).__name__}: {e}"[:200]
                host[owner] = 0

        with self._lock:
            self._total_watermark = max(self._total_watermark, total_bytes)
            for owner, row in owner_rows.items():
                self._watermarks[owner] = max(
                    self._watermarks.get(owner, 0), row["bytes"])
            watermark = self._total_watermark
            owner_watermarks = dict(self._watermarks)

        attributed = sum(r["bytes"] for r in owner_rows.values())
        snap = {
            "wall_time": self._now(),
            "backend": jax.default_backend(),
            "n_devices": len(devices),
            "total_bytes": int(total_bytes),
            "total_buffers": int(total_buffers),
            "owners": {o: dict(r) for o, r in owner_rows.items()},
            "unattributed": dict(unatt),
            "devices": devices,
            "host": dict(host),
            "watermark_bytes": int(watermark),
            "owner_watermarks": owner_watermarks,
            # recomputed, not assumed — the honesty pin tests assert on
            "sums_exactly": bool(
                attributed + unatt["bytes"] == total_bytes),
        }
        if provider_errors:
            snap["provider_errors"] = provider_errors
        if self.registry is not None:
            try:
                self.registry.set("hbm_total_bytes", total_bytes)
                self.registry.set("hbm_unattributed_bytes", unatt["bytes"])
                self.registry.set("hbm_watermark_bytes", watermark)
                for owner, row in owner_rows.items():
                    self.registry.set("hbm_owner_bytes", row["bytes"],
                                      labels={"owner": owner})
            except Exception:  # observer, never a dependency
                log.debug("hbm gauge export failed", exc_info=True)
        return snap

    # -- sentinel stream ---------------------------------------------------

    def set_baseline(self, snap: Optional[Dict[str, Any]] = None) -> dict:
        """Declare the current footprint the steady state — subsequent
        :meth:`sentinel_record` growth is measured against it."""
        snap = snap or self.snapshot()
        base = {
            "total_bytes": snap["total_bytes"],
            "total_buffers": snap["total_buffers"],
            "owners": {o: r["bytes"] for o, r in snap["owners"].items()},
            "unattributed_bytes": snap["unattributed"]["bytes"],
        }
        with self._lock:
            self._baseline = base
        return base

    def sentinel_record(self, step: int = 0,
                        snap: Optional[Dict[str, Any]] = None) -> dict:
        """A ``kind="memory"`` record for the SentinelBank: growth of
        the live footprint over the declared baseline, with the grown
        owners named (so a trip reason points at a component, not a
        number). With no baseline set, the first call sets one (growth
        0 — a sentinel can't claim a leak with nothing to compare to).
        """
        snap = snap or self.snapshot()
        with self._lock:
            base = self._baseline
        if base is None:
            base = self.set_baseline(snap)
        cur_owners = {o: r["bytes"] for o, r in snap["owners"].items()}
        cur_owners[UNATTRIBUTED] = snap["unattributed"]["bytes"]
        base_owners = dict(base["owners"])
        base_owners[UNATTRIBUTED] = base["unattributed_bytes"]
        grown = {}
        for owner, cur in cur_owners.items():
            delta = cur - base_owners.get(owner, 0)
            if delta > 0:
                grown[owner] = int(delta)
        return {
            "kind": MEMORY_RECORD_KIND,
            "step": int(step),
            "wall_time": snap["wall_time"],
            "total_bytes": snap["total_bytes"],
            "total_buffers": snap["total_buffers"],
            "baseline_bytes": base["total_bytes"],
            "baseline_buffers": base["total_buffers"],
            "growth_bytes": int(snap["total_bytes"] - base["total_bytes"]),
            "growth_buffers": int(
                snap["total_buffers"] - base["total_buffers"]),
            "unattributed_growth_bytes": int(
                snap["unattributed"]["bytes"] - base["unattributed_bytes"]),
            "grown_owners": grown,
        }

    # -- capacity planner --------------------------------------------------

    def capacity_report(self, budget_bytes: Optional[int] = None,
                        version_bytes: Optional[int] = None,
                        head_bytes: Optional[int] = None,
                        snap: Optional[Dict[str, Any]] = None) -> dict:
        """How much more fits: versions (``engine.params*`` footprint)
        and per-tenant heads against the per-device budget, plus the
        paged-arena geometry when the scheduler noted one.

        ``rows_fit`` is the headroom over the engine's
        ``state_bytes_per_row`` note: how many rows of carried encoder
        state fit, which bounds ``batch_size`` once the state is large.

        ``budget_bytes`` is PER DEVICE (default: what the device reports,
        ``budget_source: "device"``; the 16 GiB constant only where it
        reports nothing); headroom is measured on the fullest device (the
        one that OOMs first). ``version_bytes``
        defaults to the largest ``engine.params*`` owner row — the
        observed cost of one resident model version; ``head_bytes`` to
        the geometry's ``head_bytes`` note when present.
        """
        snap = snap or self.snapshot()
        with self._lock:
            geometry = dict(self._geometry)
        if budget_bytes is not None:
            budget, budget_source = int(budget_bytes), "caller"
        else:
            budget, budget_source = device_budget_bytes(), "device"
            if budget is None:
                budget, budget_source = DEFAULT_DEVICE_BUDGET_BYTES, "default"
        used = max((d["total_bytes"] for d in snap["devices"].values()),
                   default=snap["total_bytes"])
        headroom = max(0, budget - used)
        if version_bytes is None:
            candidates = [r["bytes"] for o, r in snap["owners"].items()
                          if o.startswith("engine.params") and r["bytes"] > 0]
            version_bytes = max(candidates) if candidates else None
        if head_bytes is None:
            head_bytes = geometry.get("head_bytes")
        report = {
            "budget_bytes": int(budget),
            "budget_source": budget_source,
            "used_bytes_fullest_device": int(used),
            "headroom_bytes": int(headroom),
            "version_bytes": None if version_bytes is None
            else int(version_bytes),
            "versions_fit": None if not version_bytes
            else int(headroom // int(version_bytes)),
            "head_bytes": None if head_bytes is None else int(head_bytes),
            "heads_fit": None if not head_bytes
            else int(headroom // int(head_bytes)),
            # rows of carried encoder state (the engine's geometry note,
            # from the encoder contract): the batch the headroom takes
            "rows_fit": None if not geometry.get("state_bytes_per_row")
            else int(headroom // int(geometry["state_bytes_per_row"])),
            "geometry": geometry,
            "host": dict(snap["host"]),
        }
        return report

    def watermarks(self) -> Dict[str, int]:
        with self._lock:
            out = dict(self._watermarks)
            out["_total"] = self._total_watermark
            return out


# ---------------------------------------------------------------------
# Sentinel
# ---------------------------------------------------------------------


class DeviceMemoryGrowthSentinel(Sentinel):
    """Trips when a ``kind="memory"`` record shows the live footprint
    grown past ``tolerance_bytes`` (or any net new buffers past
    ``tolerance_buffers``) over the ledger baseline. Latched — one trip
    per sustained growth episode; it re-arms when the growth is
    released back under tolerance, so a slow leak is one alert, not one
    per scrape."""

    name = "device_memory_growth"
    severity = "halt"

    def __init__(self, tolerance_bytes: int = 0,
                 tolerance_buffers: int = 0):
        if tolerance_bytes < 0 or tolerance_buffers < 0:
            raise ValueError("tolerances must be >= 0")
        self.tolerance_bytes = int(tolerance_bytes)
        self.tolerance_buffers = int(tolerance_buffers)
        self._latched = False

    def reset(self) -> None:
        self._latched = False

    @property
    def latched(self) -> bool:
        return self._latched

    def check(self, rec):
        if rec.get("kind") != MEMORY_RECORD_KIND:
            return None
        growth = rec.get("growth_bytes", 0)
        buffers = rec.get("growth_buffers", 0)
        growing = (growth > self.tolerance_bytes
                   or buffers > self.tolerance_buffers)
        if not growing:
            self._latched = False
            return None
        if self._latched:
            return None
        self._latched = True
        grown = rec.get("grown_owners") or {}
        if grown:
            names = ", ".join(
                f"{o} +{_fmt_bytes(b)}" for o, b in sorted(
                    grown.items(), key=lambda kv: -kv[1]))
        else:
            names = UNATTRIBUTED
        return (f"device memory grew {_fmt_bytes(growth)} "
                f"(+{buffers} buffers) over the "
                f"{_fmt_bytes(rec.get('baseline_bytes', 0))} baseline "
                f"— owners: {names}")


def default_memory_sentinels(tolerance_bytes: int = 0) -> List[Sentinel]:
    return [DeviceMemoryGrowthSentinel(tolerance_bytes=tolerance_bytes)]


# ---------------------------------------------------------------------
# Debug surface
# ---------------------------------------------------------------------


def debug_memory_response(ledger, query: str = ""):
    """``(status, body_bytes, content_type)`` for ``/debug/memory`` —
    snapshot + sentinel record + capacity report in one body (the
    perfwatch --memory snapshot source). ``?budget_bytes=N`` re-plans
    against a caller budget. The debug surface must not 500 the
    listener."""
    try:
        if ledger is None:
            return 404, json.dumps(
                {"error": "no memory ledger attached"}).encode(), \
                "application/json"
        from urllib.parse import parse_qs

        params = parse_qs(query or "")
        budget = None
        if params.get("budget_bytes"):
            budget = int(params["budget_bytes"][0])
        snap = ledger.snapshot()
        body = {
            "snapshot": snap,
            "sentinel": ledger.sentinel_record(snap=snap),
            "capacity": ledger.capacity_report(budget_bytes=budget,
                                               snap=snap),
            "watermarks": ledger.watermarks(),
        }
        return 200, json.dumps(body).encode(), "application/json"
    except Exception as e:
        return 500, json.dumps(
            {"error": str(e)[:200]}).encode(), "application/json"
