"""fleetwatch: the fleet half of the perfwatch regression gate.

``perfwatch`` (utils/perfwatch.py, RUNBOOK §22) gates ONE server's SLO
observatory against a baseline. Behind the fleet router (serving/fleet/)
that verdict is blind in exactly the way that matters at N replicas: the
merged rollup can sit inside the band while one replica quietly doubles
its p99 — the fleet average launders the straggler. This module gives
``perfwatch --fleet`` its machinery:

* :func:`take_fleet_snapshot` pulls the router's ``/fleet/slo`` — the
  observatory rollup whose body embeds the SERIALIZED sketches for the
  merged fleet series AND every member's per-stage series — plus
  ``/fleet/members`` and a ``fleet_*`` metrics excerpt, provenance-
  stamped ``fresh`` like every bench line since PR 4.
* :func:`compare_fleet` diffs current against baseline at BOTH levels
  on deserialized digests (the identical-estimator rule): the fleet
  rollup (read exactly like a single-server diff) and each member's
  own series. A regression names the stage AND the member — "fleet p99
  is up" is a page; "``127.0.0.1:8081``'s ``engine.group_embed`` is up
  3x while its siblings held" is a diagnosis.
* A line carrying ``member_latency_digests`` (keyed by the
  ``X-Fleet-Member`` response header) is diffable per replica through the
  same gate; nothing in the tree writes one since PR 28 (ROADMAP D15).

Honesty rules are inherited wholesale from perfwatch: provenance
respected, low-count series skipped loudly, nothing-comparable exits 2,
``latency_kind`` mismatches refused. jax-free — CI-runner code.
"""

from __future__ import annotations

import json
import logging
import time
import urllib.request
from typing import Any, Dict, List, Optional, Tuple

from code_intelligence_tpu.utils.perfwatch import _compare_series, _git_rev

log = logging.getLogger(__name__)

#: /metrics families worth keeping in a fleet snapshot
_FLEET_METRIC_PREFIXES = ("fleet_", "replica_outlier_")


def _http_json(url: str, timeout: float) -> Optional[dict]:
    try:
        with urllib.request.urlopen(url, timeout=timeout) as resp:
            return json.loads(resp.read().decode())
    except Exception as e:
        log.warning("fleet snapshot pull %s failed: %s", url, e)
        return None


# ---------------------------------------------------------------------
# Snapshot
# ---------------------------------------------------------------------


def take_fleet_snapshot(url: str, timeout: float = 10.0) -> Dict[str, Any]:
    """One fleetwatch snapshot of a live ROUTER: the ``/fleet/slo``
    rollup (serialized digests included, fleet + per-member),
    ``/fleet/members`` state, and a ``fleet_*`` metrics excerpt."""
    base = url.rstrip("/")
    slo = _http_json(f"{base}/fleet/slo", timeout)
    if slo is None or not (slo.get("fleet") or {}).get("digests"):
        raise RuntimeError(
            f"{base}/fleet/slo unavailable or digest-less — is this a "
            f"fleet router with the observatory enabled, and have its "
            f"members served (and been scraped for) any traffic?")
    snap: Dict[str, Any] = {
        "kind": "fleetwatch_snapshot",
        "url": base,
        "latency_kind": slo.get("latency_kind") or "http_e2e",
        "provenance": "fresh",
        "measured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "measured_git": _git_rev(),
        "fleet_slo": slo,
    }
    members = _http_json(f"{base}/fleet/members", timeout)
    if members is not None:
        snap["members"] = members
    try:
        with urllib.request.urlopen(f"{base}/metrics",
                                    timeout=timeout) as resp:
            text = resp.read().decode()
        snap["metrics_excerpt"] = "\n".join(
            l for l in text.splitlines()
            if l.startswith(_FLEET_METRIC_PREFIXES)
            or (l.startswith("#")
                and any(p in l for p in _FLEET_METRIC_PREFIXES)))
    except Exception as e:
        log.warning("fleet metrics pull failed: %s", e)
    return snap


# ---------------------------------------------------------------------
# Series extraction
# ---------------------------------------------------------------------


def fleet_series_of(snap: dict) -> Tuple[Dict[str, dict],
                                         Dict[str, Dict[str, dict]]]:
    """``(fleet_series, member_series)`` — serialized digests — from any
    supported shape: a fleetwatch snapshot, a raw ``/fleet/slo`` body,
    or a fleet bench JSON line. ``fleet_series`` maps
    series name (``e2e`` + stages) -> digest; ``member_series`` maps
    member id -> the same, per member."""
    if snap.get("kind") == "fleetwatch_snapshot":
        snap = snap.get("fleet_slo") or {}
    if snap.get("kind") == "fleet_slo" or (
            isinstance(snap.get("fleet"), dict)
            and "digests" in snap["fleet"]):
        fleet_block = snap.get("fleet") or {}
        dg = fleet_block.get("digests") or {}
        fleet: Dict[str, dict] = {}
        if dg.get("e2e"):
            fleet["e2e"] = dg["e2e"]
        fleet.update(dg.get("stages") or {})
        members: Dict[str, Dict[str, dict]] = {}
        for mid, info in (snap.get("members") or {}).items():
            series = dict(info.get("digests") or {})
            if series:
                members[mid] = series
        return fleet, members
    if "member_latency_digests" in snap or (
            isinstance(snap.get("fleet"), dict)
            and "member_latency_digests" in snap["fleet"]):
        # a fleet bench line: the fleet side's per-member
        # request digests, keyed by X-Fleet-Member
        side = snap if "member_latency_digests" in snap else snap["fleet"]
        fleet = {}
        if side.get("latency_digest"):
            fleet["e2e"] = side["latency_digest"]
        members = {mid: {"e2e": d} for mid, d in
                   (side.get("member_latency_digests") or {}).items()}
        return fleet, members
    return {}, {}


# ---------------------------------------------------------------------
# Comparison
# ---------------------------------------------------------------------


def compare_fleet(current: dict, baseline: dict,
                  quantiles: Tuple[float, ...] = (0.5, 0.99),
                  band_pct: float = 25.0, abs_floor_ms: float = 5.0,
                  min_count: int = 10) -> Dict[str, Any]:
    """Two-level quantile regression report: the merged fleet rollup
    plus every member's own series, on deserialized digests. Entries
    carry ``member`` (None at the fleet level), and the verdict lists
    ``regressed`` (member, stage) pairs — the gate's exit-1 message
    names both."""
    cur_fleet, cur_members = fleet_series_of(current)
    base_fleet, base_members = fleet_series_of(baseline)
    regressions: List[dict] = []
    improvements: List[dict] = []
    skipped: List[dict] = []
    compared: List[str] = []
    uncompared: List[str] = []
    ck, bk = current.get("latency_kind"), baseline.get("latency_kind")
    if ck and bk and ck != bk:
        return {
            "ok": False, "regressed": [], "regressed_stages": [],
            "regressed_members": [], "regressions": [],
            "improvements": [], "compared": [],
            "uncompared": [],
            "skipped": [{"series": "*",
                         "reason": f"latency_kind mismatch (current="
                                   f"{ck!r}, baseline={bk!r})"}],
            "band_pct": band_pct, "abs_floor_ms": abs_floor_ms,
            "quantiles": list(quantiles),
            "baseline_provenance": baseline.get("provenance"),
            "baseline_git": baseline.get("measured_git"),
        }

    def _one(label: str, member: Optional[str], name: str,
             cur: dict, base: dict) -> None:
        regs, imps, skip = _compare_series(
            label, cur, base, quantiles, band_pct, abs_floor_ms, min_count)
        for e in regs:
            e["member"], e["stage"] = member, name
        for e in imps:
            e["member"], e["stage"] = member, name
        regressions.extend(regs)
        improvements.extend(imps)
        if skip:
            skipped.append({**skip, "member": member})
        else:
            compared.append(label)

    for name in sorted(set(cur_fleet) & set(base_fleet)):
        _one(f"fleet/{name}", None, name, cur_fleet[name], base_fleet[name])
    uncompared += [f"fleet/{n}" for n in
                   sorted(set(cur_fleet) ^ set(base_fleet))]
    for mid in sorted(set(cur_members) & set(base_members)):
        cs, bs = cur_members[mid], base_members[mid]
        for name in sorted(set(cs) & set(bs)):
            _one(f"{mid}/{name}", mid, name, cs[name], bs[name])
        uncompared += [f"{mid}/{n}" for n in sorted(set(cs) ^ set(bs))]
    uncompared += [f"member:{m}" for m in
                   sorted(set(cur_members) ^ set(base_members))]
    if not compared:
        skipped.append({"series": "*",
                        "reason": "no comparable fleet or member series "
                                  "between current and baseline"})
    regressions.sort(key=lambda r: -r["delta_ms"])
    # pairs in severity order (first appearance in the delta-sorted
    # regressions), deduped: "worst first" must be TRUE of the verdict —
    # an operator reads the first pair
    pairs: List[Tuple[str, str]] = []
    for r in regressions:
        pair = (r["member"] or "fleet", r["stage"])
        if pair not in pairs:
            pairs.append(pair)
    return {
        "ok": not regressions and bool(compared),
        "regressed": [{"member": m, "stage": s} for m, s in pairs],
        "regressed_stages": sorted({r["stage"] for r in regressions}),
        "regressed_members": sorted({r["member"] for r in regressions
                                     if r["member"] is not None}),
        "regressions": regressions,
        "improvements": improvements,
        "compared": compared,
        "uncompared": uncompared,
        "skipped": skipped,
        "band_pct": band_pct,
        "abs_floor_ms": abs_floor_ms,
        "quantiles": list(quantiles),
        "baseline_provenance": baseline.get("provenance"),
        "baseline_git": baseline.get("measured_git"),
    }


def format_verdict(report: Dict[str, Any]) -> str:
    """The one-line human verdict for exit 1: every regressed
    (member, stage) pair, worst first."""
    pairs = ", ".join(f"{p['member']}:{p['stage']}"
                      for p in report.get("regressed", ()))
    return (f"fleetwatch: REGRESSION in {pairs} "
            f"(band {report.get('band_pct', 0):g}%, floor "
            f"{report.get('abs_floor_ms', 0):g}ms)")
