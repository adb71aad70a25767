"""perfwatch: the serve-path latency regression gate.

Nothing between bench runs detects drift in the serve path. perfwatch
closes that gap: it snapshots a *live* server's SLO observatory
(``/debug/slo`` — streaming quantile digests, per-stage attribution,
utils/digest.py + serving/slo.py), diffs quantiles against a committed
baseline snapshot or a ``BENCH_*.json`` line, and exits nonzero when
any stage or the end-to-end latency sits outside the regression band —
**naming the regressed stage**, because "p99 is up" without "it's
``slots.device_steps``" is a page, not a diagnosis.

Three subcommands::

    # pull /debug/slo + /metrics + /debug/flight from a live server
    python -m code_intelligence_tpu.utils.perfwatch snapshot \
        --url http://127.0.0.1:8080 --out perf_baseline.json

    # regression gate: live (or --current file) vs the baseline
    python -m code_intelligence_tpu.utils.perfwatch diff \
        --url http://127.0.0.1:8080 --baseline perf_baseline.json \
        [--band_pct 25] [--abs_floor_ms 5] [--allow_stale]

    # device-free estimator self-check (runbook_ci --check_slo runs it
    # against the committed fixture snapshot)
    python -m code_intelligence_tpu.utils.perfwatch selfcheck

Honesty rules, inherited from the bench harness:

* **Identical estimators** — snapshots and bench lines carry the
  *serialized digest*, not precomputed percentiles; both sides of a
  diff deserialize and query the same DDSketch math, so a regression
  verdict can never be bucket-boundary arithmetic.
* **Provenance is respected** — a baseline whose stamp is anything
  but ``fresh`` (an error line's ``no_measurement_available``, or no
  stamp at all) is REFUSED unless ``--allow_stale``: gating fresh
  numbers against a non-measurement silently moves the goalposts.
* **Low-count series are skipped, loudly** — a digest with fewer than
  ``--min_count`` samples is reported as ``skipped``, never silently
  compared (one warm-up request is not a distribution).

Exit codes: 0 in-band, 1 regression, 2 refused/unusable input.
jax-free by construction — this must run from any CI runner.
"""

from __future__ import annotations

import argparse
import json
import logging
import math
import sys
import time
import urllib.request
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

from code_intelligence_tpu.utils.digest import QuantileDigest
from code_intelligence_tpu.utils.eventlog import DELIVERY_LATENCY_KIND

log = logging.getLogger(__name__)

#: provenance values a baseline may carry and still gate (PR 4 stamps)
FRESH_PROVENANCE = ("fresh",)
#: the committed device-free self-check fixture
DEFAULT_FIXTURE = Path(__file__).resolve().parent / "fixtures" \
    / "perfwatch_snapshot.json"

#: /metrics families worth keeping in a snapshot (full exposition text
#: is unbounded label cardinality; the gate only needs the serve path)
_METRIC_PREFIXES = ("slo_", "stage_", "embedding_", "slot_", "cache_",
                    "canary_", "compile", "profile_",
                    "jit_recompiles_total", "h2d_d2h_bytes")


class StaleBaseline(RuntimeError):
    """Baseline provenance is not fresh (and --allow_stale was not
    given)."""


# ---------------------------------------------------------------------
# Snapshot
# ---------------------------------------------------------------------


def _http_json(url: str, timeout: float) -> Optional[dict]:
    try:
        with urllib.request.urlopen(url, timeout=timeout) as resp:
            return json.loads(resp.read().decode())
    except Exception as e:
        log.warning("snapshot pull %s failed: %s", url, e)
        return None


def _git_rev() -> str:
    try:
        import subprocess

        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
        ).stdout.strip() or "unknown"
    except Exception:
        return "unknown"


def take_snapshot(url: str, timeout: float = 10.0) -> Dict[str, Any]:
    """One perfwatch snapshot of a live server: the SLO observatory
    body (serialized digests included), a filtered /metrics excerpt,
    and the XLA compile ledger — provenance-stamped ``fresh`` because
    it was just measured."""
    base = url.rstrip("/")
    slo = _http_json(f"{base}/debug/slo", timeout)
    if slo is None or "digests" not in slo:
        raise RuntimeError(
            f"{base}/debug/slo unavailable or digest-less — is the "
            f"server running with the SLO observatory enabled?")
    snap: Dict[str, Any] = {
        "kind": "perfwatch_snapshot",
        "url": base,
        # what the e2e digest measures: /debug/slo declares it from its
        # own root span — a MetricsServer-hosted SLO on a non-HTTP
        # process (worker, training) is NOT http_e2e (bench lines
        # declare their own kind; compare() refuses mismatches)
        "latency_kind": slo.get("latency_kind") or "http_e2e",
        "provenance": "fresh",
        "measured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "measured_git": _git_rev(),
        "slo": slo,
    }
    flight = _http_json(f"{base}/debug/flight", timeout)
    if flight is not None:
        snap["compiles"] = flight.get("compiles", [])
    try:
        with urllib.request.urlopen(f"{base}/metrics",
                                    timeout=timeout) as resp:
            text = resp.read().decode()
        snap["metrics_excerpt"] = "\n".join(
            l for l in text.splitlines()
            if l.startswith(_METRIC_PREFIXES)
            or (l.startswith("#") and any(p in l for p in _METRIC_PREFIXES)))
    except Exception as e:
        log.warning("metrics pull failed: %s", e)
    return snap


def take_delivery_snapshot(url: str, timeout: float = 10.0
                           ) -> Dict[str, Any]:
    """One delivery-phase snapshot of a live loop: the per-phase
    duration digests from ``/debug/journal`` (RUNBOOK §29), under the
    same honesty stamps as the serve-path snapshot — serialized
    digests, ``latency_kind`` declared, provenance ``fresh``."""
    base = url.rstrip("/")
    body = _http_json(f"{base}/debug/journal", timeout)
    phase = (body or {}).get("phase_seconds")
    if not phase or not phase.get("digests"):
        raise RuntimeError(
            f"{base}/debug/journal has no phase_seconds digests — has "
            f"the delivery loop completed any phase with a journal "
            f"attached?")
    return {
        "kind": "perfwatch_delivery_snapshot",
        "url": base,
        "latency_kind": phase.get("latency_kind") or DELIVERY_LATENCY_KIND,
        "provenance": phase.get("provenance") or "fresh",
        "measured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "measured_git": _git_rev(),
        "digests": dict(phase["digests"]),
    }


#: what a memory snapshot measures — the cross-kind refusal token for
#: --memory mode (a byte footprint must never gate a latency digest)
MEMORY_KIND = "device_memory_bytes"


def _memory_snap_from_body(body: dict, url: Optional[str]
                           ) -> Dict[str, Any]:
    """Normalize a ``/debug/memory`` body to one perfwatch memory
    snapshot: flat per-owner byte rows (no digests — a footprint is a
    point measurement, not a distribution), honesty-stamped like every
    other snapshot kind."""
    snap = body.get("snapshot") or {}
    owners = {name: int(row.get("bytes", 0))
              for name, row in (snap.get("owners") or {}).items()}
    host = {name: int(b) for name, b in (snap.get("host") or {}).items()}
    return {
        "kind": "perfwatch_memory_snapshot",
        "url": url,
        "latency_kind": MEMORY_KIND,
        "provenance": "fresh",
        "measured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "measured_git": _git_rev(),
        "total_bytes": int(snap.get("total_bytes", 0)),
        "total_buffers": int(snap.get("total_buffers", 0)),
        "unattributed_bytes": int(
            (snap.get("unattributed") or {}).get("bytes", 0)),
        "owners": owners,
        "host": host,
        "watermark_bytes": int(snap.get("watermark_bytes", 0)),
        "capacity": body.get("capacity"),
    }


def take_memory_snapshot(url: str, timeout: float = 10.0
                         ) -> Dict[str, Any]:
    """One device-memory snapshot of a live server: the
    ``/debug/memory`` ledger (RUNBOOK §31) flattened to per-owner byte
    rows — the ``perfwatch diff --memory`` footprint-regression gate's
    input."""
    base = url.rstrip("/")
    body = _http_json(f"{base}/debug/memory", timeout)
    if body is None or "snapshot" not in body:
        raise RuntimeError(
            f"{base}/debug/memory unavailable or ledger-less — is the "
            f"server running with the device-memory ledger attached?")
    return _memory_snap_from_body(body, base)


def memory_snapshot_from_ledger(ledger) -> Dict[str, Any]:
    """Device-local sibling of :func:`take_memory_snapshot`: the same
    snapshot shape built straight from a ``DeviceMemoryLedger`` — the
    ``runbook_ci --check_memory`` path, no HTTP server needed."""
    snap = ledger.snapshot()
    return _memory_snap_from_body(
        {"snapshot": snap, "capacity": ledger.capacity_report(snap=snap)},
        url=None)


def _memory_body(snap: dict) -> dict:
    """Normalize either supported memory shape — a perfwatch memory
    snapshot or a raw ``/debug/memory`` body — to the snapshot form."""
    if "snapshot" in snap:  # a raw /debug/memory body
        out = _memory_snap_from_body(snap, url=None)
        # a raw body carries no provenance stamp; don't invent one
        out.pop("provenance", None)
        return out
    return snap


def _memory_rows(snap: dict) -> Dict[str, int]:
    """All gateable byte series of one memory snapshot, flat: owners by
    name, host rows prefixed ``host:``, plus the ``total`` and
    ``unattributed`` aggregates (the honesty rows — attributed growth
    names its owner; unattributed growth is the leak signal)."""
    rows = {name: int(b) for name, b in (snap.get("owners") or {}).items()}
    for name, b in (snap.get("host") or {}).items():
        rows[f"host:{name}"] = int(b)
    rows["total"] = int(snap.get("total_bytes", 0))
    rows["unattributed"] = int(snap.get("unattributed_bytes", 0))
    return rows


def _fmt_b(n: int) -> str:
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(n) < 1024 or unit == "GiB":
            return f"{n:.1f}{unit}" if unit != "B" else f"{n}B"
        n /= 1024.0
    return f"{n}B"


def compare_memory(current: dict, baseline: dict,
                   band_pct: float = 10.0,
                   abs_floor_bytes: int = 1 << 20) -> Dict[str, Any]:
    """Footprint regression report between two memory snapshots (the
    ``perfwatch diff --memory`` gate). Same honesty rules as
    :func:`compare` where they apply: cross-kind refusal (a byte ledger
    must never gate a latency digest), disappeared owners reported in
    ``uncompared`` — and one memory-specific rule: an owner PRESENT in
    current but absent from the baseline gates against 0 (int8 silently
    re-inflating or a canary candidate never released after promote is
    exactly a series appearing out of nowhere)."""
    cur, base = _memory_body(current), _memory_body(baseline)
    regressions: List[dict] = []
    improvements: List[dict] = []
    skipped: List[dict] = []
    compared: List[str] = []
    ck = current.get("latency_kind") or cur.get("latency_kind")
    bk = baseline.get("latency_kind") or base.get("latency_kind")
    cur_rows = _memory_rows(cur)
    base_rows = _memory_rows(base)
    if ck != MEMORY_KIND or bk != MEMORY_KIND:
        skipped.append({
            "series": "*",
            "reason": f"latency_kind mismatch (current={ck!r}, "
                      f"baseline={bk!r}, need {MEMORY_KIND!r}): "
                      f"refusing to gate a byte footprint against "
                      f"something else"})
        cur_rows = base_rows = {}
    uncompared = sorted(set(base_rows) - set(cur_rows))
    for name in sorted(cur_rows):
        cur_b = cur_rows[name]
        base_b = base_rows.get(name, 0)  # new owner gates against 0
        compared.append(name)
        delta = cur_b - base_b
        entry = {
            "series": name,
            "current_bytes": cur_b, "baseline_bytes": base_b,
            "delta_bytes": delta,
            "ratio": round(cur_b / base_b, 3) if base_b > 0 else None,
        }
        if cur_b > base_b * (1.0 + band_pct / 100.0) \
                and delta > abs_floor_bytes:
            regressions.append(entry)
        elif base_b > cur_b * (1.0 + band_pct / 100.0) \
                and -delta > abs_floor_bytes:
            improvements.append(entry)
    if not compared:
        skipped.append({"series": "*",
                        "reason": "no comparable series between current "
                                  "and baseline"})
    regressions.sort(key=lambda r: -r["delta_bytes"])
    regressed = sorted({r["series"] for r in regressions})
    return {
        "ok": not regressions and bool(compared),
        "mode": "memory",
        "regressed_stages": regressed,   # main()'s shared verdict key
        "regressed_owners": regressed,
        "regressions": regressions,
        "improvements": improvements,
        "compared": compared,
        "uncompared": uncompared,
        "skipped": skipped,
        "band_pct": band_pct,
        "abs_floor_bytes": int(abs_floor_bytes),
        "baseline_provenance": baseline.get("provenance")
        or base.get("provenance"),
        "baseline_git": baseline.get("measured_git")
        or base.get("measured_git"),
    }


def _delivery_body(snap: dict) -> dict:
    """Normalize any supported delivery shape — a delivery snapshot, a
    raw ``/debug/journal`` body, or a bare ``phase_seconds`` body — to
    one dict carrying ``latency_kind`` / ``provenance`` / ``digests``."""
    if "phase_seconds" in snap:  # a raw /debug/journal body
        return dict(snap["phase_seconds"] or {})
    return snap


def compare_delivery(current: dict, baseline: dict,
                     quantiles: Tuple[float, ...] = (0.5, 0.99),
                     band_pct: float = 50.0, abs_floor_ms: float = 50.0,
                     min_count: int = 1) -> Dict[str, Any]:
    """Phase-duration regression report between two delivery snapshots
    (the ``perfwatch diff --delivery`` gate). Same honesty rules as
    :func:`compare` — identical estimators on serialized digests,
    cross-kind refusal (a phase-duration digest must never gate a
    request-latency digest), loud low-count skips — with delivery-
    appropriate defaults: ``min_count=1`` (one completed cycle is one
    sample per phase) and a wider band (phase durations are seconds-to-
    hours scale and legitimately noisier than request latency)."""
    cur, base = _delivery_body(current), _delivery_body(baseline)
    regressions: List[dict] = []
    improvements: List[dict] = []
    skipped: List[dict] = []
    compared: List[str] = []
    ck = cur.get("latency_kind")
    bk = baseline.get("latency_kind") or base.get("latency_kind")
    cur_d = dict(cur.get("digests") or {})
    base_d = dict(base.get("digests") or {})
    if ck != DELIVERY_LATENCY_KIND or bk != DELIVERY_LATENCY_KIND:
        skipped.append({
            "series": "*",
            "reason": f"latency_kind mismatch (current={ck!r}, "
                      f"baseline={bk!r}, need "
                      f"{DELIVERY_LATENCY_KIND!r}): refusing to gate "
                      f"phase durations against something else"})
        cur_d = base_d = {}
    for name in sorted(set(cur_d) & set(base_d)):
        r, i, s = _compare_series(name, cur_d[name], base_d[name],
                                  quantiles, band_pct, abs_floor_ms,
                                  min_count)
        regressions += r
        improvements += i
        if s:
            skipped.append(s)
        else:
            compared.append(name)
    uncompared = sorted(set(cur_d) ^ set(base_d))
    if not compared:
        skipped.append({"series": "*",
                        "reason": "no comparable phase between current "
                                  "and baseline"})
    regressions.sort(key=lambda r: -r["delta_ms"])
    regressed = sorted({r["series"] for r in regressions})
    return {
        "ok": not regressions and bool(compared),
        "mode": "delivery",
        "regressed_stages": regressed,   # main()'s shared verdict key
        "regressed_phases": regressed,
        "regressions": regressions,
        "improvements": improvements,
        "compared": compared,
        "uncompared": uncompared,
        "skipped": skipped,
        "band_pct": band_pct,
        "abs_floor_ms": abs_floor_ms,
        "quantiles": list(quantiles),
        "baseline_provenance": baseline.get("provenance")
        or base.get("provenance"),
        "baseline_git": baseline.get("measured_git"),
    }


# ---------------------------------------------------------------------
# Baseline loading / normalization
# ---------------------------------------------------------------------


def _parse_any(path: Path) -> dict:
    """A baseline file may be a perfwatch snapshot, a BENCH_* wrapper
    (``{"parsed": {...}}``), a raw bench JSON line, or JSONL of lines —
    normalize to one dict."""
    text = path.read_text().strip()
    try:
        obj = json.loads(text)
    except json.JSONDecodeError:
        # JSONL: keep the LAST line that parses and looks like a bench
        # line (the series convention: newest last)
        obj = None
        for line in text.splitlines():
            line = line.strip()
            if not line:
                continue
            try:
                cand = json.loads(line)
            except json.JSONDecodeError:
                continue
            if isinstance(cand, dict) and (
                    "latency_digest" in cand or "provenance" in cand
                    or cand.get("kind") == "perfwatch_snapshot"):
                obj = cand
        if obj is None:
            raise ValueError(f"no parseable JSON object in {path}")
    if isinstance(obj, dict) and "parsed" in obj and "metric" in obj.get(
            "parsed", {}):
        obj = obj["parsed"]  # BENCH_* wrapper
    if not isinstance(obj, dict):
        raise ValueError(f"{path} is not a JSON object")
    return obj


def digests_of(snap: dict) -> Tuple[Optional[dict], Dict[str, dict]]:
    """``(e2e_digest, stage_digests)`` — serialized — from any
    supported shape (perfwatch snapshot / raw ``/debug/slo`` body /
    bench line carrying ``latency_digest``)."""
    if snap.get("kind") == "perfwatch_snapshot":
        dg = (snap.get("slo") or {}).get("digests") or {}
        return dg.get("e2e"), dict(dg.get("stages") or {})
    if "digests" in snap:  # a raw /debug/slo body
        dg = snap["digests"] or {}
        return dg.get("e2e"), dict(dg.get("stages") or {})
    if "latency_digest" in snap:  # a bench JSON line (no writer: D15)
        return snap["latency_digest"], {}
    return None, {}


def check_provenance(baseline: dict, allow_stale: bool) -> Optional[str]:
    """None when the baseline may gate; otherwise the refusal reason
    (raised as :class:`StaleBaseline` by the CLI)."""
    prov = baseline.get("provenance")
    if prov in FRESH_PROVENANCE:
        return None
    if allow_stale:
        log.warning("diffing against a %r baseline (--allow_stale)", prov)
        return None
    if prov is None:
        return ("baseline carries no provenance stamp — stamp it "
                "(bench/perfwatch lines always do) or pass --allow_stale")
    return (f"baseline provenance is {prov!r} (measured_git="
            f"{baseline.get('measured_git')}, measured_at="
            f"{baseline.get('measured_at')}): gating fresh numbers "
            f"against a stale fallback hides regressions — re-measure, "
            f"or pass --allow_stale to override")


# ---------------------------------------------------------------------
# Comparison
# ---------------------------------------------------------------------


def _compare_series(name: str, cur: dict, base: dict,
                    quantiles: Tuple[float, ...], band_pct: float,
                    abs_floor_ms: float, min_count: int
                    ) -> Tuple[List[dict], List[dict], Optional[dict]]:
    """One series (e2e or a stage): ``(regressions, improvements,
    skipped)`` at the given quantiles, on deserialized digests (the
    identical-estimator rule)."""
    try:
        cd, bd = QuantileDigest.from_dict(cur), QuantileDigest.from_dict(base)
    except (ValueError, KeyError) as e:
        return [], [], {"series": name, "reason": f"undecodable digest: {e}"}
    if cd.count < min_count or bd.count < min_count:
        return [], [], {
            "series": name,
            "reason": f"insufficient samples (current n={cd.count}, "
                      f"baseline n={bd.count}, need {min_count})"}
    regs, imps = [], []
    for q in quantiles:
        cur_ms = cd.quantile(q) * 1e3
        base_ms = bd.quantile(q) * 1e3
        if not (math.isfinite(cur_ms) and math.isfinite(base_ms)):
            continue
        entry = {
            "series": name, "quantile": q,
            "current_ms": round(cur_ms, 3), "baseline_ms": round(base_ms, 3),
            "delta_ms": round(cur_ms - base_ms, 3),
            "ratio": round(cur_ms / base_ms, 3) if base_ms > 0 else None,
        }
        over_band = cur_ms > base_ms * (1.0 + band_pct / 100.0)
        over_floor = (cur_ms - base_ms) > abs_floor_ms
        if over_band and over_floor:
            regs.append(entry)
        elif base_ms > cur_ms * (1.0 + band_pct / 100.0) \
                and (base_ms - cur_ms) > abs_floor_ms:
            imps.append(entry)
    return regs, imps, None


def compare(current: dict, baseline: dict,
            quantiles: Tuple[float, ...] = (0.5, 0.99),
            band_pct: float = 25.0, abs_floor_ms: float = 5.0,
            min_count: int = 10) -> Dict[str, Any]:
    """Quantile regression report between two snapshots/bench lines.
    Stages present on only one side are reported (``uncompared``), not
    silently dropped — a stage that *disappeared* is information."""
    cur_e2e, cur_stages = digests_of(current)
    base_e2e, base_stages = digests_of(baseline)
    regressions: List[dict] = []
    improvements: List[dict] = []
    skipped: List[dict] = []
    compared: List[str] = []
    # identical-MEASUREMENT rule, the sibling of identical-estimator:
    # when both sides declare what their e2e digest measured
    # (http_e2e vs engine_single_doc), a mismatch is refused — an
    # engine-direct smoke p50 gated against an HTTP e2e p50 yields a
    # false verdict in either direction
    ck = current.get("latency_kind")
    bk = baseline.get("latency_kind")
    kind_mismatch = bool(ck and bk and ck != bk)
    if kind_mismatch:
        skipped.append({
            "series": "e2e",
            "reason": f"latency_kind mismatch (current={ck!r}, "
                      f"baseline={bk!r}): these digests measure "
                      f"different things"})
        cur_e2e = base_e2e = None
    if cur_e2e is not None and base_e2e is not None:
        r, i, s = _compare_series("e2e", cur_e2e, base_e2e, quantiles,
                                  band_pct, abs_floor_ms, min_count)
        regressions += r
        improvements += i
        if s:
            skipped.append(s)
        else:
            compared.append("e2e")
    for name in sorted(set(cur_stages) & set(base_stages)):
        r, i, s = _compare_series(name, cur_stages[name],
                                  base_stages[name], quantiles,
                                  band_pct, abs_floor_ms, min_count)
        regressions += r
        improvements += i
        if s:
            skipped.append(s)
        else:
            compared.append(name)
    uncompared = sorted(set(cur_stages) ^ set(base_stages))
    if (cur_e2e is None or base_e2e is None) and not kind_mismatch:
        uncompared.insert(0, "e2e")
    if not compared:
        skipped.append({"series": "*",
                        "reason": "no comparable series between current "
                                  "and baseline"})
    regressions.sort(key=lambda r: -r["delta_ms"])
    return {
        "ok": not regressions and bool(compared),
        "regressed_stages": sorted({r["series"] for r in regressions}),
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


# ---------------------------------------------------------------------
# Device-free self-check (runbook_ci --check_slo)
# ---------------------------------------------------------------------


def _inflate_digest(serialized: dict, factor: float) -> dict:
    """Scale every value in a serialized digest by ~``factor`` exactly
    in sketch space: multiplying values by ``gamma**k`` shifts every
    bucket index by ``k`` (index = ceil(log_gamma v)) — no sampling, no
    estimator mismatch."""
    d = QuantileDigest.from_dict(serialized)
    k = max(int(math.ceil(math.log(factor) / d._log_gamma)), 1)
    scale = d._gamma ** k
    out = d.to_dict()
    out["bins"] = {str(int(i) + k): c for i, c in out["bins"].items()}
    out["sum"] = d.sum * scale
    out["min"] = d.min * scale if math.isfinite(d.min) else None
    out["max"] = d.max * scale if math.isfinite(d.max) else None
    return out


def self_check(fixture: Optional[Path] = None,
               inflate_stage: str = "slots.device_steps",
               factor: float = 2.0) -> Dict[str, Any]:
    """The estimator's own regression test, no device or server needed:
    the committed fixture diffed against itself must pass, and the same
    fixture with ``inflate_stage`` inflated by ``factor`` must FAIL
    naming exactly that stage. A gate that can't detect a planted 2x
    regression is not a gate — this is what ``runbook_ci --check_slo``
    pins in CI."""
    fixture = Path(fixture) if fixture else DEFAULT_FIXTURE
    snap = json.loads(fixture.read_text())
    identical = compare(snap, snap)
    inflated = json.loads(json.dumps(snap))  # deep copy
    stages = inflated["slo"]["digests"]["stages"]
    if inflate_stage not in stages:
        return {"ok": False,
                "error": f"fixture has no stage {inflate_stage!r} "
                         f"(has: {sorted(stages)})"}
    stages[inflate_stage] = _inflate_digest(stages[inflate_stage], factor)
    inflated["slo"]["digests"]["e2e"] = _inflate_digest(
        inflated["slo"]["digests"]["e2e"], factor)
    planted = compare(inflated, snap)
    detected = inflate_stage in planted["regressed_stages"]
    ok = identical["ok"] and not planted["ok"] and detected
    return {
        "ok": ok,
        "fixture": str(fixture),
        "identical_ok": identical["ok"],
        "planted_detected": detected,
        "planted_regressed_stages": planted["regressed_stages"],
        "identical_skipped": identical["skipped"],
    }


# ---------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------


def _load_current(args) -> dict:
    if args.current:
        return _parse_any(Path(args.current))
    if not args.url:
        raise SystemExit("diff needs --url (live server) or --current "
                         "(snapshot file)")
    if getattr(args, "fleet", False):
        from code_intelligence_tpu.utils import fleetwatch

        return fleetwatch.take_fleet_snapshot(args.url,
                                              timeout=args.timeout)
    if getattr(args, "delivery", False):
        return take_delivery_snapshot(args.url, timeout=args.timeout)
    if getattr(args, "memory", False):
        return take_memory_snapshot(args.url, timeout=args.timeout)
    return take_snapshot(args.url, timeout=args.timeout)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="perfwatch",
        description="serve-path SLO snapshot + quantile regression gate "
                    "(RUNBOOK §22)")
    sub = p.add_subparsers(dest="cmd", required=True)

    ps = sub.add_parser("snapshot", help="pull /debug/slo + /metrics + "
                                         "/debug/flight from a live server")
    ps.add_argument("--url", required=True, help="server base URL")
    ps.add_argument("--out", default=None,
                    help="write here (default: stdout)")
    ps.add_argument("--fleet", action="store_true",
                    help="the URL is a fleet ROUTER: snapshot its "
                         "/fleet/slo observatory rollup (merged + "
                         "per-member sketches, utils/fleetwatch.py) "
                         "instead of a single server's /debug/slo")
    ps.add_argument("--delivery", action="store_true",
                    help="snapshot the delivery loop's per-phase "
                         "duration digests (/debug/journal "
                         "phase_seconds, RUNBOOK §29) instead of the "
                         "serve-path SLO")
    ps.add_argument("--memory", action="store_true",
                    help="snapshot the device-memory ledger "
                         "(/debug/memory, RUNBOOK §31): per-owner live-"
                         "buffer byte rows instead of the serve-path "
                         "SLO — the footprint-regression baseline")
    ps.add_argument("--timeout", type=float, default=10.0)

    pd = sub.add_parser("diff", help="regression gate: current vs baseline")
    pd.add_argument("--url", default=None, help="live server to snapshot "
                                                "as the current side")
    pd.add_argument("--current", default=None,
                    help="snapshot file for the current side (instead of "
                         "--url)")
    pd.add_argument("--baseline", required=True,
                    help="committed perfwatch snapshot or BENCH_*.json "
                         "(line) to gate against")
    pd.add_argument("--band_pct", type=float, default=25.0,
                    help="allowed quantile growth in percent (default 25)")
    pd.add_argument("--abs_floor_ms", type=float, default=5.0,
                    help="ignore regressions smaller than this many ms "
                         "(scheduler noise at microsecond scale)")
    pd.add_argument("--quantiles", default="0.5,0.99",
                    help="comma-separated quantiles to gate on")
    pd.add_argument("--min_count", type=int, default=None,
                    help="series with fewer samples are skipped, loudly "
                         "(default 10; 1 in --delivery mode, where one "
                         "completed cycle is one sample per phase)")
    pd.add_argument("--allow_stale", action="store_true",
                    help="permit a non-fresh baseline (PR 4 provenance "
                         "stamps are refused by default)")
    pd.add_argument("--fleet", action="store_true",
                    help="fleet mode: diff a router's /fleet/slo rollup "
                         "AND every member's own series against a "
                         "fleetwatch baseline — exit 1 names the "
                         "regressed STAGE and MEMBER (a straggler the "
                         "merged average would launder)")
    pd.add_argument("--delivery", action="store_true",
                    help="delivery mode: diff per-PHASE delivery-loop "
                         "duration digests (/debug/journal "
                         "phase_seconds) against a delivery baseline — "
                         "exit 1 names the regressed phase (a canary "
                         "soak that quietly doubled is a regression "
                         "too)")
    pd.add_argument("--memory", action="store_true",
                    help="memory mode: diff per-OWNER device-memory "
                         "byte rows (/debug/memory, RUNBOOK §31) "
                         "against a memory baseline — exit 1 names the "
                         "owning component whose footprint grew (int8 "
                         "re-inflating, a canary never released after "
                         "promote, unattributed = a leak)")
    pd.add_argument("--abs_floor_bytes", type=int, default=1 << 20,
                    help="--memory only: ignore footprint growth "
                         "smaller than this many bytes (default 1MiB — "
                         "allocator jitter is not a regression)")
    pd.add_argument("--timeout", type=float, default=10.0)

    pc = sub.add_parser("selfcheck",
                        help="device-free estimator check against the "
                             "committed fixture (runbook_ci --check_slo)")
    pc.add_argument("--fixture", default=None)

    args = p.parse_args(argv)

    if args.cmd == "snapshot":
        try:
            if args.fleet:
                from code_intelligence_tpu.utils import fleetwatch

                snap = fleetwatch.take_fleet_snapshot(
                    args.url, timeout=args.timeout)
            elif args.delivery:
                snap = take_delivery_snapshot(args.url,
                                              timeout=args.timeout)
            elif args.memory:
                snap = take_memory_snapshot(args.url,
                                            timeout=args.timeout)
            else:
                snap = take_snapshot(args.url, timeout=args.timeout)
        except RuntimeError as e:
            # unreachable / SLO-disabled server is UNUSABLE INPUT, not
            # a regression: exit 2 like the diff branch maps the same
            # failure, one JSON object on stdout (the bench convention)
            print(json.dumps({"ok": False, "error": str(e)}))
            return 2
        text = json.dumps(snap, indent=1)
        if args.out:
            Path(args.out).write_text(text)
            if args.delivery:
                print(json.dumps({"ok": True, "out": args.out,
                                  "phases": sorted(snap["digests"])}))
            elif args.memory:
                print(json.dumps({"ok": True, "out": args.out,
                                  "total_bytes": snap["total_bytes"],
                                  "owners": sorted(snap["owners"])}))
            else:
                body = snap["fleet_slo"]["fleet"] if args.fleet \
                    else snap["slo"]
                print(json.dumps({"ok": True, "out": args.out,
                                  "requests_total":
                                  body.get("requests_total")}))
        else:
            print(text)
        return 0

    if args.cmd == "selfcheck":
        report = self_check(Path(args.fixture) if args.fixture else None)
        print(json.dumps(report))
        return 0 if report["ok"] else 1

    # diff
    try:
        baseline = _parse_any(Path(args.baseline))
    except (OSError, ValueError) as e:
        print(json.dumps({"ok": False, "error": f"baseline: {e}"}))
        return 2
    if args.delivery and "provenance" not in baseline:
        # a raw /debug/journal body carries its stamp inside
        # phase_seconds — hoist it so the shared provenance gate sees it
        prov = _delivery_body(baseline).get("provenance")
        if prov is not None:
            baseline["provenance"] = prov
    reason = check_provenance(baseline, args.allow_stale)
    if reason is not None:
        print(json.dumps({"ok": False, "refused": True, "error": reason}))
        return 2
    try:
        current = _load_current(args)
    except (OSError, ValueError, RuntimeError) as e:
        print(json.dumps({"ok": False, "error": f"current: {e}"}))
        return 2
    qs = tuple(float(q) for q in args.quantiles.split(","))
    min_count = args.min_count if args.min_count is not None \
        else (1 if args.delivery else 10)
    if args.fleet:
        from code_intelligence_tpu.utils import fleetwatch

        report = fleetwatch.compare_fleet(
            current, baseline, quantiles=qs, band_pct=args.band_pct,
            abs_floor_ms=args.abs_floor_ms, min_count=min_count)
    elif args.delivery:
        report = compare_delivery(current, baseline, quantiles=qs,
                                  band_pct=args.band_pct,
                                  abs_floor_ms=args.abs_floor_ms,
                                  min_count=min_count)
    elif args.memory:
        report = compare_memory(current, baseline,
                                band_pct=args.band_pct,
                                abs_floor_bytes=args.abs_floor_bytes)
    else:
        report = compare(current, baseline, quantiles=qs,
                         band_pct=args.band_pct,
                         abs_floor_ms=args.abs_floor_ms,
                         min_count=min_count)
    print(json.dumps(report))
    if report["ok"]:
        return 0
    # the one-line human verdict, on stderr (stdout stays one JSON
    # object, the bench convention)
    if not report["compared"]:
        # nothing was comparable (warm-up server, min_count skips,
        # digest-less baseline): that is UNUSABLE INPUT, not a latency
        # regression — exit 2, like a refused provenance stamp
        print("perfwatch: nothing comparable between current and "
              "baseline (see 'skipped'/'uncompared') — not gating",
              file=sys.stderr)
        return 2
    if args.fleet:
        from code_intelligence_tpu.utils import fleetwatch

        # the fleet verdict names the regressed member AND stage
        print(fleetwatch.format_verdict(report), file=sys.stderr)
        return 1
    stages = ", ".join(report["regressed_stages"])
    if args.memory:
        # the memory verdict names the owning component and the growth
        worst = report["regressions"][0]
        print(f"perfwatch: DEVICE-MEMORY REGRESSION in owner(s) {stages} "
              f"(worst: {worst['series']} "
              f"+{_fmt_b(worst['delta_bytes'])}; band "
              f"{args.band_pct:g}%, floor "
              f"{_fmt_b(args.abs_floor_bytes)})", file=sys.stderr)
        return 1
    what = "DELIVERY-PHASE REGRESSION in phase(s)" if args.delivery \
        else "REGRESSION in"
    print(f"perfwatch: {what} {stages} "
          f"(band {args.band_pct:g}%, floor {args.abs_floor_ms:g}ms)",
          file=sys.stderr)
    return 1


if __name__ == "__main__":
    sys.exit(main())
