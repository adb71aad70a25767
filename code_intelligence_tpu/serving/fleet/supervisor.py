"""Local fleet supervisor: spawn and monitor N replica processes.

Production runs replicas under k8s (the reference's Deployment with a
readiness probe); tests, chaos drills and the ``--check_*`` fleet gates
need the same topology on one host with real process boundaries — a
SIGKILLed thread proves nothing, a SIGKILLed *process* proves the
router's ejection path. The supervisor:

* spawns N replicas as subprocesses — either **fake** (``--serve_fake``:
  the real ``serving.server`` HTTP stack over the deterministic
  jax-free ``SmokeEngine`` from registry/promotion.py, with a real
  ``RolloutManager`` canary split, booting in well under a second) or
  **real** (``python -m code_intelligence_tpu.serving.server
  --model_dir ...``);
* waits for every replica's ``/healthz``/``/readyz``;
* exposes the chaos verbs the drills need: :meth:`kill` (SIGKILL),
  :meth:`drain` (SIGTERM — the replica's graceful-drain path),
  :meth:`restart`;
* optionally monitors and restarts dead replicas (``monitor=True``) —
  the local stand-in for the k8s restart policy.

The fake replica carries the full serve-path admission/drain/rollout
machinery, so fleet-level properties (shed-before-proxy, canary-split
consistency, zero-failure drain) are proven against the REAL server
code, not a mock.
"""

from __future__ import annotations

import logging
import os
import random
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.request
from pathlib import Path
from typing import Dict, List, Optional

from code_intelligence_tpu.utils.resilience import full_jitter_backoff

log = logging.getLogger(__name__)

#: repo root (the package's parent) — children need it on PYTHONPATH
_REPO_ROOT = str(Path(__file__).resolve().parents[3])


def free_port() -> int:
    """An OS-assigned free TCP port (bind-close-reuse; the tiny race is
    acceptable for local supervision)."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Replica:
    """One supervised replica process."""

    def __init__(self, index: int, port: int, cmd: List[str]):
        self.index = index
        self.port = port
        self.cmd = cmd
        self.proc: Optional[subprocess.Popen] = None
        self.restarts = 0
        #: scaled in (or being drained for removal): the monitor must
        #: never resurrect a replica the autoscaler retired
        self.retired = False
        # crash-loop bookkeeping for the monitor's jittered backoff
        self.crash_streak = 0
        self.restart_at: Optional[float] = None
        self.spawned_at: Optional[float] = None

    @property
    def base_url(self) -> str:
        return f"http://127.0.0.1:{self.port}"

    def alive(self) -> bool:
        return self.proc is not None and self.proc.poll() is None


class FleetSupervisor:
    """Spawn/monitor N local replicas. ``engine="fake"`` needs no model
    artifact and no jax; ``engine="real"`` needs ``model_dir``."""

    def __init__(
        self,
        n: int = 2,
        engine: str = "fake",
        model_dir: Optional[str] = None,
        candidate_dir: Optional[str] = None,
        canary_pct: float = 0.0,
        model_version: str = "incumbent",
        candidate_version: str = "candidate",
        max_pending: int = 64,
        engine_delay_ms: float = 0.0,
        mesh: Optional[str] = None,
        extra_args: Optional[List[str]] = None,
        monitor: bool = False,
        monitor_interval_s: float = 0.5,
        env: Optional[Dict[str, str]] = None,
        ports: Optional[List[int]] = None,
        fault_member: Optional[int] = None,
        fault_latency_ms: float = 0.0,
        fault_rate: float = 1.0,
        fault_seed: int = 0,
        restart_backoff_base_s: float = 0.5,
        restart_backoff_cap_s: float = 30.0,
        healthy_after_s: float = 5.0,
        registry=None,
        rng: Optional[random.Random] = None,
    ):
        if n < 1:
            raise ValueError("n must be >= 1")
        if ports is not None and len(ports) != n:
            raise ValueError(f"ports must name exactly n={n} ports, "
                             f"got {len(ports)}")
        if fault_member is not None and not (0 <= fault_member < n):
            raise ValueError(f"fault_member must index a replica "
                             f"(0..{n - 1}), got {fault_member}")
        if engine not in ("fake", "real"):
            raise ValueError(f"unknown engine mode {engine!r}")
        if engine == "real" and not model_dir:
            raise ValueError("engine='real' requires model_dir")
        if mesh and engine != "real":
            # the fake replica is jax-free by design — silently dropping
            # the knob would "prove" mesh scaling that never ran
            raise ValueError("mesh requires engine='real' (the fake "
                             "replica has no device step to shard)")
        if engine == "real" and canary_pct > 0 and not candidate_dir:
            # fail loud at construction: silently spawning 100%-incumbent
            # replicas under a router expecting a split would fire
            # fleet_canary_mismatch_total on every candidate-bucket doc
            raise ValueError("engine='real' with canary_pct > 0 requires "
                             "candidate_dir (the canary model artifact)")
        self.engine = engine
        self.model_dir = model_dir
        self.candidate_dir = candidate_dir
        self.canary_pct = float(canary_pct)
        self.model_version = model_version
        self.candidate_version = candidate_version
        self.max_pending = int(max_pending)
        self.engine_delay_ms = float(engine_delay_ms)
        #: serve-mesh spec for real-engine replicas (serving.server
        #: --mesh, RUNBOOK §26): every replica shards its step over its
        #: own visible devices — sharding WITHIN a replica composes
        #: with the router's scaling ACROSS replicas
        self.mesh = mesh
        self.extra_args = list(extra_args or [])
        self.monitor_interval_s = float(monitor_interval_s)
        self._monitor = bool(monitor)
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._env = dict(os.environ)
        self._env["PYTHONPATH"] = _REPO_ROOT + os.pathsep + \
            self._env.get("PYTHONPATH", "")
        self._env.update(env or {})
        #: per-replica seeded fault plan (utils/faults.py): injected
        #: engine latency on ONE member — the straggler the fleet
        #: observatory's replica_outlier sentinel exists to catch
        #: (fake-engine mode only; a real engine's latency is real)
        self.fault_member = fault_member
        self.fault_latency_ms = float(fault_latency_ms)
        self.fault_rate = float(fault_rate)
        self.fault_seed = int(fault_seed)
        # crash-loop damping: a replica that keeps dying is respawned
        # on a full-jitter exponential schedule, not in a tight storm;
        # a replica that stays up healthy_after_s resets its streak
        self.restart_backoff_base_s = float(restart_backoff_base_s)
        self.restart_backoff_cap_s = float(restart_backoff_cap_s)
        self.healthy_after_s = float(healthy_after_s)
        self._rng = rng or random.Random()
        self.registry = registry
        if registry is not None:
            registry.gauge("fleet_restart_backoff_s",
                           "current monitor restart-backoff delay per "
                           "replica (0 = not crash-looping)")
        self.replicas: List[Replica] = []
        for i in range(n):
            # explicit ports keep member ids (host:port) stable across
            # fleets — what lets a perfwatch --fleet baseline taken from
            # one fleet gate a later fleet's per-member series
            port = ports[i] if ports is not None else free_port()
            self.replicas.append(Replica(i, port, self._cmd_for(port, i)))

    def _cmd_for(self, port: int, index: int = -1) -> List[str]:
        if self.engine == "fake":
            cmd = [sys.executable, "-m",
                   "code_intelligence_tpu.serving.fleet.supervisor",
                   "--serve_fake", "--port", str(port),
                   "--max_pending", str(self.max_pending),
                   "--model_version", self.model_version,
                   "--engine_delay_ms", str(self.engine_delay_ms)]
            if self.canary_pct > 0:
                cmd += ["--canary_pct", str(self.canary_pct),
                        "--candidate_version", self.candidate_version]
            if self.fault_member is not None \
                    and index == self.fault_member \
                    and self.fault_latency_ms > 0:
                cmd += ["--fault_latency_ms", str(self.fault_latency_ms),
                        "--fault_rate", str(self.fault_rate),
                        "--fault_seed", str(self.fault_seed)]
        else:
            cmd = [sys.executable, "-m",
                   "code_intelligence_tpu.serving.server",
                   "--model_dir", str(self.model_dir),
                   "--host", "127.0.0.1", "--port", str(port),
                   "--max_pending", str(self.max_pending),
                   "--model_version", self.model_version]
            if self.mesh:
                cmd += ["--mesh", self.mesh]
            if self.canary_pct > 0:
                # the fleet-consistency contract: every replica carries
                # the SAME split the router verifies against
                cmd += ["--candidate_dir", str(self.candidate_dir),
                        "--candidate_version", self.candidate_version,
                        "--canary_pct", str(self.canary_pct)]
        return cmd + self.extra_args

    # -- lifecycle -----------------------------------------------------

    def start(self) -> "FleetSupervisor":
        for r in self.replicas:
            self._spawn(r)
        if self._monitor:
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._monitor_loop, name="fleet-supervisor",
                daemon=True)
            self._thread.start()
        return self

    def _spawn(self, r: Replica) -> None:
        log.info("spawning replica %d on port %d", r.index, r.port)
        r.proc = subprocess.Popen(
            r.cmd, env=self._env, cwd=_REPO_ROOT,
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
        r.spawned_at = time.monotonic()

    def member_urls(self) -> List[str]:
        return [r.base_url for r in self.replicas if not r.retired]

    # -- dynamic membership (autoscaler verbs) -------------------------

    def add_replica(self, port: Optional[int] = None) -> Replica:
        """Spawn one more replica (autoscaler scale-out). Non-blocking:
        poll :meth:`replica_ready` (or ``wait_ready``) before admitting
        it to a routing table."""
        index = len(self.replicas)
        port = port or free_port()
        r = Replica(index, port, self._cmd_for(port, index))
        self.replicas.append(r)
        self._spawn(r)
        return r

    @staticmethod
    def _probe_readyz(r: "Replica", timeout_s: float) -> bool:
        """One ``/readyz`` probe of a child replica."""
        try:
            with urllib.request.urlopen(  # graft: noqa[outbound-missing-context] — supervisor readiness poll of its own child replica; no ambient request context exists
                    f"{r.base_url}/readyz", timeout=timeout_s) as resp:
                return resp.status == 200
        except Exception:
            return False

    def replica_ready(self, index: int, timeout_s: float = 1.0) -> bool:
        """One ``/readyz`` probe of a single replica — the autoscaler's
        admission check during a draining rotation."""
        r = self.replicas[index]
        if not r.alive():
            return False
        return self._probe_readyz(r, timeout_s)

    def retire_replica(self, index: int, drain: bool = True) -> None:
        """Mark a replica as scaled in (monitor will not respawn it) and
        start its graceful drain."""
        r = self.replicas[index]
        r.retired = True
        if drain:
            self.drain(index)

    def wait_ready(self, timeout_s: float = 30.0) -> bool:
        """Block until every replica answers ``/readyz`` 200 (False on
        timeout). Replica processes that died are NOT waited for."""
        end = time.monotonic() + timeout_s
        pending = {r.index: r for r in self.replicas if not r.retired}
        while pending and time.monotonic() < end:
            for idx in list(pending):
                r = pending[idx]
                if not r.alive():
                    del pending[idx]
                    continue
                if self._probe_readyz(r, timeout_s=1.0):
                    del pending[idx]
            if pending:
                time.sleep(0.05)
        return not pending and all(r.alive() for r in self.replicas
                                   if not r.retired)

    # -- chaos verbs ---------------------------------------------------

    def kill(self, index: int) -> None:
        """SIGKILL — the ungraceful death the ejection path exists for."""
        r = self.replicas[index]
        if r.proc is not None and r.proc.poll() is None:
            r.proc.kill()
            r.proc.wait(timeout=10)

    def drain(self, index: int) -> None:
        """SIGTERM — the replica's graceful-drain path (finish in-flight,
        ``/readyz`` flips to 503 ``draining``, then exit)."""
        r = self.replicas[index]
        if r.proc is not None and r.proc.poll() is None:
            r.proc.send_signal(signal.SIGTERM)

    def restart(self, index: int) -> None:
        r = self.replicas[index]
        if r.proc is not None and r.proc.poll() is None:
            r.proc.terminate()
            r.proc.wait(timeout=10)
        r.restarts += 1
        self._spawn(r)

    def stop_all(self) -> None:
        self._stop.set()
        t, self._thread = self._thread, None
        if t is not None:
            t.join(timeout=self.monitor_interval_s + 2)
        for r in self.replicas:
            if r.proc is not None and r.proc.poll() is None:
                r.proc.terminate()
        for r in self.replicas:
            if r.proc is not None:
                try:
                    r.proc.wait(timeout=5)
                except subprocess.TimeoutExpired:
                    r.proc.kill()
                    r.proc.wait(timeout=5)

    # -- monitoring ----------------------------------------------------

    def _monitor_loop(self) -> None:
        while not self._stop.wait(self.monitor_interval_s):
            self._monitor_tick(time.monotonic())

    def _set_backoff_gauge(self, r: Replica, delay: float) -> None:
        if self.registry is not None:
            try:
                self.registry.set("fleet_restart_backoff_s", delay,
                                  labels={"replica": str(r.index)})
            except Exception:
                pass

    def _monitor_tick(self, now: float) -> None:
        """One monitor pass (clock injected so the backoff schedule is
        testable without real processes). First death of a healthy
        replica restarts immediately; a crash-looping one waits a
        full-jitter exponential delay, capped, so N looping replicas
        never synchronize into a restart storm."""
        for r in self.replicas:
            if r.retired or r.proc is None:
                continue
            if r.proc.poll() is None:
                # alive long enough -> forgive the streak
                if (r.crash_streak and r.spawned_at is not None
                        and now - r.spawned_at >= self.healthy_after_s):
                    r.crash_streak = 0
                    self._set_backoff_gauge(r, 0.0)
                continue
            if r.restart_at is None:
                delay = 0.0 if r.crash_streak == 0 else full_jitter_backoff(
                    r.crash_streak, self.restart_backoff_base_s,
                    self.restart_backoff_cap_s, self._rng)
                r.restart_at = now + delay
                self._set_backoff_gauge(r, delay)
                log.warning("replica %d died (rc=%s) — restart in %.2fs",
                            r.index, r.proc.returncode, delay)
            if now < r.restart_at:
                continue
            r.restart_at = None
            r.crash_streak += 1
            r.restarts += 1
            try:
                self._spawn(r)
            except Exception:
                log.exception("respawn of replica %d failed", r.index)

    def __enter__(self) -> "FleetSupervisor":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop_all()


# ---------------------------------------------------------------------
# Fake replica child mode (--serve_fake)
# ---------------------------------------------------------------------


def _instrument_fake_engine(engine, injector=None):
    """Wrap a SmokeEngine's device stand-in in an ambient
    ``engine.group_embed`` span (the stage name the real groups path
    emits) so the replica's SLO observatory attributes engine time to a
    REAL stage — which is where a seeded :class:`FaultInjector` latency
    plan lands too, making an injected straggler attributable to a
    named stage in the fleet rollup, not just ``unattributed``."""
    from code_intelligence_tpu.utils import tracing

    inner = injector.wrap(engine.embed_issues) if injector is not None \
        else engine.embed_issues

    def traced_embed(issues, **kw):
        with tracing.span("engine.group_embed", n_docs=len(issues)):
            return inner(issues, **kw)

    engine.embed_issues = traced_embed
    return engine


def serve_fake(port: int, max_pending: int, model_version: str,
               canary_pct: float, candidate_version: str,
               engine_delay_ms: float, drain_timeout_s: float,
               fault_latency_ms: float = 0.0, fault_rate: float = 1.0,
               fault_seed: int = 0) -> None:
    """Child-process entry: the REAL serving stack (EmbeddingServer +
    RolloutManager + SIGTERM drain + SLO observatory) over the
    deterministic jax-free SmokeEngine — two independent replicas agree
    bit-for-bit on every document, which is exactly the property the
    fleet canary-consistency and affinity checks need. ``/debug/slo``
    is live (the fleet observatory scrapes it) and engine time lands in
    the ``engine.group_embed`` stage; ``fault_latency_ms > 0`` plants a
    seeded ``FaultInjector`` latency on that stage — the controlled
    straggler the ``--check_fleetobs`` gate detects."""
    from code_intelligence_tpu.registry.promotion import SmokeEngine
    from code_intelligence_tpu.serving.rollout import RolloutManager
    from code_intelligence_tpu.serving.server import make_server

    injector = None
    if fault_latency_ms > 0:
        from code_intelligence_tpu.utils.faults import FaultInjector

        injector = FaultInjector(seed=fault_seed,
                                 latency_s=fault_latency_ms / 1e3,
                                 latency_rate=fault_rate)
    delay_s = max(engine_delay_ms, 0.0) / 1e3
    engine = _instrument_fake_engine(SmokeEngine(delay_s=delay_s), injector)
    rollout = RolloutManager(engine, version=model_version, sentinels=[])
    if canary_pct > 0:
        rollout.start_canary(
            candidate_version,
            _instrument_fake_engine(SmokeEngine(delay_s=delay_s), injector),
            canary_pct)
    srv = make_server(engine, host="127.0.0.1", port=port,
                      scheduler="groups", max_pending=max_pending,
                      rollout=rollout, drain_timeout_s=drain_timeout_s)

    def _sigterm(signum, frame):
        def _go():
            srv.drain()
            srv.shutdown()
            srv.server_close()

        threading.Thread(target=_go, daemon=True).start()

    signal.signal(signal.SIGTERM, _sigterm)
    log.info("fake replica (version=%s canary=%s/%.1f%%) on port %d",
             model_version, candidate_version, canary_pct, port)
    srv.serve_forever()


def main(argv=None) -> None:
    import argparse

    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--serve_fake", action="store_true",
                   help="run ONE fake replica in this process (the "
                        "supervisor's child mode) instead of "
                        "supervising")
    p.add_argument("--port", type=int, default=0)
    p.add_argument("--n", type=int, default=2,
                   help="replica count (supervisor mode)")
    p.add_argument("--max_pending", type=int, default=64)
    p.add_argument("--model_version", default="incumbent")
    p.add_argument("--candidate_version", default="candidate")
    p.add_argument("--canary_pct", type=float, default=0.0)
    p.add_argument("--engine_delay_ms", type=float, default=0.0,
                   help="per-request fake-engine delay (makes load and "
                        "hedging observable in drills)")
    p.add_argument("--fault_latency_ms", type=float, default=0.0,
                   help="seeded FaultInjector latency planted on the "
                        "engine stage (child mode; the controlled "
                        "straggler for observatory drills, §25)")
    p.add_argument("--fault_rate", type=float, default=1.0,
                   help="probability a call pays --fault_latency_ms")
    p.add_argument("--fault_seed", type=int, default=0)
    p.add_argument("--drain_timeout_s", type=float, default=30.0)
    p.add_argument("--mesh", default=None,
                   help="serve-mesh spec forwarded to real-engine "
                        "replicas (serving.server --mesh, RUNBOOK §26); "
                        "rejected with fake engines")
    p.add_argument("--model_dir", default=None,
                   help="export_encoder dir: supervise REAL engine "
                        "replicas instead of fake ones")
    p.add_argument("--candidate_dir", default=None,
                   help="canary candidate export dir for real-engine "
                        "replicas (required when --canary_pct > 0 with "
                        "--model_dir)")
    p.add_argument("--monitor", action="store_true",
                   help="restart dead replicas (supervisor mode)")
    args = p.parse_args(argv)
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(message)s")
    if args.serve_fake:
        serve_fake(args.port, args.max_pending, args.model_version,
                   args.canary_pct, args.candidate_version,
                   args.engine_delay_ms, args.drain_timeout_s,
                   fault_latency_ms=args.fault_latency_ms,
                   fault_rate=args.fault_rate,
                   fault_seed=args.fault_seed)
        return
    sup = FleetSupervisor(
        n=args.n, canary_pct=args.canary_pct,
        engine="real" if args.model_dir else "fake",
        model_dir=args.model_dir, candidate_dir=args.candidate_dir,
        mesh=args.mesh,
        model_version=args.model_version,
        candidate_version=args.candidate_version,
        max_pending=args.max_pending,
        engine_delay_ms=args.engine_delay_ms, monitor=args.monitor)
    sup.start()
    ok = sup.wait_ready()
    log.info("fleet of %d replicas %s: %s", args.n,
             "ready" if ok else "NOT ready",
             " ".join(sup.member_urls()))
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        pass
    finally:
        sup.stop_all()


if __name__ == "__main__":
    main()
