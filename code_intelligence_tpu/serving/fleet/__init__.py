"""Serve fleet: N embedding-server replicas behind one router.

The single-replica serve path (slots -> ragged paging -> content cache ->
SLO observatory -> canary promotion) is deeply optimized per chip; this
package is the horizontal axis — the replica-fleet layer production TPU
serving stacks get their throughput from (PAPERS.md, the Gemma-on-TPU
serving comparison; ROADMAP direction #1b):

* :mod:`members` — readiness-driven membership: a :class:`MemberTable`
  probes each replica's ``/healthz``/``/readyz``, ejects dead members,
  rotates draining ones out, and readmits recovered ones.
* :mod:`router` — the :class:`FleetRouter` HTTP front: fleet-level
  token-bucket admission (shed with 429 + ``Retry-After`` *before* any
  proxy hop), deadline-aware replica selection, cache-affinity
  rendezvous hashing with power-of-two-choices load blending, per-member
  circuit breakers, one optional hedged retry, and fleet-wide canary
  verification (the same md5 split rule as serving/rollout.py).
* :mod:`supervisor` — spawns/monitors N local replica processes for
  tests, chaos drills and the ``--check_*`` fleet gates.
* :mod:`fleet_check` — the device-free ``runbook_ci --check_fleet``
  gate: a live 2-replica fake fleet proving deadline propagation,
  shed-before-proxy, and canary-split consistency.
* :mod:`observatory` — the fleet-as-one-system signal plane (RUNBOOK
  §25): cross-process trace stitching (``/fleet/traces``), the merged
  member SLO rollup (``/fleet/slo``, exact digest merge), and
  leave-one-out ``replica_outlier`` straggler sentinels — the inputs
  the SLO-driven autoscaler (ROADMAP #4) consumes.
* :mod:`fleetobs_check` — the ``runbook_ci --check_fleetobs`` gate:
  seeded FaultInjector latency on ONE member must trip the outlier
  sentinel and make ``perfwatch --fleet`` exit 1 naming member+stage.

Everything here is jax-free host code: the router never loads a model,
so it boots in milliseconds and the whole subsystem is CPU-provable in
tier-1 and chaos-testable with the seeded ``FaultInjector``.
"""

from code_intelligence_tpu.serving.fleet.members import (  # noqa: F401
    Member, MemberTable)
from code_intelligence_tpu.serving.fleet.observatory import (  # noqa: F401
    FleetObservatory, ReplicaOutlierSentinel, stitch_traces)
from code_intelligence_tpu.serving.fleet.router import (  # noqa: F401
    FleetRouter, TokenBucket, make_router)
from code_intelligence_tpu.serving.fleet.supervisor import (  # noqa: F401
    FleetSupervisor)
