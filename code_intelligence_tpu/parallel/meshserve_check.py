"""Device-free mesh-serve acceptance gate (``runbook_ci --check_meshserve``).

The mesh-sharded serve step's claims (RUNBOOK §26) are provable WITHOUT
a multi-chip TPU: a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` runs the REAL
sharded slot/ragged step over a real ``("data","model")`` mesh on 8
virtual CPU devices — the same compile path the MULTICHIP dryruns
proved for training. The gate asserts, on a tiny randomly-initialized
engine over the committed ragged fixture lengths:

* allclose parity between the mesh-sharded step and the single-device
  path for BOTH schedulers (a sharding that changes answers is not a
  sharding),
* the sharded ragged steady state clean under
  ``no_implicit_transfers()`` + ``recompile_guard(budget=0)`` +
  ``memory_guard(budget_bytes=0)`` on its own step name
  (``slots.step_ragged_mesh``) — the staging block stays the ONE
  explicit h2d per step, one compiled shape, zero retained buffers,
* the device-memory ledger (RUNBOOK §31) sums exactly over the forced
  8-device mesh and attributes owner rows on >= 2 distinct devices
  (per-shard physical bytes, not logical array bytes),
* buffer donation recorded on the sharded step's lowering (the state
  arenas never round-trip the host),
* per-device AOT ``cost_analysis`` flops of the sharded step within
  ``max_flops_balance`` (1.2×) of total/``mesh_size`` — the ×N
  capacity claim, measured on the SPMD-partitioned program,
* ``mesh=None`` leaves today's single-chip path bitwise unchanged.

This gate is the only holder of these pins: counts and parity on a forced
CPU mesh, never a rate (a four-chip cell is ROADMAP S9).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path
from typing import Optional

#: virtual CPU devices the child forces (the training dryrun's count)
FORCED_DEVICES = 8
#: the default serve mesh geometry under those devices
DEFAULT_SPEC = "data=4,model=2"
#: repo root (the package's parent) — the child needs it on PYTHONPATH
_REPO_ROOT = str(Path(__file__).resolve().parents[2])


def _collective_timeout_flags() -> str:
    """The CPU-collective-timeout XLA flags (an 8-way in-process
    collective rendezvous can starve past XLA's 40s abort on a loaded
    host). The constant lives in the repo-root driver; a packaged
    install just goes without."""
    try:
        sys.path.insert(0, _REPO_ROOT)
        from __graft_entry__ import COLLECTIVE_TIMEOUT_FLAGS

        return COLLECTIVE_TIMEOUT_FLAGS
    except ImportError:
        return ""
    finally:
        if sys.path and sys.path[0] == _REPO_ROOT:
            sys.path.pop(0)


def _child_check(spec: str, max_flops_balance: float = 1.2) -> dict:
    """The in-process body (expects >= 2 visible devices — the parent
    forces them). Returns the verdict dict; ``ok`` aggregates the pins
    in the module docstring."""
    import jax
    import numpy as np

    from code_intelligence_tpu.analysis import runtime as audit
    from code_intelligence_tpu.inference.ragged_check import (
        FIXTURE, _tiny_engine)
    from code_intelligence_tpu.inference.slots import (
        RaggedSlotScheduler, SlotScheduler)
    from code_intelligence_tpu.parallel import serve_shard

    n_devices = len(jax.devices())
    mesh = serve_shard.build_serve_mesh(spec)
    msize = serve_shard.mesh_size(mesh)
    engine = _tiny_engine()
    fix = json.loads(FIXTURE.read_text())
    rng = np.random.RandomState(int(fix.get("seed", 0)))
    hi = engine.config.vocab_size - 1
    ids = [rng.randint(5, hi, int(l)).astype(np.int32)
           for l in fix["lengths"]]

    # single-device reference (and the bitwise-off baseline)
    base_dense = engine.embed_ids_batch(ids, scheduler="slots")
    base_ragged = engine.embed_ids_batch(ids, scheduler="ragged")

    ss = SlotScheduler(engine, mesh=mesh)
    rs = RaggedSlotScheduler(engine, mesh=mesh)
    mesh_dense = ss.embed_ids(ids)
    mesh_ragged = rs.embed_ids(ids)
    parity_dense = float(np.max(np.abs(mesh_dense - base_dense)))
    parity_ragged = float(np.max(np.abs(mesh_ragged - base_ragged)))
    parity_ok = bool(
        np.allclose(mesh_dense, base_dense, atol=1e-5, rtol=1e-5)
        and np.allclose(mesh_ragged, base_ragged, atol=1e-5, rtol=1e-5))

    # steady state: zero new compiles on the sharded step's own name,
    # zero implicit transfers, zero retained device buffers — the page
    # table and valid lengths still ride the packed staging block, now
    # as ONE sharded device_put (memory_guard: RUNBOOK §31)
    with audit.recompile_guard(fn="slots.step_ragged_mesh", budget=0), \
            audit.no_implicit_transfers(), \
            audit.memory_guard(budget_bytes=0):
        rs.embed_ids(ids)

    # per-device ledger attribution on the forced 8-CPU-device mesh:
    # the sharded arenas/pool/params must land attributed rows on >= 2
    # distinct devices (a ledger that collapses a mesh to one device
    # can't answer direction-4 capacity questions)
    from code_intelligence_tpu.utils.memtrack import DeviceMemoryLedger

    ledger = DeviceMemoryLedger()
    rs.register_memory_owners(ledger, prefix="slots_ragged")
    ss.register_memory_owners(ledger, prefix="slots")
    mem = ledger.snapshot()
    devices_attributed = sum(
        1 for dev in mem["devices"].values()
        if any(o != "unattributed" and nbytes > 0
               for o, nbytes in dev["owners"].items()))
    ledger_ok = bool(mem["sums_exactly"] and devices_attributed >= 2)

    # donation recorded on the sharded lowering (jax marks donated
    # params as aliased/buffer-donor in the exported module text)
    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype)

    lowered = rs._step_raw.lower(
        jax.tree.map(sds, engine._enc_params),
        jax.ShapeDtypeStruct(
            (rs.batch_size, rs.chunk_len + rs._STAGING_EXTRA),
            np.int32),
        jax.tree.map(sds, rs._h_leaves), sds(rs._pool))
    txt = lowered.as_text()
    donated = bool("buffer_donor" in txt or "aliasing" in txt)

    # per-device flops vs total/N: the sharded scheduler's memoized AOT
    # cost_analysis reads the SPMD-partitioned (per-device) module; the
    # unsharded scheduler's reads the whole program
    per_dev = rs.step_cost_analysis()["flops"]
    total = engine.slot_scheduler(ragged=True).step_cost_analysis()["flops"]
    flops_balance = per_dev * msize / max(total, 1e-9)
    flops_ok = bool(0.0 < flops_balance <= max_flops_balance)

    # mesh off => bitwise-identical to the pre-mesh baseline
    again = engine.embed_ids_batch(ids, scheduler="ragged")
    mesh_off_bitwise = bool(np.array_equal(again, base_ragged))

    return {
        "n_devices": n_devices,
        "mesh": {str(k): int(v) for k, v in dict(mesh.shape).items()},
        "mesh_size": msize,
        "n_docs": len(ids),
        "parity_ok": parity_ok,
        "parity_dense_max_abs_diff": parity_dense,
        "parity_ragged_max_abs_diff": parity_ragged,
        "audited": True,
        "donated": donated,
        "mesh_compiled_step_shapes": rs.compiled_step_shapes(),
        "step_flops_per_device": per_dev,
        "step_flops_total": total,
        "flops_balance": round(flops_balance, 4),
        "max_flops_balance": max_flops_balance,
        "flops_balance_ok": flops_ok,
        "mesh_off_bitwise_equal": mesh_off_bitwise,
        "ledger_sums_exactly": bool(mem["sums_exactly"]),
        "ledger_devices_attributed": int(devices_attributed),
        "ledger_ok": ledger_ok,
        "ok": bool(parity_ok and donated and flops_ok
                   and mesh_off_bitwise and ledger_ok
                   and rs.compiled_step_shapes() in (1, -1)),
    }


def run_meshserve_check(spec: str = DEFAULT_SPEC,
                        devices: int = FORCED_DEVICES,
                        timeout_s: float = 600.0,
                        env: Optional[dict] = None) -> dict:
    """Spawn the forced-device-count child and return its verdict.

    A subprocess on purpose: the parent's jax (if imported) is already
    pinned to its device set — ``--xla_force_host_platform_device_count``
    only takes effect at backend init.
    """
    child_env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}"
                     + _collective_timeout_flags(),
        "PYTHONPATH": _REPO_ROOT + os.pathsep
                      + os.environ.get("PYTHONPATH", ""),
    }
    child_env.update(env or {})
    try:
        proc = subprocess.run(
            [sys.executable, "-m",
             "code_intelligence_tpu.parallel.meshserve_check",
             "--child", "--mesh", spec],
            capture_output=True, text=True, timeout=timeout_s,
            env=child_env, cwd=_REPO_ROOT)
    except subprocess.TimeoutExpired:
        return {"ok": False,
                "error": f"meshserve child timed out after {timeout_s}s"}
    lines = [l for l in (proc.stdout or "").strip().splitlines() if l]
    if proc.returncode != 0 or not lines:
        return {"ok": False,
                "error": ("meshserve child rc="
                          f"{proc.returncode}: "
                          + (proc.stderr or "")[-1500:])}
    try:
        return json.loads(lines[-1])
    except json.JSONDecodeError:
        return {"ok": False,
                "error": f"meshserve child emitted no JSON: {lines[-1][:300]}"}


def main(argv=None) -> int:
    import argparse

    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--child", action="store_true",
                   help="run the in-process check (expects the forced "
                        "device count already in XLA_FLAGS)")
    p.add_argument("--mesh", default=DEFAULT_SPEC,
                   help="serve mesh spec for the check")
    p.add_argument("--devices", type=int, default=FORCED_DEVICES,
                   help="virtual CPU devices to force (parent mode)")
    args = p.parse_args(argv)
    if args.child:
        report = _child_check(args.mesh)
    else:
        report = run_meshserve_check(args.mesh, devices=args.devices)
    print(json.dumps(report))
    return 0 if report.get("ok") else 1


if __name__ == "__main__":
    sys.exit(main())
