"""Device-free runtime half of ``runbook_ci --check_jaxcheck``.

The static pass proves the *lint* finds planted dispatch hazards; this
module proves the *sentinel* does. It drives a small instrumented jit
step on the CPU backend through three pins:

1. **clean steady state** — a warmed loop under
   :class:`~code_intelligence_tpu.analysis.runtime.CompileWatch` passes
   with zero recompiles, zero unsanctioned host syncs, and the
   ``jit_recompiles_total`` / ``h2d_d2h_bytes`` gauges rendered on a
   real registry;
2. **planted recompile** — one shape-varying call inside the watched
   scope must raise :class:`CompileWatchViolation` NAMING the step fn;
3. **planted host sync** — one ``.item()`` inside the watched loop must
   raise, naming the fn and the materializer kind.

A sentinel that cannot catch its own planted violations is the same
kind of worst green the planted-fixture lint self-check exists for.
jax is imported lazily inside :func:`run_jaxcheck_gate`; importing this
module stays device-free.
"""

from __future__ import annotations

_STEP_NAME = "jaxgate.step"


def run_jaxcheck_gate() -> dict:
    import jax
    import jax.numpy as jnp

    from code_intelligence_tpu.analysis.runtime import (
        CompileWatch, CompileWatchViolation)
    from code_intelligence_tpu.utils import flight_recorder, metrics

    step = flight_recorder.instrument(
        jax.jit(lambda x: x * 2.0 + 1.0), name=_STEP_NAME)
    x = jnp.ones((8, 16))
    x_other = jnp.ones((8, 17))  # built OUTSIDE the guarded scopes
    step(x).block_until_ready()  # graft: measure — warmup fence

    pins: dict = {}

    # -- pin 1: a warmed loop is clean and the gauges land ---------------
    registry = metrics.Registry()
    watch = CompileWatch(fn=_STEP_NAME)
    try:
        with watch.steady_state():
            y = x
            for _ in range(8):
                y = step(y)
            jax.block_until_ready(y)  # graft: measure — scope fence
        watch.bind_registry(registry)
        rendered = registry.render()
        pins["clean_steady"] = {
            "ok": ("jit_recompiles_total" in rendered
                   and "h2d_d2h_bytes" in rendered
                   and watch.d2h_bytes == 0 and not watch.host_syncs),
            "d2h_bytes": watch.d2h_bytes,
            "stray_compiles": len(watch.stray_compiles),
        }
    except CompileWatchViolation as e:
        pins["clean_steady"] = {"ok": False, "error": str(e)[:300]}

    # -- pin 2: a shape-varying call fails the gate naming the fn --------
    try:
        with CompileWatch(fn=_STEP_NAME).steady_state():
            jax.block_until_ready(step(x_other))  # graft: measure
        pins["planted_recompile"] = {
            "ok": False, "error": "recompile not caught"}
    except CompileWatchViolation as e:
        pins["planted_recompile"] = {
            "ok": _STEP_NAME in str(e) and "recompile" in str(e),
            "message": str(e)[:300],
        }

    # -- pin 3: a .item() in the loop fails the gate naming the fn -------
    # warm the reduction too, so the violation is PURELY the host sync
    step(x).sum().block_until_ready()  # graft: measure — warmup fence
    try:
        with CompileWatch(fn=_STEP_NAME).steady_state():
            total = 0.0
            for _ in range(4):
                total += step(x).sum().item()
        pins["planted_host_sync"] = {
            "ok": False, "error": ".item() not caught"}
    except CompileWatchViolation as e:
        pins["planted_host_sync"] = {
            "ok": (_STEP_NAME in str(e)
                   and "materialization" in str(e)),
            "message": str(e)[:300],
        }

    return {"pins": pins,
            "ok": all(p.get("ok") for p in pins.values())}
