"""Where the program runs: device identity, the chip requirement of the
measurement entry points, and the one persistent compile cache.

Every entry point that compiles calls :func:`enable_compile_cache`
before its first compile; ``benchmark/run.py`` and ``chip_smoke.py`` call
:func:`require_tpu` before anything else, because a time taken on the
CPU backend or the Pallas interpreter says nothing about the chip.
"""

from __future__ import annotations

import os
from pathlib import Path

# <checkout>/.jax_cache — fixed, because the directory is part of the
# cache key: a path that moves (tmp name, pid, time) never hits.
_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory and
    return it. ``JAX_COMPILATION_CACHE_DIR`` wins when set (JAX reads it
    itself, so nothing is set in code); otherwise the fixed directory
    inside the checkout. On the CPU backend nothing is enabled unless the
    variable asks for it: CPU programs compile in seconds, and the test
    suite must not leave a cache in the checkout."""
    from code_intelligence_tpu.utils import flight_recorder

    # whoever asks for the cache wants to know what it held: the compile
    # ledger listens from here on (hit, miss and seconds a program)
    flight_recorder.get_accountant().listen()
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    if jax.default_backend() != "cpu":
        jax.config.update("jax_compilation_cache_dir", str(_CACHE_DIR))
    return str(_CACHE_DIR)


def describe() -> dict:
    """``{"platform", "kind", "count"}`` as JAX reports the devices."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_tpu(what: str) -> dict:
    """:func:`describe`, or exit non-zero when the platform is not a TPU.
    ``what`` names the caller in the message."""
    dev = describe()
    if dev["platform"] != "tpu":
        raise SystemExit(
            f"{what}: needs a TPU, JAX reports {dev} — refusing to "
            f"measure on this platform")
    return dev
