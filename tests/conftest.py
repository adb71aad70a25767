"""Test harness: run everything on a virtual 8-device CPU mesh.

SURVEY.md §4 "implication for the TPU build": multi-chip code paths must be
testable without a TPU pod, via
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
"""

import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(__file__)))
from __graft_entry__ import COLLECTIVE_TIMEOUT_FLAGS  # noqa: E402

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    _flags = (_flags + " --xla_force_host_platform_device_count=8").strip()
if "xla_cpu_collective" not in _flags:
    # An 8-way collective rendezvous must time-slice 8 device threads
    # through the host's cores, and under concurrent load the default
    # 20s-warn/40s-terminate window starves — XLA then ABORTS the whole
    # process ("Exiting to ensure a consistent program state",
    # rendezvous.cc). Waiting is always correct here.
    _flags += COLLECTIVE_TIMEOUT_FLAGS
os.environ["XLA_FLAGS"] = _flags
os.environ["JAX_PLATFORMS"] = "cpu"

# One compilation cache a run. Compiling is most of the suite's CPU time,
# and the same small programs are compiled by every worker, by every
# subprocess a test starts and by every new closure over one function:
# the first to compile a program leaves it where the others find it. Made
# here, before the workers start (they and the subprocesses inherit the
# environment), and removed when the session ends; a directory the caller
# placed is left alone, and so is a worker's (it has its controller's).
_cache_dir = None
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    _cache_dir = tempfile.mkdtemp(prefix="jax_cache_tier1_")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = _cache_dir
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "-1"


def pytest_unconfigure(config):
    if _cache_dir is not None:
        shutil.rmtree(_cache_dir, ignore_errors=True)
