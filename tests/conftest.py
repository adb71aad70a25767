"""Test harness: run everything on a virtual 8-device CPU mesh.

SURVEY.md §4 "implication for the TPU build": multi-chip code paths must be
testable without a TPU pod, via
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
"""

import os
import sys

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
