"""Shared k-steps-per-dispatch scan wrapper.

Every program invocation pays a fixed host dispatch cost; fast training
steps (the universal kind model, the distiller) are dominated by it in a
naive per-batch loop. This helper builds
the one construct they share: a jit-compiled ``lax.scan`` that chains k
optimizer steps over stacked batches with the ``(params, opt_state)``
carry donated.

The LM trainer's ``train_steps`` (`training/loop.py`) is the richer,
TrainState-and-sharding-aware sibling of this pattern and intentionally
not expressed through it.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax


def scan_dispatch(step_fn: Callable) -> Callable:
    """Wrap ``step_fn(params, opt_state, *batch) -> (params, opt_state,
    aux)`` into ``steps(params, opt_state, *stacked)`` running one scanned
    device program over the leading axis of ``stacked`` and returning
    ``(params, opt_state, auxs)`` with each aux leaf stacked to ``(k, ...)``.

    Chunking policy is the caller's: keep the set of distinct leading-dim
    shapes small (full chunks + at most one tail shape) so the jit cache
    stays at two programs.
    """

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def steps(params, opt_state, *stacked):
        def body(carry, xs):
            p, o = carry
            p, o, aux = step_fn(p, o, *xs)
            return (p, o), aux

        (params, opt_state), auxs = jax.lax.scan(
            body, (params, opt_state), stacked)
        return params, opt_state, auxs

    return steps
