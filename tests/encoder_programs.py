"""What the encoder test files share to run their models compiled.

Op by op, the CPU backend compiles and dispatches every primitive of a
model of several layers one by one, chunk after chunk; that, not any
check, was most of tier-1's time (ROADMAP D26). A new encoder's tests jit
their init and their encode through these two. No `test_*.py`, so nothing
is collected from it.
"""

import functools
import math

import jax
import jax.numpy as jnp


@functools.lru_cache(maxsize=None)
def compiled(enc):
    """``enc.encode`` as ONE jitted callable an encoder, for the life of
    the process (the models are tiny): the tests that run an encoder at
    the same shapes share its programs. A test that patches what
    ``encode`` calls builds its own encoder after the patch, so its
    trace sees it."""
    return jax.jit(enc.encode)


def seeded(ref, seed, model, tails=None, dtype=jnp.float32, layer=None):
    """``ref.init_params`` at ``PRNGKey(seed)``, sampled by ONE compiled
    program. ``layer`` names the one layer a test reads: the keys are
    split as for the whole model and the other layers' draws are
    pruned."""
    def sample(key):
        params = ref.init_params(key, model, tails, dtype)
        return params["layers"][layer] if layer else params

    return jax.jit(sample)(jax.random.PRNGKey(seed))


def the_rule_says_grouped_kernels(monkeypatch):
    """``ops/gmm.py``'s rule answered by the test (it sees the CPU and
    float32 here), and tiles that divide the tiny programs (up to 8
    rows, whole widths): the held experts' two products run on the
    Pallas kernels, interpreted. An encoder built after this call traces
    them."""
    from code_intelligence_tpu.ops import gmm

    monkeypatch.setattr(gmm, "gmm_is_kernel", lambda *a: True)
    monkeypatch.setattr(
        gmm, "_kernel_tiles",
        lambda R, count, E, F: (math.gcd(R, 8), math.gcd(R, 8), F, E))
