"""What the four `test_pallas_tpu_compile_*.py` files share: a v5e that is
described and not attached, and the compiled text of a function for it.

A tile is a function of the shapes alone (tests/test_pallas_lstm.py), so
what the pickers return is what the chip is handed: Mosaic, the TPU's own
compiler, is asked to take it (`/opt/skills/guides/on-chip-measurement`
§2). Interpret mode cannot refuse a slice that is not aligned to the
tiling or a kernel that asks for more VMEM than it may use; this can.
Nothing runs and nothing is timed. Compiling is the work, so the cases lie
in four files by kernel family (the GQA core's in two: Trinity's sixteen,
and the rest) and as many workers share them; this module is no
`test_*.py`, so nothing is collected twice.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _gqa_text(monkeypatch, one_chip, rows, T, S, window, Hq, Hkv, d):
    """The compiled text of one ``gqa_cached`` call as the encoders make
    it; the rule asks the backend, so the test answers for it."""
    from code_intelligence_tpu.ops.attention import gqa_cached

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def core(q, k, v, k_cache, v_cache, pos):
        return gqa_cached(q, k, v, k_cache, v_cache, pos, d ** -0.5,
                          window=window)

    args = [jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)
            for s in ((rows, T, Hq, d), (rows, T, Hkv, d), (rows, T, Hkv, d),
                      (rows, Hkv, S, d), (rows, Hkv, S, d))]
    pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    return jax.jit(core).lower(*args, pos).compile().as_text()
