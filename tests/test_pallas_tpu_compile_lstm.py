"""The tiles `_pick_tiles` chooses compile for the chip, at the shapes the
repo runs: the LSTM's forward, backward and a layer's gradient
(`tests/pallas_tpu_compile.py` has the how and the why).
"""

import math
import re

import jax
import jax.numpy as jnp
import pytest

from code_intelligence_tpu.ops import lm_loss
from code_intelligence_tpu.ops.pallas_lstm import (
    fused_lstm_backward,
    fused_lstm_forward,
    lstm_layer_fused,
)
from pallas_tpu_compile import _compiled_text, one_chip  # noqa: F401

H = 2500  # the flagship's hidden size: W_hh is 50 MB of bfloat16


# (T, B): a bulk chunk program at the cells' 200 rows and at the 100 and 25
# its last group narrows to, and the server's default batch
@pytest.mark.parametrize("t,b", [(512, 200), (512, 100), (512, 25), (64, 32)])
def test_the_inference_forward_compiles_at_the_picked_tile(one_chip, t, b):
    text = _compiled_text(
        lambda x, w, h, c: fused_lstm_forward(x, w, h, c, interpret=False),
        one_chip, (t, b, 4 * H), (4 * H, H), (b, H), (b, H))
    assert "tpu_custom_call" in text


def test_the_training_forward_and_backward_compile_at_the_picked_tiles(
        one_chip):
    t, b = 67, 104  # the reference's bptt and batch
    fwd = _compiled_text(
        lambda x, w, h, c: fused_lstm_forward(x, w, h, c, with_gates=True,
                                              interpret=False),
        one_chip, (t, b, 4 * H), (4 * H, H), (b, H), (b, H))
    assert "tpu_custom_call" in fwd
    bwd = _compiled_text(
        lambda g, cp, do, w, dh, dc: fused_lstm_backward(
            g, cp, do, w, dh, dc, interpret=False),
        one_chip, (t, b, 4 * H), (t, b, H), (t, b, H), (4 * H, H), (b, H),
        (b, H))
    assert "tpu_custom_call" in bwd


# (B, H, in, dtype): the train cell's two layer shapes, a batch that splits
# into two tiles, and a float32 layer the rule also calls resident
@pytest.mark.parametrize("b,h,in_dim,dtype", [
    (104, H, 800, jnp.bfloat16), (104, 800, H, jnp.bfloat16),
    (200, H, H, jnp.bfloat16), (104, 800, H, jnp.float32)])
def test_a_layers_gradient_compiles_outside_the_train_step(
        one_chip, monkeypatch, b, h, in_dim, dtype):
    """`jax.grad` of ONE resident layer: the program in which the adjoint
    at its whole-batch tile ran out of VMEM under the inference kernels'
    limit while `train_steps` compiled (PR 31): XLA keeps the kernel's
    small operands in VMEM here. The layer asks the backend whether to
    interpret, so the test answers for it."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    t, g = 67, 4 * h

    def loss(x, h0, c0, w_ih, w_hh, bias):
        out, (h_t, _) = lstm_layer_fused(x, (h0, c0), w_ih, w_hh, bias)
        return (out.astype(jnp.float32) ** 2).mean() \
            + h_t.astype(jnp.float32).sum()

    args = [jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)
            for s in ((b, t, in_dim), (b, h), (b, h), (g, in_dim), (g, h),
                      (g,))]
    text = jax.jit(jax.grad(loss, argnums=(0, 3, 4, 5))).lower(
        *args).compile().as_text()
    assert text.count("tpu_custom_call") >= 2  # forward and adjoint


# the decoder's product and the cross-entropy, forward and backward
# (`ops/lm_loss.py`): the flagship's 104 x 67 rows, width and vocabulary
@pytest.mark.parametrize("bias", [True, False], ids=["bias", "no_bias"])
def test_the_decoders_loss_compiles_at_the_flagships_shapes(
        one_chip, monkeypatch, bias):
    """`jax.value_and_grad` of the kernel core at the tile the rule picks:
    both kernels are Mosaic's, and no float32 array of the logits' shape
    is left. The kernels ask the backend whether to interpret, so the
    test answers for it."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    n, e, v = 104 * 67, 800, 60000
    assert lm_loss.loss_is_kernel("tpu", jnp.bfloat16, n, e, v, 1)

    def loss(h, w, b, y):
        ce, hit = lm_loss.decoder_cross_entropy(h, w, b if bias else None, y)
        return ce.mean(), hit

    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        ((104, 67, e), jnp.bfloat16), ((v, e), jnp.bfloat16),
        ((v,), jnp.bfloat16), ((104, 67), jnp.int32))]
    text = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2) if bias else (0, 1), has_aux=True)).lower(
            *args).compile().as_text()
    assert text.count("tpu_custom_call") >= 2  # forward and backward
    sizes = [math.prod(map(int, dims.split(",")))
             for dims in re.findall(r"f32\[([\d,]+)\]", text)]
    assert sizes and max(sizes) < n * v // 8  # (60000, 800) is the largest
