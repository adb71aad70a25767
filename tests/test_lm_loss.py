"""`ops/lm_loss.py`: the decoder's product, the cross-entropy and the
accuracy as one op, its two cores and the rule between them, and the
trainer that calls it. The kernels are interpreted here (the CPU); that
Mosaic takes them at the flagship's shapes is
`tests/test_pallas_tpu_compile_lstm.py`'s to say.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from code_intelligence_tpu.data import LMStreamLoader
from code_intelligence_tpu.models import AWDLSTMConfig
from code_intelligence_tpu.ops import lm_loss
from code_intelligence_tpu.parallel import make_mesh
from code_intelligence_tpu.training import LMTrainer, TrainConfig, loop
from test_training import (_FirstWindows, _one_chip, repeating_corpus,
                           tiny_model)

BF16, F32 = jnp.bfloat16, jnp.float32
# shapes that divide nothing evenly: rows, width, vocabulary
B, T, E, V = 3, 67, 96, 1000
N = B * T


def _parent(h, w, b, y):
    """The three lines `training/loop.py::_loss` had until PR 50, on the
    logits `AWDLSTMLM.__call__` makes."""
    logits = jnp.einsum("bte,ve->btv", h, w)
    if b is not None:
        logits = logits + b
    ce = optax.softmax_cross_entropy_with_integer_labels(
        logits.astype(jnp.float32), y)
    return ce, jnp.argmax(logits, -1) == y


def _operands(dtype, bias):
    """Random rows, and four made ones whose maximum is known: row 0's in
    the first vocabulary tile, row 1's in the last, partly masked one
    (and ``y`` there), rows 2 and 3 with the same maximum in two tiles,
    ``y`` at the first of the two and at the second."""
    k = jax.random.split(jax.random.PRNGKey(50), 4)
    h = jax.random.normal(k[0], (N, E), F32)
    w = jax.random.normal(k[1], (V, E), F32) * 0.3
    b = jax.random.normal(k[2], (V,), F32) * 0.5
    y = jax.random.randint(k[3], (N,), 0, V)
    at = {0: (3,), 1: (V - 1,), 2: (5, 700), 3: (6, 701)}
    w = w.at[:, :4].set(0.0)
    h = h.at[:4].set(0.0)
    for row, cols in at.items():
        h = h.at[row, row].set(8.0)
        for c in cols:
            w = w.at[c, row].set(4.0)
            b = b.at[c].set(0.25)
    y = y.at[:4].set(jnp.asarray([3, V - 1, 5, 701]))
    return (h.astype(dtype), w.astype(dtype),
            b.astype(dtype) if bias else None, y)


def _value_and_grads(core, h, w, b, y):
    """``(ce, hit, (dh, dw[, db]))`` under a row weight that is no
    constant, so a row's gradient is its own."""
    weight = jnp.linspace(0.5, 1.5, y.size).reshape(y.shape)

    def loss(h, w, b):
        ce, hit = core(h, w, b, y)
        return (ce * weight).sum(), (ce, hit)

    (_, (ce, hit)), grads = jax.value_and_grad(
        loss, argnums=(0, 1) if b is None else (0, 1, 2), has_aux=True)(
            h, w, b)
    return ce, hit, grads


# -- (a) the reference core is the parent's code -------------------------------

@pytest.mark.parametrize("bias", [True, False], ids=["bias", "no_bias"])
@pytest.mark.parametrize("dtype", [F32, BF16], ids=["f32", "bf16"])
def test_the_reference_core_is_the_parents_einsum_optax_and_argmax(dtype,
                                                                   bias):
    h, w, b, y = _operands(dtype, bias)
    h, y = h.reshape(B, T, E), y.reshape(B, T)
    want = jax.jit(lambda *a: _value_and_grads(_parent, *a))(h, w, b, y)
    got = jax.jit(lambda *a: _value_and_grads(
        lm_loss.decoder_cross_entropy, *a))(h, w, b, y)
    assert got[0].shape == (B, T) and got[0].dtype == F32
    assert got[1].dtype == jnp.bool_
    for a, c in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        assert a.dtype == c.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(c, np.float32))


# -- (b) one arithmetic, two cores ---------------------------------------------

@pytest.mark.parametrize("tiles", [(128, 128), (128, 256), (256, 1024)],
                         ids=lambda t: "x".join(map(str, t)))
@pytest.mark.parametrize("bias", [True, False], ids=["bias", "no_bias"])
@pytest.mark.parametrize("dtype", [F32, BF16], ids=["f32", "bf16"])
def test_the_kernel_core_equals_the_reference_core(dtype, bias, tiles):
    """Two row tiles of 128 and eight or four vocabulary tiles, the last
    one masked from column 1000 on; and one tile of each. The reference
    runs op by op (a jitted bfloat16 program on the CPU keeps excess
    precision between the product and the bias, which is not the
    rounding the op states)."""
    h, w, b, y = _operands(dtype, bias)
    want = _value_and_grads(lm_loss._reference_core, h, w, b, y)
    got = _value_and_grads(
        lambda *a: lm_loss._kernel_core(*a, tiles), h, w, b, y)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(got[1], want[1])
    assert list(np.asarray(got[1][:4])) == [True, True, True, False]
    for a, c in zip(want[2], got[2]):
        assert a.dtype == c.dtype == dtype and a.shape == c.shape
        a, c = np.asarray(a, np.float32), np.asarray(c, np.float32)
        if dtype == F32:
            np.testing.assert_allclose(c, a, rtol=1e-4, atol=1e-5)
        else:
            # d is rounded to bfloat16 (2**-9 of itself) before its
            # products and the sums after: an element moves by a few
            # roundings of its largest term
            np.testing.assert_allclose(c, a, rtol=2 ** -6,
                                       atol=2 ** -8 * np.abs(a).max())


def test_the_forward_alone_keeps_no_logits():
    """A validation step takes no gradient: the op then writes its three
    vectors and no array of the logits' shape."""
    h, w, b, y = _operands(BF16, True)
    text = str(jax.make_jaxpr(
        lambda *a: lm_loss._kernel_core(*a, (128, 256)))(h, w, b, y))
    assert "pallas_call" in text and "bf16[256,1024]" not in text
    text = str(jax.make_jaxpr(lambda *a: jax.grad(
        lambda *a: lm_loss._kernel_core(*a, (128, 256))[0].sum())(*a))(
            h, w, b, y))
    assert "bf16[256,1024]" in text and "f32[256,1024]" not in text


# -- (c) the rule ---------------------------------------------------------------

FLAGSHIP = (104 * 67, 800, 60000)


@pytest.mark.parametrize("backend,dtype,shape,devices,want", [
    ("tpu", BF16, FLAGSHIP, 1, True),
    ("cpu", BF16, FLAGSHIP, 1, False),      # the interpreter: a test device
    ("tpu", F32, FLAGSHIP, 1, False),       # the parity tests' dtype
    ("tpu", BF16, FLAGSHIP, 4, False),      # a partitioned step: no Mosaic
    ("tpu", BF16, FLAGSHIP, 8, False),
    ("tpu", BF16, (48, 8, 32), 1, True),    # the tests' tiny model
    ("tpu", BF16, (48, 12, 32), 1, False),  # E of part of a sublane
    ("tpu", BF16, (64 * 1024, 800, 60000), 1, False),  # dh past VMEM
])
def test_the_rule(backend, dtype, shape, devices, want):
    assert lm_loss.loss_is_kernel(backend, dtype, *shape, devices) is want


def test_the_tiles_are_a_function_of_the_shapes():
    assert lm_loss._kernel_tiles(*FLAGSHIP) == lm_loss._kernel_tiles(
        *FLAGSHIP)
    tm, tn = lm_loss._kernel_tiles(*FLAGSHIP)
    assert tm % 128 == 0 and tn % 128 == 0
    # the least padding of the rows of any tile there is
    assert -(-FLAGSHIP[0] // tm) * tm == min(
        -(-FLAGSHIP[0] // t) * t for t in lm_loss._ROW_TILES)
    assert (tm, tn) == (1408, 1024)  # 5 x 1408 = 7040 rows for 6968
    assert lm_loss._kernel_tiles(48, 8, 32) == (128, 128)


def test_on_the_cpu_the_op_is_the_reference_core():
    h, w, b, y = _operands(BF16, True)
    text = str(jax.make_jaxpr(lm_loss.decoder_cross_entropy)(h, w, b, y))
    assert "pallas_call" not in text and "custom_vjp" not in text


# -- (d) the trainer ------------------------------------------------------------

def _parent_loss(trainer, params, x, y, lstm_states, dropout_rng):
    """`LMTrainer._loss` as the parent commit had it."""
    logits, raw, dropped, new_states = trainer.model.apply(
        {"params": params}, x, lstm_states, deterministic=False,
        rngs={"dropout": dropout_rng})
    ce = optax.softmax_cross_entropy_with_integer_labels(
        logits.astype(jnp.float32), y).mean()
    ar = trainer.tcfg.alpha * jnp.mean(jnp.square(dropped.astype(jnp.float32)))
    tar = trainer.tcfg.beta * jnp.mean(
        jnp.square((raw[:, 1:] - raw[:, :-1]).astype(jnp.float32)))
    acc = jnp.mean((jnp.argmax(logits, -1) == y).astype(jnp.float32))
    return ce + ar + tar, (new_states, ce, acc)


@pytest.mark.parametrize("kw", [{}, {"dtype": BF16}, {"out_bias": False},
                                {"tie_weights": False}, {"qrnn": True}],
                         ids=["f32", "bf16", "no_bias", "untied", "qrnn"])
def test_on_the_cpu_the_trainers_loss_is_the_parents(kw):
    """One seeded step: the loss, the cross-entropy, the accuracy, the
    carried states and every leaf's gradient, to the bit."""
    trainer = LMTrainer(tiny_model(**kw), TrainConfig(batch_size=8, bptt=6),
                        mesh=_one_chip())
    assert trainer.loss_kernel == 0
    state = trainer.init_state(jax.random.PRNGKey(0))
    x, y = next(LMStreamLoader(repeating_corpus(), 8, 6).epoch(0))
    args = (state.params, x, y, state.lstm_states, jax.random.PRNGKey(7))
    got = jax.jit(jax.value_and_grad(trainer._loss, has_aux=True))(*args)
    want = jax.jit(jax.value_and_grad(
        lambda *a: _parent_loss(trainer, *a), has_aux=True))(*args)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, c in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(c, np.float32))


def test_the_models_logits_are_the_features_product():
    """`AWDLSTMLM.__call__` keeps returning logits, for every caller but
    the trainer, and ``features`` is the same call without the product."""
    trainer = LMTrainer(tiny_model(), TrainConfig(batch_size=8, bptt=6),
                        mesh=_one_chip())
    state = trainer.init_state(jax.random.PRNGKey(0))
    x, _ = next(LMStreamLoader(repeating_corpus(), 8, 6).epoch(0))
    logits, raw, dropped, states = trainer.model.apply(
        {"params": state.params}, x, state.lstm_states)
    raw2, dropped2, states2, dec_w, dec_b = trainer.model.apply(
        {"params": state.params}, x, state.lstm_states, method="features")
    assert logits.shape == (8, 6, 32) and dec_w.shape == (32, 8)
    np.testing.assert_array_equal(
        logits, jnp.einsum("bte,ve->btv", dropped2, dec_w) + dec_b)
    for a, c in zip(jax.tree.leaves((raw, dropped, states)),
                    jax.tree.leaves((raw2, dropped2, states2))):
        np.testing.assert_array_equal(a, c)


def _rule_sees_backend(monkeypatch, backend):
    """The rule's first input, steered from the test (the op and the
    trainer ask ``jax.default_backend()``, which is "cpu" here); its
    other inputs come from the call as they do on the chip."""
    real = lm_loss.loss_is_kernel
    for module in (lm_loss, loop):
        monkeypatch.setattr(module, "loss_is_kernel",
                            lambda _backend, *rest: real(backend, *rest))


def test_kernel_steps_equal_the_reference_steps(monkeypatch):
    """The rule's answer forced to "kernel" (interpret mode, the tiny
    widths, bfloat16): three scanned steps' loss, cross-entropy, accuracy
    and gradient norm are the reference core's, in bfloat16's band, and
    a validation dispatch's too."""
    tcfg = TrainConfig(batch_size=8, bptt=6, lr=5e-3, steps_per_dispatch=3)
    it = LMStreamLoader(repeating_corpus(), 8, 6, shuffle_offsets=False).epoch(0)
    xs, ys = map(np.stack, zip(*(next(it) for _ in range(3))))
    out = {}
    # the op asks the rule when a step is traced: the reference's steps
    # run before the rule is steered
    for name in ("reference", "kernel"):
        if name == "kernel":
            _rule_sees_backend(monkeypatch, "tpu")
        trainer = LMTrainer(tiny_model(dtype=BF16), tcfg, mesh=_one_chip(),
                            steps_per_epoch=40)
        assert trainer.loss_kernel == (name == "kernel")
        state = trainer.init_state(jax.random.PRNGKey(0))
        with trainer.mesh:
            text = str(jax.make_jaxpr(trainer._make_train_steps())(
                state, xs, ys))
            assert ("pallas_call" in text) == (name == "kernel")
            ces, accs, _ = trainer.eval_steps(
                state.params, state.lstm_states, xs, ys)
            _, ms = trainer.train_steps(state, xs, ys)
        out[name] = dict(jax.device_get(ms), val_ce=np.asarray(ces),
                         val_acc=np.asarray(accs))
    for key in ("loss", "ce", "val_ce"):
        np.testing.assert_allclose(out["kernel"][key], out["reference"][key],
                                   rtol=2e-3)
    np.testing.assert_allclose(out["kernel"]["grad_norm"],
                               out["reference"]["grad_norm"], rtol=2e-2)
    for key in ("accuracy", "val_acc"):
        np.testing.assert_allclose(out["kernel"][key], out["reference"][key],
                                   atol=1.5 / 48)


def test_a_mesh_trains_on_the_reference_core(monkeypatch):
    """On a mesh of eight the rule sees the devices: the step holds no
    Mosaic call, which a GSPMD-partitioned program cannot."""
    _rule_sees_backend(monkeypatch, "tpu")
    mesh = make_mesh({"data": 8})
    trainer = LMTrainer(tiny_model(dtype=BF16), TrainConfig(batch_size=16, bptt=6),
                        mesh=mesh)
    assert trainer.loss_kernel == 0
    state = trainer.init_state(jax.random.PRNGKey(0))
    x, y = next(LMStreamLoader(repeating_corpus(), 16, 6).epoch(0))
    with mesh:
        text = str(jax.make_jaxpr(trainer._make_train_step())(state, x, y))
        assert "pallas_call" not in text
        _, m = trainer.train_step(state, x, y)
    assert np.isfinite(float(m["loss"]))


@pytest.mark.parametrize("k,roots", [(3, ("train.fit", "train.dispatch")),
                                     (1, ("train.fit", "train.step"))],
                         ids=["scanned", "single"])
def test_a_traced_fit_says_which_core_ran(monkeypatch, k, roots):
    """``loss_kernel`` on the ``train.fit`` record and on every dispatch:
    0 on the CPU."""
    from code_intelligence_tpu.utils import tracing

    tracer = tracing.Tracer()
    monkeypatch.setattr(tracing, "_default", tracer)
    trainer = LMTrainer(tiny_model(), TrainConfig(batch_size=8, bptt=6,
                                             steps_per_dispatch=k),
                        mesh=_one_chip(), steps_per_epoch=40)
    got = []
    tracer.on_trace(got.append)
    trainer.fit(_FirstWindows(LMStreamLoader(repeating_corpus(), 8, 6), 3), None,
                epochs=1)
    by_root = {t["root"]: t for t in got}
    for root in roots:
        attrs = by_root[root]["spans"][0]["attrs"]
        assert attrs["loss_kernel"] == 0, (root, attrs)
        assert attrs["resident_lstm_layers"] == 0, (root, attrs)
