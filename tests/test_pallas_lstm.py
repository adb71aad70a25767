"""Pallas fused LSTM cell: exact parity with the XLA-scan reference
(`ops/lstm.py`) for forward outputs, carried state, and all gradients.
Runs in interpret mode on the CPU mesh (on real hardware the kernel is
exercised by chip_smoke.py's `kernels` and `train` legs, and by the
benchmark's `lstm_train_lm` cell)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from code_intelligence_tpu.ops.lstm import lstm_layer
from code_intelligence_tpu.ops.pallas_lstm import (
    MAX_RESIDENT_H,
    _pick_tiles,
    _pick_tiles_bwd,
    _sublane_snap,
    _train_grid,
    feasible_tiles,
    feasible_tiles_bwd,
    fits_resident,
    fused_lstm_backward,
    fused_lstm_forward,
    fused_lstm_forward_ragged,
    lstm_layer_fused,
    lstm_layer_fused_ragged,
)

B, T, IN, H = 4, 21, 12, 16  # T deliberately not a multiple of the chunk


def make_inputs(seed=0, t=T, h=H, in_dim=IN, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(B, t, in_dim) * 0.5, dtype)
    h0 = jnp.asarray(rng.randn(B, h) * 0.1, dtype)
    c0 = jnp.asarray(rng.randn(B, h) * 0.1, dtype)
    w_ih = jnp.asarray(rng.randn(4 * h, in_dim) * 0.2, dtype)
    w_hh = jnp.asarray(rng.randn(4 * h, h) * 0.2, dtype)
    bias = jnp.asarray(rng.randn(4 * h) * 0.1, dtype)
    return x, (h0, c0), w_ih, w_hh, bias


class TestForwardParity:
    def test_outputs_and_state_match_scan(self):
        x, state, w_ih, w_hh, bias = make_inputs()
        ref_out, (ref_h, ref_c) = lstm_layer(x, state, w_ih, w_hh, bias)
        out, (h_t, c_t) = lstm_layer_fused(x, state, w_ih, w_hh, bias, True)
        np.testing.assert_allclose(out, ref_out, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(h_t, ref_h, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(c_t, ref_c, rtol=1e-5, atol=1e-5)

    def test_time_padding_edge(self):
        # T smaller than one chunk and T an exact multiple both work
        for t in (3, 16, 32):
            x, state, w_ih, w_hh, bias = make_inputs(seed=t, t=t)
            ref_out, _ = lstm_layer(x, state, w_ih, w_hh, bias)
            out, _ = lstm_layer_fused(x, state, w_ih, w_hh, bias, True)
            np.testing.assert_allclose(out, ref_out, rtol=1e-5, atol=1e-5, err_msg=str(t))

    def test_inference_path_skips_gates(self):
        x, (h0, c0), w_ih, w_hh, bias = make_inputs(seed=4)
        x_proj = jnp.einsum("bti,gi->tbg", x, w_ih) + bias  # time-major
        out, gates, _ = fused_lstm_forward(x_proj, w_hh, h0, c0, interpret=True)
        assert gates is None  # no residual HBM write outside training
        ref_out, _ = lstm_layer(x, (h0, c0), w_ih, w_hh, bias)
        np.testing.assert_allclose(out.swapaxes(0, 1), ref_out, rtol=1e-5, atol=1e-5)

    def test_gates_returned_match_recomputation(self):
        x, (h0, c0), w_ih, w_hh, bias = make_inputs(seed=5)
        x_proj = jnp.einsum("bti,gi->tbg", x, w_ih) + bias  # time-major
        out, (gates, c_prev_seq), _ = fused_lstm_forward(
            x_proj, w_hh, h0, c0, with_gates=True, interpret=True
        )
        # forward c/h reconstruction from saved gates reproduces outputs
        # (out, gates, c_prev_seq are (T, B, ·) time-major)
        i_g, f_g = gates[..., :H], gates[..., H:2*H]
        g_g, o_g = gates[..., 2*H:3*H], gates[..., 3*H:]
        c = c0
        for t in range(T):
            # the emitted pre-step cell state matches the recurrence
            np.testing.assert_allclose(c_prev_seq[t], c, rtol=1e-5, atol=1e-5)
            c = f_g[t] * c + i_g[t] * g_g[t]
            h = o_g[t] * jnp.tanh(c)
            np.testing.assert_allclose(h, out[t], rtol=1e-5, atol=1e-5)


class TestRaggedForward:
    """Golden pins for the length-aware serve kernel (interpret mode):
    the ragged contract `inference/slots.py` relies on — dense values on
    each row's valid prefix, finite zeros beyond it, carry frozen at
    exactly ``min(valid, T)`` real steps."""

    def _proj(self, x, w_ih, bias):
        return jnp.einsum("bti,gi->tbg", x, w_ih) + bias

    def test_valid_prefix_matches_dense_and_tail_is_zero(self):
        x, (h0, c0), w_ih, w_hh, bias = make_inputs(seed=6)
        x_proj = self._proj(x, w_ih, bias)
        valid = jnp.asarray(np.array([0, 1, T, T - 3], np.int32))
        dense, _, _ = fused_lstm_forward(x_proj, w_hh, h0, c0,
                                         interpret=True)
        out, _ = fused_lstm_forward_ragged(x_proj, w_hh, h0, c0, valid,
                                           interpret=True)
        out, dense = np.asarray(out), np.asarray(dense)
        for b, v in enumerate(np.asarray(valid)):
            np.testing.assert_allclose(out[:v, b], dense[:v, b],
                                       rtol=1e-5, atol=1e-5,
                                       err_msg=f"row {b}")
            assert np.all(out[v:, b] == 0.0), f"tail not zero, row {b}"

    def test_state_frozen_at_valid(self):
        # h_T/c_T equal the dense kernel run for exactly `valid` steps:
        # a row never pollutes its carry on dead tail tokens
        x, (h0, c0), w_ih, w_hh, bias = make_inputs(seed=7)
        x_proj = self._proj(x, w_ih, bias)
        # three valids = three truncated dense compiles; enough to pin
        # zero / mid-chunk / full without paying a 4th compile in tier-1
        valid_np = np.array([0, 9, T], np.int32)
        _, (h_t, c_t) = fused_lstm_forward_ragged(
            x_proj, w_hh, h0, c0, jnp.asarray(valid_np), interpret=True)
        for b, v in enumerate(valid_np):
            if v == 0:
                want_h, want_c = h0[b], c0[b]
            else:
                _, _, (hd, cd) = fused_lstm_forward(
                    x_proj[:v], w_hh, h0, c0, interpret=True)
                want_h, want_c = hd[b], cd[b]
            np.testing.assert_allclose(h_t[b], want_h, rtol=1e-5,
                                       atol=1e-5, err_msg=f"h row {b}")
            np.testing.assert_allclose(c_t[b], want_c, rtol=1e-5,
                                       atol=1e-5, err_msg=f"c row {b}")

    def test_all_exhausted_batch_emits_finite_zeros(self):
        # the grid-skip branch: every chunk is dead, so the output block
        # is the zero-fill path end to end and the carry is untouched
        x, (h0, c0), w_ih, w_hh, bias = make_inputs(seed=8)
        x_proj = self._proj(x, w_ih, bias)
        out, (h_t, c_t) = fused_lstm_forward_ragged(
            x_proj, w_hh, h0, c0, jnp.zeros((B,), jnp.int32),
            interpret=True)
        assert np.all(np.asarray(out) == 0.0)
        np.testing.assert_allclose(h_t, h0, rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(c_t, c0, rtol=1e-6, atol=1e-6)

    def test_valid_straddling_time_chunks(self):
        # explicit (bt, tc) so valid lengths land before, on, and after
        # every chunk boundary the grid walks
        x, (h0, c0), w_ih, w_hh, bias = make_inputs(seed=9, t=8)
        x_proj = self._proj(x, w_ih, bias)
        dense, _, _ = fused_lstm_forward(x_proj, w_hh, h0, c0,
                                         interpret=True, tiles=(8, 2))
        for v in (1, 2, 3, 4, 7, 8):
            valid = jnp.full((B,), v, jnp.int32)
            out, _ = fused_lstm_forward_ragged(
                x_proj, w_hh, h0, c0, valid, interpret=True, tiles=(8, 2))
            np.testing.assert_allclose(np.asarray(out)[:v],
                                       np.asarray(dense)[:v],
                                       rtol=1e-5, atol=1e-5,
                                       err_msg=f"valid={v}")
            assert np.all(np.asarray(out)[v:] == 0.0)

    def test_layer_wrapper_matches_scan_on_valid_prefix(self):
        x, state, w_ih, w_hh, bias = make_inputs(seed=10)
        ref_out, _ = lstm_layer(x, state, w_ih, w_hh, bias)
        valid_np = np.array([3, T, 1, 12], np.int32)
        out, _ = lstm_layer_fused_ragged(
            x, state, w_ih, w_hh, bias, jnp.asarray(valid_np),
            interpret=True)
        for b, v in enumerate(valid_np):
            np.testing.assert_allclose(out[b, :v], ref_out[b, :v],
                                       rtol=1e-5, atol=1e-5,
                                       err_msg=f"row {b}")

    def test_encoder_routes_valid_lens_to_ragged_kernel(self):
        # full AWD encoder with the pallas flag: pooled-relevant outputs
        # (the valid prefix) match the scan encoder given the same
        # valid_lens, and the tail stays finite for masked pooling
        from code_intelligence_tpu.models import AWDLSTMConfig
        from code_intelligence_tpu.models.awd_lstm import (
            AWDLSTMEncoder,
            init_lstm_states,
        )

        tokens = jnp.asarray(np.random.RandomState(0).randint(0, 50, (3, 9)))
        valid = jnp.asarray(np.array([2, 9, 5], np.int32))
        outs = {}
        for flag in (False, True):
            cfg = AWDLSTMConfig(
                vocab_size=50, emb_sz=8, n_hid=16, n_layers=2,
                lstm_use_pallas=flag,
            )
            enc = AWDLSTMEncoder(cfg)
            params = enc.init(
                {"params": jax.random.PRNGKey(0)}, tokens,
                init_lstm_states(cfg, 3)
            )
            raw, _, _ = enc.apply(
                params, tokens, init_lstm_states(cfg, 3),
                deterministic=True, valid_lens=valid
            )
            outs[flag] = np.asarray(raw)
        assert np.all(np.isfinite(outs[True]))
        for b, v in enumerate(np.asarray(valid)):
            np.testing.assert_allclose(outs[True][b, :v], outs[False][b, :v],
                                       rtol=1e-5, atol=1e-5,
                                       err_msg=f"row {b}")


class TestGradientParity:
    def test_all_grads_match_scan_vjp(self):
        x, state, w_ih, w_hh, bias = make_inputs(seed=7)

        def loss_ref(x, state, w_ih, w_hh, bias):
            out, (h_t, c_t) = lstm_layer(x, state, w_ih, w_hh, bias)
            return (out * out).mean() + (h_t * c_t).sum() * 1e-2

        def loss_fused(x, state, w_ih, w_hh, bias):
            out, (h_t, c_t) = lstm_layer_fused(x, state, w_ih, w_hh, bias, True)
            return (out * out).mean() + (h_t * c_t).sum() * 1e-2

        ref = jax.grad(loss_ref, argnums=(0, 1, 2, 3, 4))(x, state, w_ih, w_hh, bias)
        got = jax.grad(loss_fused, argnums=(0, 1, 2, 3, 4))(x, state, w_ih, w_hh, bias)
        names = ["dx", "dstate", "dw_ih", "dw_hh", "dbias"]
        for name, r, g in zip(names, ref, got):
            jax.tree.map(
                lambda a, b: np.testing.assert_allclose(
                    a, b, rtol=2e-4, atol=2e-5, err_msg=name),
                r, g,
            )

    def test_bf16_grads_close_to_scan(self):
        # the training dtype: fused fwd + Pallas adjoint bwd in bf16
        # must track the scan's autodiff within bf16 tolerance
        x, state, w_ih, w_hh, bias = make_inputs(seed=11, dtype=jnp.bfloat16)

        def loss(layer, w_hh):
            out, (h_t, c_t) = layer(x, state, w_ih, w_hh, bias)
            return (out.astype(jnp.float32) ** 2).mean() + (
                h_t.astype(jnp.float32) * c_t.astype(jnp.float32)).sum() * 1e-2

        g_ref = jax.grad(lambda w: loss(lstm_layer, w))(w_hh)
        g_fus = jax.grad(
            lambda w: loss(lambda *a: lstm_layer_fused(*a, True), w))(w_hh)
        np.testing.assert_allclose(
            g_fus.astype(jnp.float32), g_ref.astype(jnp.float32),
            rtol=0.08, atol=2e-3)

    def test_value_and_grad_through_downstream_use(self):
        # grads flow when outputs feed pooling + a head (the classifier path)
        x, state, w_ih, w_hh, bias = make_inputs(seed=9)
        w_head = jnp.ones((H,), jnp.float32)

        def loss(w_hh, variant):
            layer = lstm_layer if variant == "ref" else (
                lambda *a: lstm_layer_fused(*a, True))
            out, _ = layer(x, state, w_ih, w_hh, bias)
            pooled = jnp.concatenate([out.mean(1), out.max(1)], -1)
            return (pooled[:, :H] @ w_head).sum()

        g_ref = jax.grad(lambda w: loss(w, "ref"))(w_hh)
        g_fus = jax.grad(lambda w: loss(w, "fused"))(w_hh)
        np.testing.assert_allclose(g_fus, g_ref, rtol=2e-4, atol=2e-5)


class TestCallerPaddedWindow:
    """The train path pads the layer's input once, to both kernels'
    grids, and hands the kernels windows that are already padded: the
    live steps are ``t_real``, and the padded ones change nothing."""

    def _window(self, seed=13):
        x, (h0, c0), w_ih, w_hh, bias = make_inputs(seed=seed)
        x_proj = jnp.einsum("bti,gi->tbg", x, w_ih) + bias
        return x_proj, w_hh, h0, c0

    def test_forward_freezes_the_carry_past_t_real(self):
        x_proj, w_hh, h0, c0 = self._window()
        want = fused_lstm_forward(x_proj, w_hh, h0, c0, with_gates=True,
                                  interpret=True, tiles=(8, 1))
        padded = jnp.pad(x_proj, ((0, 3), (0, 0), (0, 0)))  # 21 -> 24 steps
        got = fused_lstm_forward(padded, w_hh, h0, c0, with_gates=True,
                                 interpret=True, tiles=(8, 4), t_real=T)
        np.testing.assert_allclose(got[0][:T], want[0], rtol=1e-6, atol=1e-6)
        for g, w in zip(got[1], want[1]):  # gates, c_prev
            np.testing.assert_allclose(g[:T], w, rtol=1e-6, atol=1e-6)
        for g, w in zip(got[2], want[2]):  # the state after T live steps
            np.testing.assert_allclose(g, w, rtol=1e-6, atol=1e-6)

    def test_adjoint_emits_nothing_past_t_real(self):
        x_proj, w_hh, h0, c0 = self._window(seed=14)
        _, (gates, c_prev), _ = fused_lstm_forward(
            x_proj, w_hh, h0, c0, with_gates=True, interpret=True)
        rng = np.random.RandomState(3)
        d_out = jnp.asarray(rng.randn(T, B, H) * 0.1, jnp.float32)
        dh = jnp.asarray(rng.randn(B, H) * 0.1, jnp.float32)
        dc = jnp.asarray(rng.randn(B, H) * 0.1, jnp.float32)
        want = fused_lstm_backward(gates, c_prev, d_out, w_hh, dh, dc,
                                   interpret=True, tiles=(8, 1))
        pad = ((0, 3), (0, 0), (0, 0))
        got = fused_lstm_backward(
            jnp.pad(gates, pad, constant_values=0.5), jnp.pad(c_prev, pad),
            jnp.pad(d_out, pad), w_hh, dh, dc, interpret=True,
            tiles=(8, 4), t_real=T)
        np.testing.assert_allclose(got[0][:T], want[0], rtol=1e-6, atol=1e-6)
        assert not np.asarray(got[0][T:]).any()
        for g, w in zip(got[1:], want[1:]):  # dh0, dc0
            np.testing.assert_allclose(g, w, rtol=1e-6, atol=1e-6)

    @pytest.mark.parametrize("batch,hidden", [
        (104, 2500), (104, 800), (128, 2500), (200, 2500), (4, 16)])
    def test_one_grid_serves_both_train_kernels(self, batch, hidden):
        bp, tp = _train_grid(batch, 67, hidden, 2)
        for bt, tc in (_pick_tiles(batch, hidden, 4 * hidden, True, 2),
                       _pick_tiles_bwd(batch, hidden, 4 * hidden, 2)):
            assert bp % bt == 0 and tp % tc == 0
        assert bp >= batch and tp >= 67 and bp - batch < 16


class TestModelIntegration:
    def test_awd_encoder_parity_with_flag(self):
        # the full AWD-LSTM encoder produces identical outputs with the
        # fused cell enabled (small H -> resident path taken)
        from code_intelligence_tpu.models import AWDLSTMConfig
        from code_intelligence_tpu.models.awd_lstm import (
            AWDLSTMEncoder,
            init_lstm_states,
        )

        tokens = jnp.asarray(np.random.RandomState(0).randint(0, 50, (2, 9)))
        outs = {}
        for flag in (False, True):
            cfg = AWDLSTMConfig(
                vocab_size=50, emb_sz=8, n_hid=16, n_layers=2,
                lstm_use_pallas=flag,
            )
            enc = AWDLSTMEncoder(cfg)
            params = enc.init(
                {"params": jax.random.PRNGKey(0)}, tokens, init_lstm_states(cfg, 2)
            )
            raw, _, new_states = enc.apply(
                params, tokens, init_lstm_states(cfg, 2), deterministic=True
            )
            outs[flag] = (raw, new_states)
        np.testing.assert_allclose(outs[True][0], outs[False][0], rtol=1e-5, atol=1e-5)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5),
            outs[True][1], outs[False][1],
        )

    def test_flagship_h_is_resident_bf16(self):
        # Round 3 on-chip A/B: v5e's ~64MB Mosaic VMEM scope holds the
        # flagship's 50MB bf16 W_hh — the flag routes H=2500 to the
        # kernel in bf16; f32 (100MB) still falls back to the scan.
        from code_intelligence_tpu.models import AWDLSTMConfig

        cfg = AWDLSTMConfig(vocab_size=50, emb_sz=8, n_hid=2500, lstm_use_pallas=True)
        assert fits_resident(cfg.n_hid, itemsize=2)
        assert not fits_resident(cfg.n_hid, itemsize=4)


class TestResidencyGate:
    def test_fits_resident_is_dtype_aware(self):
        assert fits_resident(256) and fits_resident(MAX_RESIDENT_H)  # bf16
        assert not fits_resident(3000, itemsize=2)  # 72MB > VMEM scope
        assert not fits_resident(MAX_RESIDENT_H, itemsize=4)  # f32 halves H
        assert fits_resident(1800, itemsize=4)
        assert fits_resident(2500)  # flagship W_hh (50MB bf16) is resident


# the shapes the repo runs, bf16: train 104 x 2500, the bulk cells' 200 and
# the 100 and 25 rows their last group narrows to, the product's serve
# batch 32, and the tiny widths of the CPU tests
_SHAPES = [(104, 2500), (200, 2500), (100, 2500), (25, 2500), (32, 2500),
           (8, 96)]
_PICKERS = {
    "fwd_gates": (lambda b, h: _pick_tiles(b, h, 4 * h, True, 2),
                  lambda b, h: feasible_tiles(b, h, 4 * h, True, 2)),
    "fwd": (lambda b, h: _pick_tiles(b, h, 4 * h, False, 2),
            lambda b, h: feasible_tiles(b, h, 4 * h, False, 2)),
    "bwd": (lambda b, h: _pick_tiles_bwd(b, h, 4 * h, 2),
            lambda b, h: feasible_tiles_bwd(b, h, 4 * h, 2)),
}


@pytest.mark.parametrize("picker", sorted(_PICKERS))
@pytest.mark.parametrize("batch,hidden", _SHAPES)
def test_tiles_follow_from_shapes(batch, hidden, picker, monkeypatch):
    """A tile is a function of the shapes alone: it is feasible, it is the
    same on a second call, and nothing in the environment moves it (the
    variables below were a hand-off between processes until PR 28; a cell
    runs with none set, so a tile that arrived that way could not be
    measured)."""
    pick, feasible = _PICKERS[picker]
    monkeypatch.delenv("CI_TPU_LSTM_FWD_TILES", raising=False)
    monkeypatch.delenv("CI_TPU_LSTM_BWD_TILES", raising=False)
    base = pick(batch, hidden)
    cands = feasible(batch, hidden)
    assert len(cands) > 1 and base in cands
    _, padded, _ = _sublane_snap(batch, 2)
    assert padded % base[0] == 0  # an exact grid over the padded batch
    assert pick(batch, hidden) == base
    other = next(c for c in cands if c != base)
    for var in ("CI_TPU_LSTM_FWD_TILES", "CI_TPU_LSTM_BWD_TILES"):
        monkeypatch.setenv(var, f"{batch},{hidden},{other[0]},{other[1]}")
    assert pick(batch, hidden) == base


# One sweep on the chip (v5e, PR 31, B104 x T67 bf16; PERF.md §6 has the
# table): the train kernels take the largest batch tile and the smallest
# time chunk; the inference kernel's pick is the one it had.
@pytest.mark.parametrize("hidden,picker,winner", [
    (2500, "fwd_gates", (112, 1)), (2500, "bwd", (112, 1)),
    (800, "fwd_gates", (112, 1)), (800, "bwd", (112, 1)),
    (2500, "fwd", (56, 4)), (800, "fwd", (112, 4))])
def test_the_pickers_return_the_sweeps_winners(hidden, picker, winner):
    assert _PICKERS[picker][0](104, hidden) == winner


@pytest.mark.parametrize("picker", sorted(_PICKERS))
@pytest.mark.parametrize("hidden", [2500, 800])
@pytest.mark.parametrize("batch", [32, 200])
def test_the_serve_batches_get_a_feasible_tile(batch, hidden, picker):
    pick, feasible = _PICKERS[picker]
    assert pick(batch, hidden) in feasible(batch, hidden)


@pytest.mark.parametrize("batch,hidden,tile", [
    (32, 2500, (32, 4)), (200, 2500, (104, 2)), (100, 2500, (56, 4)),
    (25, 2500, (32, 4)), (32, 800, (32, 4)), (200, 800, (104, 4))])
def test_the_inference_tiles_are_the_ones_they_were(batch, hidden, tile):
    # the wider stream budget and the sweep's rule are the train kernels'
    # alone: what a serve program compiles did not move with this PR
    assert _pick_tiles(batch, hidden, 4 * hidden, False, 2) == tile


def test_no_feasible_tile_falls_back_to_the_smallest_batch_tile():
    # float32 W_hh at H=2500 is 100 MB: nothing is feasible, and the
    # documented fallback is the smallest batch tile, one timestep
    assert feasible_tiles(104, 2500, 10000, True, 4) == []
    assert _pick_tiles(104, 2500, 10000, True, 4) == (8, 1)
    assert _pick_tiles_bwd(104, 2500, 10000, 4) == (8, 1)
