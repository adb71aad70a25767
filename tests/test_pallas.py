"""Pallas kernel parity tests (interpret mode on the CPU mesh)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from code_intelligence_tpu.ops import forget_mult
from code_intelligence_tpu.ops.pallas_qrnn import forget_mult_pallas


class TestForgetMultPallas:
    @pytest.mark.parametrize(
        "B,T,H", [(2, 7, 128), (8, 16, 256), (3, 5, 100), (9, 67, 130)]
    )
    def test_matches_associative_scan(self, B, T, H):
        rng = np.random.RandomState(0)
        z = jnp.asarray(rng.randn(B, T, H), jnp.float32)
        f = jax.nn.sigmoid(jnp.asarray(rng.randn(B, T, H), jnp.float32))
        h0 = jnp.asarray(rng.randn(B, H), jnp.float32)
        ref = forget_mult(z, f, h0)
        out = forget_mult_pallas(z, f, h0, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)

    def test_zero_init_default(self):
        rng = np.random.RandomState(1)
        z = jnp.asarray(rng.randn(2, 4, 128), jnp.float32)
        f = jnp.full((2, 4, 128), 0.5, jnp.float32)
        ref = forget_mult(z, f)
        out = forget_mult_pallas(z, f, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-6)

    def test_padding_edges(self):
        # B and H both non-multiples of the tile sizes
        rng = np.random.RandomState(2)
        z = jnp.asarray(rng.randn(5, 3, 70), jnp.float32)
        f = jax.nn.sigmoid(jnp.asarray(rng.randn(5, 3, 70), jnp.float32))
        h0 = jnp.asarray(rng.randn(5, 70), jnp.float32)
        ref = forget_mult(z, f, h0)
        out = forget_mult_pallas(z, f, h0, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)

    def test_bf16_native(self):
        # Round-4 rework: the time-major layout (dynamic index on the
        # LEADING block axis) makes bf16 a first-class kernel dtype — no
        # f32 upcast wrapper. Gate math still runs f32 inside; only the
        # stores are bf16, so tolerance vs the bf16 scan.
        rng = np.random.RandomState(3)
        z = jnp.asarray(rng.randn(4, 6, 128), jnp.bfloat16)
        f = jax.nn.sigmoid(jnp.asarray(rng.randn(4, 6, 128), jnp.bfloat16))
        ref = forget_mult(z, f)
        out = forget_mult_pallas(z, f, interpret=True)
        assert out.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            rtol=2e-2, atol=2e-2)

    def test_time_major_layout_matches(self):
        rng = np.random.RandomState(4)
        z = jnp.asarray(rng.randn(5, 9, 130), jnp.float32)
        f = jax.nn.sigmoid(jnp.asarray(rng.randn(5, 9, 130), jnp.float32))
        h0 = jnp.asarray(rng.randn(5, 130), jnp.float32)
        ref = forget_mult_pallas(z, f, h0, interpret=True)
        tm = forget_mult_pallas(
            z.swapaxes(0, 1), f.swapaxes(0, 1), h0,
            interpret=True, time_major=True)
        np.testing.assert_allclose(
            np.asarray(tm.swapaxes(0, 1)), np.asarray(ref), rtol=1e-6)

    @pytest.mark.parametrize("B,T,H", [(2, 7, 128), (5, 3, 70)])
    def test_gradients_match_associative_scan(self, B, T, H):
        # The fused custom-vjp adjoint (reverse affine recurrence in the
        # same kernel family) vs autodiff through the associative scan:
        # dz, df, dh0 must all agree.
        rng = np.random.RandomState(5)
        z = jnp.asarray(rng.randn(B, T, H), jnp.float32)
        f = jax.nn.sigmoid(jnp.asarray(rng.randn(B, T, H), jnp.float32))
        h0 = jnp.asarray(rng.randn(B, H), jnp.float32)
        w = jnp.asarray(rng.randn(B, T, H), jnp.float32)  # loss weights

        def loss_ref(z, f, h0):
            return (forget_mult(z, f, h0) * w).sum()

        def loss_pl(z, f, h0):
            return (forget_mult_pallas(z, f, h0, interpret=True) * w).sum()

        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(z, f, h0)
        g_pl = jax.grad(loss_pl, argnums=(0, 1, 2))(z, f, h0)
        for a, b in zip(g_pl, g_ref):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)

    def test_qrnn_layer_fused_branch_matches_scan(self):
        # The LAYER-level fused branch (time-major "tbg" einsum, output
        # swapaxes, h[-1] final state, interpret kernels off-TPU) vs the
        # scan branch: forward, final state, and gradients, incl. the
        # window=2 convolution path.
        from code_intelligence_tpu.ops.qrnn import qrnn_layer

        rng = np.random.RandomState(7)
        B, T, In, H = 3, 6, 10, 128
        for window in (1, 2):
            params = {
                "w": jnp.asarray(rng.randn(3 * H, window * In) * 0.2,
                                 jnp.float32),
                "b": jnp.asarray(rng.randn(3 * H) * 0.1, jnp.float32),
            }
            x = jnp.asarray(rng.randn(B, T, In), jnp.float32)
            h0 = jnp.asarray(rng.randn(B, H), jnp.float32)

            out_s, hT_s = qrnn_layer(x, params, h0=h0, window=window)
            out_p, hT_p = qrnn_layer(x, params, h0=h0, window=window,
                                     use_pallas=True)
            np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_s),
                                       rtol=1e-5, atol=1e-5)
            np.testing.assert_allclose(np.asarray(hT_p), np.asarray(hT_s),
                                       rtol=1e-5, atol=1e-5)

            def loss(x, params, use_pallas):
                o, hT = qrnn_layer(x, params, h0=h0, window=window,
                                   use_pallas=use_pallas)
                return (o ** 2).sum() + (hT ** 2).sum()

            gx_s, gp_s = jax.grad(loss, argnums=(0, 1))(x, params, False)
            gx_p, gp_p = jax.grad(loss, argnums=(0, 1))(x, params, True)
            np.testing.assert_allclose(np.asarray(gx_p), np.asarray(gx_s),
                                       rtol=1e-4, atol=1e-4)
            for k in gp_s:
                np.testing.assert_allclose(
                    np.asarray(gp_p[k]), np.asarray(gp_s[k]),
                    rtol=1e-4, atol=1e-4)

    def test_gradient_through_final_state_carry(self):
        # BPTT carry: the next window's loss differentiates through h[:, -1];
        # the cotangent arrives at the kernel through the output sequence.
        rng = np.random.RandomState(6)
        B, T, H = 3, 5, 128
        z = jnp.asarray(rng.randn(B, T, H), jnp.float32)
        f = jax.nn.sigmoid(jnp.asarray(rng.randn(B, T, H), jnp.float32))
        h0 = jnp.asarray(rng.randn(B, H), jnp.float32)

        def loss_ref(z, f, h0):
            h = forget_mult(z, f, h0)
            return (h[:, -1] ** 2).sum()

        def loss_pl(z, f, h0):
            h = forget_mult_pallas(z, f, h0, interpret=True)
            return (h[:, -1] ** 2).sum()

        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(z, f, h0)
        g_pl = jax.grad(loss_pl, argnums=(0, 1, 2))(z, f, h0)
        for a, b in zip(g_pl, g_ref):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)


class TestRaggedForgetMult:
    """Length-aware forget-mult (the ragged slot step's QRNN path):
    dense values on each row's valid prefix, the frozen carry held on
    the dead tail (so ``out[-1]`` is the state after ``min(valid, T)``
    real steps — the ``h_T`` ``qrnn_layer`` reads), finite everywhere."""

    def _inputs(self, B=6, T=9, H=130, seed=21):
        rng = np.random.RandomState(seed)
        z = jnp.asarray(rng.randn(B, T, H), jnp.float32)
        f = jax.nn.sigmoid(jnp.asarray(rng.randn(B, T, H), jnp.float32))
        h0 = jnp.asarray(rng.randn(B, H), jnp.float32)
        return z, f, h0

    def test_valid_prefix_matches_scan_and_carry_frozen(self):
        z, f, h0 = self._inputs()
        valid_np = np.array([0, 1, 4, 9, 6, 3], np.int32)
        ref = np.asarray(forget_mult(z, f, h0))
        out = np.asarray(forget_mult_pallas(
            z, f, h0, interpret=True, valid_lens=jnp.asarray(valid_np)))
        assert np.all(np.isfinite(out))
        for b, v in enumerate(valid_np):
            np.testing.assert_allclose(out[b, :v], ref[b, :v],
                                       rtol=1e-5, atol=1e-5,
                                       err_msg=f"row {b}")
            want_h_t = ref[b, v - 1] if v > 0 else np.asarray(h0)[b]
            np.testing.assert_allclose(out[b, -1], want_h_t,
                                       rtol=1e-5, atol=1e-5,
                                       err_msg=f"h_T row {b}")

    def test_time_major_layout_matches_batch_major(self):
        z, f, h0 = self._inputs(seed=22)
        valid = jnp.asarray(np.array([2, 9, 5, 0, 7, 1], np.int32))
        bm = forget_mult_pallas(z, f, h0, interpret=True, valid_lens=valid)
        tm = forget_mult_pallas(z.swapaxes(0, 1), f.swapaxes(0, 1), h0,
                                interpret=True, time_major=True,
                                valid_lens=valid)
        np.testing.assert_allclose(np.asarray(tm.swapaxes(0, 1)),
                                   np.asarray(bm), rtol=1e-6)

    def test_budget_fallback_runs_dense_scan(self, monkeypatch):
        # over-budget shapes fall back to the associative scan (the
        # dense parity reference); valid_lens is ignored there — the
        # ragged contract only promises the valid prefix + finiteness
        from code_intelligence_tpu.ops import pallas_qrnn as pq

        monkeypatch.setattr(pq, "_STREAM_BUDGET", 1024)
        monkeypatch.setattr(pq, "_warned_budget", False)
        z, f, h0 = self._inputs(B=2, T=9, H=130, seed=23)
        out = forget_mult_pallas(
            z, f, h0, interpret=True,
            valid_lens=jnp.asarray(np.array([3, 9], np.int32)))
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(forget_mult(z, f, h0)),
                                   rtol=1e-6)

    def test_qrnn_layer_threads_valid_lens(self):
        # the fused qrnn_layer branch hands valid_lens to the ragged
        # kernel: valid-prefix outputs and h_T match the scan branch
        from code_intelligence_tpu.ops.qrnn import qrnn_layer

        rng = np.random.RandomState(24)
        B, T, IN, H = 4, 7, 12, 128
        x = jnp.asarray(rng.randn(B, T, IN) * 0.5, jnp.float32)
        params = {
            "w": jnp.asarray(rng.randn(3 * H, IN) * 0.2, jnp.float32),
            "b": jnp.asarray(rng.randn(3 * H) * 0.1, jnp.float32),
        }
        h0 = jnp.asarray(rng.randn(B, H) * 0.1, jnp.float32)
        valid_np = np.array([1, 7, 3, 0], np.int32)
        ref_out, _ = qrnn_layer(x, params, h0=h0)
        out, h_t = qrnn_layer(x, params, h0=h0, use_pallas=True,
                              valid_lens=jnp.asarray(valid_np))
        assert np.all(np.isfinite(np.asarray(out)))
        for b, v in enumerate(valid_np):
            np.testing.assert_allclose(np.asarray(out)[b, :v],
                                       np.asarray(ref_out)[b, :v],
                                       rtol=1e-5, atol=1e-5,
                                       err_msg=f"row {b}")
            if v > 0:
                ref_v, ref_ht = qrnn_layer(x[:, :v], params, h0=h0)
                np.testing.assert_allclose(np.asarray(h_t)[b],
                                           np.asarray(ref_ht)[b],
                                           rtol=1e-5, atol=1e-5,
                                           err_msg=f"h_T row {b}")
            else:
                np.testing.assert_allclose(np.asarray(h_t)[b],
                                           np.asarray(h0)[b], rtol=1e-6)


class TestStreamBudgetFallback:
    def test_pick_block_b_raises_when_nothing_fits(self):
        from code_intelligence_tpu.ops import pallas_qrnn as pq

        # bf16 long-T: even the minimum sublane tile exceeds the budget
        # (silently returning the smallest tile let
        # Mosaic compilation fail downstream)
        t_over = pq._STREAM_BUDGET // (3 * 16 * pq._LANE * 2) + 1
        with pytest.raises(ValueError, match="associative scan"):
            pq._pick_block_b(16, t_over, itemsize=2, n_streams=3)

    def test_forget_mult_pallas_falls_back_to_scan(self, monkeypatch):
        from code_intelligence_tpu.ops import pallas_qrnn as pq

        # shrink the budget so a small shape triggers the fallback
        monkeypatch.setattr(pq, "_STREAM_BUDGET", 1024)
        monkeypatch.setattr(pq, "_warned_budget", False)
        rng = np.random.RandomState(11)
        z = jnp.asarray(rng.randn(2, 9, 130), jnp.float32)
        f = jax.nn.sigmoid(jnp.asarray(rng.randn(2, 9, 130), jnp.float32))
        h0 = jnp.asarray(rng.randn(2, 130), jnp.float32)
        out = forget_mult_pallas(z, f, h0, interpret=True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(forget_mult(z, f, h0)), rtol=1e-6)
        # gradients flow through the scan fallback too
        g = jax.grad(lambda z: forget_mult_pallas(
            z, f, h0, interpret=True).sum())(z)
        assert np.all(np.isfinite(np.asarray(g)))
        # time-major callers (qrnn_layer's fused branch) get the same
        # fallback with the layout handled
        tm = forget_mult_pallas(z.swapaxes(0, 1), f.swapaxes(0, 1), h0,
                                interpret=True, time_major=True)
        np.testing.assert_allclose(np.asarray(tm.swapaxes(0, 1)),
                                   np.asarray(out), rtol=1e-6)

    def test_fits_stream_budget_boundary(self):
        from code_intelligence_tpu.ops import pallas_qrnn as pq

        # f32: min tile 8 sublanes, 6 backward streams
        t_edge = pq._STREAM_BUDGET // (6 * 8 * pq._LANE * 4)
        assert pq.fits_stream_budget(t_edge, 4)
        assert not pq.fits_stream_budget(t_edge + 1, 4)
