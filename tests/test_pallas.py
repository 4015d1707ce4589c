"""Pallas fused-LSTM kernel: numerics parity with the reference scan
(interpreter mode on the CPU test mesh; the compiled-TPU parity run is
chip_smoke.py phase e) and gradient parity through the recompute VJP."""

import jax
import jax.numpy as jnp
import numpy as np

from dotaclient_tpu.ops.pallas import (
    lstm_sequence_pallas,
    lstm_sequence_reference,
)


def lstm_sequence(*args):
    """The kernel in interpreter mode: this suite runs on the CPU."""
    return lstm_sequence_pallas(*args, True)


def inputs(B=8, T=6, D=32, H=64, seed=0, reset_p=0.2):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.normal(size=s).astype(np.float32) * 0.3)
    return (
        f(B, T, D), f(B, H), f(B, H),
        f(D, 4 * H), f(H, 4 * H), f(4 * H),
        jnp.asarray((rng.random((B, T)) < reset_p).astype(np.float32)),
    )


class TestPallasLSTM:
    def test_forward_parity(self):
        args = inputs()
        hs_r, (hT_r, cT_r) = lstm_sequence_reference(*args)
        hs_p, (hT_p, cT_p) = lstm_sequence(*args)
        np.testing.assert_allclose(np.asarray(hs_r), np.asarray(hs_p),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(hT_r), np.asarray(hT_p),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(cT_r), np.asarray(cT_p),
                                   rtol=1e-5, atol=1e-6)

    def test_resets_cut_state(self):
        """A reset at step t must make steps ≥ t independent of the carry."""
        x, h0, c0, wx, wh, b, _ = inputs(reset_p=0.0)
        resets = jnp.zeros(x.shape[:2], jnp.float32).at[:, 3].set(1.0)
        hs_a, _ = lstm_sequence(x, h0, c0, wx, wh, b, resets)
        hs_b, _ = lstm_sequence(x, 17.0 + h0, c0 - 5.0, wx, wh, b, resets)
        assert not np.allclose(np.asarray(hs_a[:, 0]), np.asarray(hs_b[:, 0]))
        np.testing.assert_allclose(
            np.asarray(hs_a[:, 3:]), np.asarray(hs_b[:, 3:]),
            rtol=1e-5, atol=1e-6,
        )

    def test_gradient_parity(self):
        x, h0, c0, wx, wh, b, resets = inputs(seed=3)

        def loss(fn):
            def inner(wx_, wh_, b_):
                hs, (hT, cT) = fn(x, h0, c0, wx_, wh_, b_, resets)
                return (hs ** 2).sum() + (hT * cT).sum()
            return inner

        g_p = jax.grad(loss(lstm_sequence), argnums=(0, 1, 2))(wx, wh, b)
        g_r = jax.grad(loss(lstm_sequence_reference), argnums=(0, 1, 2))(wx, wh, b)
        for a, r in zip(g_p, g_r):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(r), rtol=1e-4, atol=1e-5
            )
