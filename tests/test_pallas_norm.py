"""Pallas norm kernels vs the jnp reference (interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.normalization import fused_layer_norm_affine, fused_rms_norm_affine
from apex_tpu.ops import pallas_norm


class TestPallasNorm:
    def test_layer_norm_matches_reference(self):
        x = jnp.asarray(np.random.RandomState(0).randn(64, 128), jnp.float32)
        w = jnp.asarray(np.random.RandomState(1).randn(128) + 1, jnp.float32)
        b = jnp.asarray(np.random.RandomState(2).randn(128), jnp.float32)
        got = pallas_norm.pallas_layer_norm(x, w, b, interpret=True)
        want = fused_layer_norm_affine(x, w, b, (128,))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_rms_norm_matches_reference(self):
        x = jnp.asarray(np.random.RandomState(3).randn(32, 256), jnp.float32)
        w = jnp.asarray(np.random.RandomState(4).randn(256) + 1, jnp.float32)
        got = pallas_norm.pallas_rms_norm(x, w, interpret=True)
        want = fused_rms_norm_affine(x, w, (256,))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_3d_input(self):
        x = jnp.asarray(np.random.RandomState(5).randn(2, 8, 128), jnp.float32)
        w = jnp.ones(128)
        b = jnp.zeros(128)
        got = pallas_norm.pallas_layer_norm(x, w, b, interpret=True)
        want = fused_layer_norm_affine(x, w, b, (128,))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_availability_gate(self):
        assert pallas_norm.is_available(128)
        assert not pallas_norm.is_available(100)

    def test_layer_norm_grad(self):
        """Pallas norms must be differentiable (custom_vjp to analytic bwd)."""
        x = jnp.asarray(np.random.RandomState(7).randn(16, 128), jnp.float32)
        w = jnp.ones(128)
        b = jnp.zeros(128)
        dx = jax.grad(
            lambda x_: jnp.sum(
                pallas_norm.pallas_layer_norm(x_, w, b, interpret=True) ** 2
            )
        )(x)
        want = jax.grad(
            lambda x_: jnp.sum(fused_layer_norm_affine(x_, w, b, (128,)) ** 2)
        )(x)
        np.testing.assert_allclose(np.asarray(dx), np.asarray(want),
                                   rtol=1e-4, atol=1e-5)

    def test_rms_norm_grad(self):
        x = jnp.asarray(np.random.RandomState(8).randn(16, 128), jnp.float32)
        w = jnp.ones(128) * 1.3
        dx, dw = jax.grad(
            lambda x_, w_: jnp.sum(
                pallas_norm.pallas_rms_norm(x_, w_, interpret=True) ** 2
            ),
            argnums=(0, 1),
        )(x, w)
        wantx, wantw = jax.grad(
            lambda x_, w_: jnp.sum(fused_rms_norm_affine(x_, w_, (128,)) ** 2),
            argnums=(0, 1),
        )(x, w)
        np.testing.assert_allclose(np.asarray(dx), np.asarray(wantx),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(dw), np.asarray(wantw),
                                   rtol=1e-4, atol=1e-5)

    def test_ragged_rows(self):
        """rows not divisible by block_rows exercises the grid remainder."""
        x = jnp.asarray(np.random.RandomState(6).randn(70, 128), jnp.float32)
        w = jnp.ones(128)
        b = jnp.zeros(128)
        got = pallas_norm.pallas_layer_norm(x, w, b, block_rows=64, interpret=True)
        want = fused_layer_norm_affine(x, w, b, (128,))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
