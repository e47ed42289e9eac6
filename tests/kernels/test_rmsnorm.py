"""RMSNorm Pallas kernels (fwd + bwd) vs oracle, incl. Myia-primitive AD."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import api as myia_api
from repro.kernels import ref, rmsnorm
from repro.kernels.rmsnorm import rmsnorm_bwd, rmsnorm_fwd


def make(seed, R, D, dtype=jnp.float32):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(k1, (R, D), jnp.float32).astype(dtype)
    w = (1.0 + 0.1 * jax.random.normal(k2, (D,), jnp.float32)).astype(dtype)
    return x, w


class TestForward:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, dtype):
        x, w = make(0, 512, 256, dtype)
        out = rmsnorm_fwd(x, w, block_rows=128, interpret=True)
        exp = ref.rmsnorm_ref(x, w)
        tol = 2e-2 if dtype == jnp.bfloat16 else 1e-6
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(exp, np.float32), rtol=tol, atol=tol
        )

    def test_3d_input(self):
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 32, 128))
        w = jnp.ones((128,))
        np.testing.assert_allclose(
            np.asarray(rmsnorm_fwd(x, w, block_rows=64, interpret=True)),
            np.asarray(ref.rmsnorm_ref(x, w)),
            rtol=1e-6,
            atol=1e-6,
        )

    @settings(max_examples=15, deadline=None)
    @given(
        seed=st.integers(0, 2**16),
        R=st.sampled_from([64, 128, 384, 512]),
        D=st.sampled_from([128, 256, 1024]),
        br=st.sampled_from([32, 64, 128]),
    )
    def test_property_sweep(self, seed, R, D, br):
        x, w = make(seed, R, D)
        out = rmsnorm_fwd(x, w, block_rows=br, interpret=True)
        exp = ref.rmsnorm_ref(x, w)
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp), rtol=1e-5, atol=1e-5)


class TestBackward:
    def test_bwd_kernel_matches_jax_grad(self):
        x, w = make(2, 256, 128)
        dy = jax.random.normal(jax.random.PRNGKey(3), x.shape)
        dx, dw = rmsnorm_bwd(x, w, dy, block_rows=64, interpret=True)
        (ex, ew) = jax.grad(
            lambda x_, w_: jnp.sum(ref.rmsnorm_ref(x_, w_) * dy), argnums=(0, 1)
        )(x, w)
        np.testing.assert_allclose(np.asarray(dx), np.asarray(ex), rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(dw), np.asarray(ew), rtol=1e-4, atol=1e-5)

    def test_custom_vjp_pallas_path(self):
        x, w = make(4, 128, 128)
        g1 = jax.grad(lambda x_: jnp.sum(rmsnorm(x_, w, impl="pallas_interpret") ** 2))(x)
        g2 = jax.grad(lambda x_: jnp.sum(ref.rmsnorm_ref(x_, w) ** 2))(x)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), rtol=1e-4, atol=1e-5)


def _myia_loss(x, w):
    """Myia-subset function calling the rmsnorm kernel *primitive*."""
    y = _rmsnorm_prim(x, w, 1e-6)
    return _reduce_sum(y * y, (0, 1), False)


class TestMyiaPrimitive:
    def test_myia_grad_through_kernel_prim(self):
        """The paper's kernels-as-primitives: Myia ST-AD differentiates a
        function whose body calls the rmsnorm kernel primitive, using its
        hand-written backpropagator."""
        import repro.core.primitives as P
        from repro.kernels.ops import rmsnorm_prim

        global _rmsnorm_prim, _reduce_sum
        _rmsnorm_prim = rmsnorm_prim
        _reduce_sum = P.reduce_sum

        x, w = make(5, 64, 128)
        g = myia_api.grad(_myia_loss, wrt=(0, 1))
        dx, dw = g(x, w)
        ex, ew = jax.grad(
            lambda x_, w_: jnp.sum(ref.rmsnorm_ref(x_, w_) ** 2), argnums=(0, 1)
        )(x, w)
        np.testing.assert_allclose(np.asarray(dx), np.asarray(ex), rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(dw), np.asarray(ew), rtol=1e-4, atol=1e-5)
