"""OO-vs-ST differential corpus (paper §2.1.1 vs §3.2).

The operator-overloading tape (``repro.core.oo_tape``) and the ST
pipeline (``repro.core.api.grad``) implement the same math through
opposite mechanisms — runtime tracing vs ahead-of-time transformation.
On array workloads both execute the *same* jnp primitives in the same
dataflow, so their gradients must agree **bitwise**; scalar workloads
differ only in scalar representation (python float64 arithmetic on the
tape vs f32 arrays through the jax backend), so those assert tight
allclose in float64.
"""

import jax
import numpy as np
import pytest

from repro.core import api as myia
from repro.core import oo_tape as oo
from repro.core.primitives import reduce_sum as _sum
from repro.core.primitives import tanh as _tanh


def scalar_chain(x, y):
    """The paper's footnote-1 pathology: an unrolled scalar recurrence."""
    z = x
    z = z * y + x
    z = z * z + y
    z = z * y + x
    z = z * z + y
    z = z * y + x
    z = z * z + y
    return z


def poly(x):
    return 2.0 * x * x * x + 4.0 * x * x + x + 1.0


def cube(x):
    return x * x * x


def _mlp_pair(depth2=False):
    def oo_loss(w1, w2, x):
        h = oo.tanh(x @ w1)
        return oo.reduce_sum(oo.tanh(h @ w2))

    def st_loss(w1, w2, x):
        h = _tanh(x @ w1)
        return _sum(_tanh(h @ w2), (0, 1), False)

    return oo_loss, st_loss


def _relu_pair():
    from repro.core.primitives import relu as _relu

    def oo_loss(w, x):
        return oo.reduce_sum(oo.relu(x @ w))

    def st_loss(w, x):
        return _sum(_relu(x @ w), (0, 1), False)

    return oo_loss, st_loss


def _arrays(*shapes, seed=0):
    return tuple(
        jax.random.normal(jax.random.PRNGKey(seed + i), s) for i, s in enumerate(shapes)
    )


class TestScalarWorkloads:
    """Python-scalar programs: the tape computes in float64, the jax
    backend in f32 — agreement is tight allclose, not bitwise."""

    @pytest.mark.parametrize("args", [(0.3, 0.7), (1.5, -0.2), (-0.9, 0.1)])
    def test_scalar_chain_grads(self, args):
        oo_g = oo.oo_grad(scalar_chain, wrt=(0, 1))(*args)
        st_g = myia.grad(scalar_chain, wrt=(0, 1))(*args)
        np.testing.assert_allclose(
            np.asarray(oo_g, dtype=np.float64),
            np.asarray(st_g, dtype=np.float64),
            rtol=1e-5,
        )

    @pytest.mark.parametrize("fn,x", [(poly, 1.3), (poly, -0.4), (cube, 2.0)])
    def test_polynomials(self, fn, x):
        oo_g = oo.oo_grad(fn)(x)
        st_g = myia.grad(fn)(x)
        np.testing.assert_allclose(float(oo_g), float(st_g), rtol=1e-5)

    def test_cube_vm_backend_bit_match(self):
        """On the VM backend nothing ever leaves python floats, so the
        multiplicative chain matches the tape bit for bit."""
        st = myia.grad(cube, options=myia.CompileOptions(backend="vm"))
        assert float(oo.oo_grad(cube)(1.3)) == float(st(1.3))

    def test_value_and_grad_value_agrees(self):
        ov, og = oo.oo_value_and_grad(scalar_chain, wrt=0)(0.3, 0.7)
        sv, sg = myia.value_and_grad(scalar_chain, wrt=0)(0.3, 0.7)
        np.testing.assert_allclose(float(ov), float(sv), rtol=1e-6)
        np.testing.assert_allclose(float(og), float(sg), rtol=1e-5)


class TestArrayWorkloads:
    """Array programs execute identical jnp primitives in both systems:
    gradients must be BIT-identical."""

    def test_mlp_grads_bitwise(self):
        oo_loss, st_loss = _mlp_pair()
        w1, w2, x = _arrays((8, 8), (8, 8), (4, 8))
        oo_g = oo.oo_grad(oo_loss, wrt=(0, 1))(w1, w2, x)
        st_g = myia.grad(st_loss, wrt=(0, 1))(w1, w2, x)
        assert len(oo_g) == len(st_g) == 2
        for u, v in zip(oo_g, st_g):
            np.testing.assert_array_equal(np.asarray(u), np.asarray(v))

    def test_mlp_grad_wrt_input_bitwise(self):
        oo_loss, st_loss = _mlp_pair()
        w1, w2, x = _arrays((6, 6), (6, 6), (3, 6), seed=5)
        u = oo.oo_grad(oo_loss, wrt=2)(w1, w2, x)
        v = myia.grad(st_loss, wrt=2)(w1, w2, x)
        np.testing.assert_array_equal(np.asarray(u), np.asarray(v))

    def test_relu_grads_bitwise(self):
        oo_loss, st_loss = _relu_pair()
        w, x = _arrays((8, 4), (5, 8), seed=9)
        u = oo.oo_grad(oo_loss, wrt=0)(w, x)
        v = myia.grad(st_loss, wrt=0)(w, x)
        np.testing.assert_array_equal(np.asarray(u), np.asarray(v))

    def test_value_and_grad(self):
        oo_loss, st_loss = _mlp_pair()
        w1, w2, x = _arrays((8, 8), (8, 8), (4, 8), seed=3)
        ov, og = oo.oo_value_and_grad(oo_loss, wrt=(0, 1))(w1, w2, x)
        sv, sg = myia.value_and_grad(st_loss, wrt=(0, 1))(w1, w2, x)
        # the VALUE is a full reduction: eager (tape) and jitted (ST)
        # summation orders differ by an ulp — grads stay bitwise
        np.testing.assert_allclose(float(ov), float(sv), rtol=1e-6)
        for u, v in zip(og, sg):
            np.testing.assert_array_equal(np.asarray(u), np.asarray(v))

    def test_fused_tier_matches_tape(self):
        """The fusion tier must not disturb the OO/ST agreement: tape
        gradients == fused-lowering gradients, still bitwise."""
        oo_loss, st_loss = _mlp_pair()
        w1, w2, x = _arrays((8, 8), (8, 8), (4, 8), seed=7)
        oo_g = oo.oo_grad(oo_loss, wrt=(0, 1))(w1, w2, x)
        st_g = myia.grad(st_loss, wrt=(0, 1), options=myia.CompileOptions(fuse=True))(w1, w2, x)
        for u, v in zip(oo_g, st_g):
            np.testing.assert_array_equal(np.asarray(u), np.asarray(v))
