"""Property-based tests on the AD system's invariants (hypothesis):
Myia ST gradients == jax.grad on randomly generated compositions, and
the optimizer never changes values or gradients."""

import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import api as myia
import repro.core.primitives as P

tanh, sigmoid, exp_, relu = P.tanh, P.sigmoid, P.exp, P.relu


def poly3(x, a, b, c):
    return a * x ** 3 + b * x * x + c * x + 1.0


def comp1(x, a, b, c):
    return tanh(a * x) * sigmoid(b * x) + c


def comp2(x, a, b, c):
    return relu(x * a + b) * x + sigmoid(c * x * x)


_FNS = {"poly3": poly3, "comp1": comp1, "comp2": comp2}
_JAX = {
    "poly3": lambda x, a, b, c: a * x**3 + b * x * x + c * x + 1.0,
    "comp1": lambda x, a, b, c: jnp.tanh(a * x) * jax.nn.sigmoid(b * x) + c,
    "comp2": lambda x, a, b, c: jnp.maximum(x * a + b, 0) * x + jax.nn.sigmoid(c * x * x),
}


@settings(max_examples=25, deadline=None)
@given(
    name=st.sampled_from(sorted(_FNS)),
    x=st.floats(-2.0, 2.0),
    a=st.floats(-1.5, 1.5),
    b=st.floats(-1.5, 1.5),
    c=st.floats(-1.5, 1.5),
)
def test_st_grad_matches_jax_grad(name, x, a, b, c):
    if name == "comp2" and abs(x * a + b) < 1e-3:
        return  # relu kink: subgradient choice may differ
    g_myia = myia.grad(_FNS[name], wrt=(0, 1, 2, 3))(x, a, b, c)
    g_jax = jax.grad(_JAX[name], argnums=(0, 1, 2, 3))(
        jnp.float32(x), jnp.float32(a), jnp.float32(b), jnp.float32(c)
    )
    for gm, gj in zip(g_myia, g_jax):
        np.testing.assert_allclose(float(gm), float(gj), rtol=2e-4, atol=2e-5)


@settings(max_examples=15, deadline=None)
@given(
    name=st.sampled_from(sorted(_FNS)),
    x=st.floats(-2.0, 2.0),
    a=st.floats(-1.5, 1.5),
)
def test_optimizer_preserves_value_and_grad(name, x, a):
    fn = _FNS[name]
    v1 = myia.myia(fn, options=myia.CompileOptions(opt=False))(x, a, 0.5, -0.25)
    v2 = myia.myia(fn, options=myia.CompileOptions(opt=True))(x, a, 0.5, -0.25)
    np.testing.assert_allclose(float(v1), float(v2), rtol=1e-5, atol=1e-6)
    g1 = myia.grad(fn, options=myia.CompileOptions(opt=False))(x, a, 0.5, -0.25)
    g2 = myia.grad(fn, options=myia.CompileOptions(opt=True))(x, a, 0.5, -0.25)
    np.testing.assert_allclose(float(g1), float(g2), rtol=1e-5, atol=1e-6)
