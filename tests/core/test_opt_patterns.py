"""Kernel-pattern rules of the fusion tier (``optimize(..., patterns=True)``):
rmsnorm and the softmax-attention core are recognized in user graphs and
rewritten to the hand-written Pallas primitives from ``repro.kernels.ops``.
Off by default — the plain pipeline must be unaffected."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import P
from repro.core import api as myia


def _prims(fn, *args):
    g = fn.optimized_graph(*args)
    return [n.fn.value.name for n in g.nodes() if n.is_apply]


def rms(x, w):
    ms = P.reduce_sum(x * x, (1,), True) / 8.0
    return x * P.rsqrt(ms + 1e-6) * w


def rms_commuted(x, w):
    ms = P.reduce_sum(x * x, (1,), True) / 8.0
    return w * (P.rsqrt(ms + 1e-6) * x)


def attn(q, k, v):
    s = (q @ P.mT(k)) * 0.35355339059327373  # 1/sqrt(8)
    m = P.reduce_max(s, (3,), True)
    e = P.exp(s - m)
    z = P.reduce_sum(e, (3,), True)
    return (e / z) @ v


@pytest.fixture
def xw():
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 8))
    w = jnp.asarray(np.linspace(0.5, 1.5, 8), jnp.float32)
    return x, w


@pytest.fixture
def qkv():
    keys = [jax.random.PRNGKey(i) for i in range(3)]
    return tuple(jax.random.normal(k, (2, 4, 16, 8)) for k in keys)


class TestRmsnormPattern:
    def test_rewrites_to_kernel_prim(self, xw):
        f = myia.myia(rms, options=myia.CompileOptions(patterns=True))
        assert _prims(f, *xw) == ["rmsnorm"]

    def test_commuted_spelling_matches(self, xw):
        f = myia.myia(rms_commuted, options=myia.CompileOptions(patterns=True))
        assert _prims(f, *xw) == ["rmsnorm"]

    def test_numerics_match_reference(self, xw):
        x, w = xw
        r_pat = myia.myia(rms, options=myia.CompileOptions(patterns=True))(x, w)
        r_ref = myia.myia(rms)(x, w)
        np.testing.assert_allclose(
            np.asarray(r_pat), np.asarray(r_ref), rtol=1e-5, atol=1e-6
        )

    def test_off_by_default(self, xw):
        assert "rmsnorm" not in _prims(myia.myia(rms), *xw)

    def test_grad_through_pattern(self, xw):
        """Pattern rewrites inside an adjoint keep gradients correct (the
        kernel prim carries its own backpropagator)."""
        x, w = xw

        def loss(x, w):
            ms = P.reduce_sum(x * x, (1,), True) / 8.0
            return P.reduce_sum(x * P.rsqrt(ms + 1e-6) * w, (0, 1), False)

        g_ref = myia.grad(loss, (0, 1))(x, w)
        g_pat = myia.grad(loss, (0, 1), options=myia.CompileOptions(patterns=True))(x, w)
        for u, v in zip(g_ref, g_pat):
            np.testing.assert_allclose(
                np.asarray(u), np.asarray(v), rtol=1e-5, atol=1e-6
            )

    def test_wrong_divisor_does_not_fire(self, xw):
        """mean divided by the wrong constant is NOT rmsnorm."""

        def not_rms(x, w):
            ms = P.reduce_sum(x * x, (1,), True) / 4.0  # D is 8
            return x * P.rsqrt(ms + 1e-6) * w

        f = myia.myia(not_rms, options=myia.CompileOptions(patterns=True))
        assert "rmsnorm" not in _prims(f, *xw)


class TestAttentionPattern:
    def test_rewrites_to_flash_attention(self, qkv):
        f = myia.myia(attn, options=myia.CompileOptions(patterns=True))
        assert _prims(f, *qkv) == ["flash_attention"]

    def test_numerics_match_reference(self, qkv):
        r_pat = myia.myia(attn, options=myia.CompileOptions(patterns=True))(*qkv)
        r_ref = myia.myia(attn)(*qkv)
        np.testing.assert_allclose(
            np.asarray(r_pat), np.asarray(r_ref), rtol=2e-5, atol=2e-6
        )

    def test_rank_gate(self):
        """2-D operands (no batch/heads) must not fire — the kernel's
        layout is (B, H, S, D)."""

        def attn2d(q, k, v):
            s = q @ P.mT(k)
            m = P.reduce_max(s, (1,), True)
            e = P.exp(s - m)
            z = P.reduce_sum(e, (1,), True)
            return (e / z) @ v

        args = tuple(jax.random.normal(jax.random.PRNGKey(i), (16, 8)) for i in range(3))
        f = myia.myia(attn2d, options=myia.CompileOptions(patterns=True))
        assert "flash_attention" not in _prims(f, *args)

    def test_off_by_default(self, qkv):
        assert "flash_attention" not in _prims(myia.myia(attn), *qkv)
