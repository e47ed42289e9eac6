"""Optimizer × recursion regressions: inlining must terminate (no cycle
peeling) and stay semantics-preserving; partial evaluation must not fold
frame-sensitive closure values (the 0.0-gradient bug)."""

import pytest

from repro.core import api as myia


def power_rec(x, n):
    if n == 0:
        return 1.0
    return x * power_rec(x, n - 1)


def use_recursion(x):
    return power_rec(x, 5)


class TestRecursionOptimization:
    def test_value_all_backends(self):
        assert myia.myia(use_recursion, options=myia.CompileOptions(backend="vm"))(2.0) == 32.0
        assert myia.myia(use_recursion, options=myia.CompileOptions(backend="jax"))(2.0) == 32.0

    @pytest.mark.parametrize("opt", [False, True])
    @pytest.mark.parametrize("backend", ["vm", "jax"])
    def test_grad_correct_with_and_without_opt(self, opt, backend):
        """d/dx x^5 at 2 = 80 — the optimizer must preserve it (this
        caught both the inline cycle-peeling hang and the unsound
        partial evaluation of frame-sensitive closure values)."""
        g = myia.grad(use_recursion, options=myia.CompileOptions(backend=backend, opt=opt))
        assert float(g(2.0)) == pytest.approx(80.0)

    def test_inline_pass_terminates_fast(self):
        """Compile-time guard: the whole pipeline on grad-of-recursion
        must finish in seconds, not unroll the cycle."""
        import time

        t0 = time.monotonic()
        myia.grad(use_recursion)(3.0)
        assert time.monotonic() - t0 < 60

    def test_mutual_recursion_grad(self):
        def even_weight(x, k):
            if k == 0:
                return x
            return odd_weight(x, k - 1) * 2.0

        def odd_weight(x, k):
            if k == 0:
                return x * x
            return even_weight(x, k - 1) + x

        def f(x):
            return even_weight(x, 3)

        # f(x) = odd(x,2)·2 = (even(x,1)+x)·2 = ((x·x)·2+x)·2 = 4x²+2x
        g = myia.grad(f)
        assert float(g(3.0)) == pytest.approx(8 * 3.0 + 2.0)
