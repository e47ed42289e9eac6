"""Direct lowering (tentpole of the straight-line execution path):

* every first-order graph in the corpus lowers, and the lowered callable's
  outputs match the VM's bit-for-bit under ``jax.jit``,
* graphs with residual recursion report blockers and demonstrably fall
  back to the VM path,
* the jax backend's tiered runner returns identical results on the tier-0
  first call and the fully-optimized jitted second call."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import P, build_grad_graph, parse_function
from repro.core.api import compile_pipeline
from repro.core import api as myia
from repro.core.infer import abstract_of_value
from repro.core.jax_backend import compile_graph, trace_graph
from repro.core.lowering import (
    LoweringError,
    lower_graph,
    lowering_blockers,
    try_lower,
)


def _cube(x):
    return x**3


def _poly(x):
    return 2.0 * x**3 + 4.0 * x * x + x + 1.0


def _mlp(x, w):
    return P.reduce_sum(P.tanh(x @ w), None, False)


def _two_layer(w1, w2, x):
    h = P.tanh(x @ w1)
    return P.reduce_sum(P.tanh(h @ w2), (0, 1), False)


def power_rec(x, n):
    if n == 0:
        return 1.0
    return x * power_rec(x, n - 1)


def _use_recursion(x):
    return power_rec(x, 5)


def fib_rec(x, n):
    if n <= 1:
        return x
    return fib_rec(x, n - 1) + fib_rec(x, n - 2)


def _use_double_rec(x):
    return fib_rec(x, 5)


_F32 = jax.ShapeDtypeStruct((), jnp.float32)

CORPUS = [
    ("grad_cube", build_grad_graph, _cube, 0, (_F32,)),
    ("grad_poly", build_grad_graph, _poly, 0, (_F32,)),
    (
        "grad_mlp",
        build_grad_graph,
        _mlp,
        1,
        (jnp.ones((3, 4)) * 0.3, jnp.ones((4, 5)) * 0.2),
    ),
    (
        "grad_two_layer",
        build_grad_graph,
        _two_layer,
        0,
        (jnp.ones((8, 8)) * 0.1, jnp.ones((8, 8)) * 0.2, jnp.ones((4, 8))),
    ),
    ("fwd_poly", None, _poly, 0, (_F32,)),
]


def _concrete(a):
    if isinstance(a, jax.ShapeDtypeStruct):
        return jnp.asarray(1.3, a.dtype)
    return a


def _optimized(build, fn, wrt, example):
    g = parse_function(fn)
    if build is not None:
        g = build(g, wrt)
    return compile_pipeline(g, tuple(abstract_of_value(a) for a in example))


@pytest.mark.parametrize("name,build,fn,wrt,example", CORPUS, ids=[c[0] for c in CORPUS])
class TestLoweredMatchesVM:
    def test_bit_for_bit_under_jit(self, name, build, fn, wrt, example):
        g = _optimized(build, fn, wrt, example)
        assert lowering_blockers(g) == []
        lowered = lower_graph(g)
        args = tuple(_concrete(a) for a in example)
        r_low = jax.jit(lowered)(*args)
        r_vm = jax.jit(trace_graph(g))(*args)
        np.testing.assert_array_equal(np.asarray(r_low), np.asarray(r_vm))

    def test_eager_matches_vm(self, name, build, fn, wrt, example):
        g = _optimized(build, fn, wrt, example)
        lowered = lower_graph(g)
        args = tuple(_concrete(a) for a in example)
        np.testing.assert_allclose(
            np.asarray(lowered(*args), dtype=np.float64),
            np.asarray(jax.jit(trace_graph(g))(*args), dtype=np.float64),
            rtol=1e-6,
        )

    def test_source_is_straight_line(self, name, build, fn, wrt, example):
        g = _optimized(build, fn, wrt, example)
        src = lower_graph(g).__lowered_source__
        body = [l for l in src.splitlines()[1:] if l.strip()]
        # one assignment per apply + one return; no control flow, no calls
        # through anything but bound primitives
        assert body[-1].strip().startswith("return ")
        for line in body[:-1]:
            assert "=" in line and ("_prim_" in line)
        assert "for " not in src and "while " not in src and "if " not in src


class TestConstantBinding:
    def test_numpy_scalar_constant_binds_by_name(self):
        """np.float64 is a float subclass but must NOT be emitted as a
        source literal (numpy>=2 reprs as ``np.float64(…)`` → NameError;
        demoting to a Python float would change dtype promotion)."""
        scale = np.float64(1.5)

        def f(x):
            return x * scale

        fn = myia.myia(f)
        x = jnp.ones((2, 2))
        np.testing.assert_allclose(np.asarray(fn(x)), 1.5 * np.ones((2, 2)))
        np.testing.assert_allclose(np.asarray(fn(x)), 1.5 * np.ones((2, 2)))
        runner = fn.specialize((x,))
        assert runner.lowered is True
        g = fn.optimized_graph(x)
        src = lower_graph(g).__lowered_source__
        assert "np.float64" not in src


class TestFallback:
    def test_recursion_reports_blockers(self):
        g = compile_pipeline(
            build_grad_graph(parse_function(_use_recursion), 0),
            (abstract_of_value(_F32),),
        )
        blockers = lowering_blockers(g)
        assert blockers, "residual recursion must block lowering"
        assert any("graph" in b for b in blockers)
        assert try_lower(g) is None
        with pytest.raises(LoweringError):
            lower_graph(g)

    def test_affine_recursion_now_lowers(self):
        # power_rec is single-call affine non-tail recursion: the closure
        # tier rewrites it to count + reversed-accumulator loops, so it no
        # longer needs the VM (it used to be the documented fallback here)
        fn = myia.myia(_use_recursion, options=myia.CompileOptions(backend="jax"))
        assert float(fn(2.0)) == pytest.approx(32.0)
        assert fn.specialize((2.0,)).lowered is True
        gr = myia.grad(_use_recursion)
        assert float(gr(2.0)) == pytest.approx(80.0)
        assert gr.specialize((2.0,)).lowered is True

    def test_jax_backend_falls_back_to_vm(self):
        # a double self-call is beyond the loop rewriter (no single
        # back-edge): still the VM's job, traced under jit
        fn = myia.myia(_use_double_rec, options=myia.CompileOptions(backend="jax"))
        assert float(fn(2.0)) == pytest.approx(16.0)
        runner = fn.specialize((2.0,))
        assert runner.lowered is False
        # and the fallback still computes correct grads
        gr = myia.grad(_use_double_rec)
        assert float(gr(2.0)) == pytest.approx(8.0)
        assert gr.specialize((2.0,)).lowered is False

    def test_compile_graph_flags(self):
        g = _optimized(build_grad_graph, _cube, 0, (_F32,))
        run = compile_graph(g)
        assert run.lowered is True
        assert float(run(jnp.asarray(2.0))) == pytest.approx(12.0)
        g_rec = compile_pipeline(
            build_grad_graph(parse_function(_use_recursion), 0),
            (abstract_of_value(_F32),),
        )
        run_rec = compile_graph(g_rec)
        assert run_rec.lowered is False
        assert float(run_rec(jnp.float32(2.0))) == pytest.approx(80.0)


class TestTieredRunner:
    def test_first_call_tier0_matches_jitted(self):
        fn = myia.myia(_two_layer, options=myia.CompileOptions(backend="jax"))
        w1 = jnp.ones((8, 8)) * 0.1
        w2 = jnp.ones((8, 8)) * 0.2
        x = jnp.ones((4, 8))
        r1 = fn(w1, w2, x)  # tier-0 compiled straight-line
        r2 = fn(w1, w2, x)  # fully optimized jit
        runner = fn.specialize((w1, w2, x))
        assert runner.lowered is True
        np.testing.assert_allclose(
            np.asarray(r1, dtype=np.float64),
            np.asarray(r2, dtype=np.float64),
            rtol=1e-6,
        )
        # later calls keep using the jitted path
        r3 = fn(w1, w2, x)
        np.testing.assert_array_equal(np.asarray(r2), np.asarray(r3))

    def test_vm_backend_untouched(self):
        fn = myia.myia(_poly, options=myia.CompileOptions(backend="vm"))
        assert float(fn(1.5)) == pytest.approx(_poly(1.5))
        assert fn.specialize((1.5,)).lowered is False
