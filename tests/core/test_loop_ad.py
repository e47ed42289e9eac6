"""Loop-AD differential corpus: tape-free reverse mode of structured loops.

The loop-adjoint tier differentiates ``while_loop`` / ``scan_loop``
primitives directly (reversed scan over saved-carry stacks; trip-counted,
checkpointed backward while), so grad-of-loop programs compile VM-free.
Every adjoint here is checked three ways:

* **bit-identical** under jit to the VM tracing the same optimized graph
  (identical op sequence → identical executable),
* **allclose** to a ``jax.grad`` oracle — the loops statically unrolled
  (jax cannot reverse-differentiate a dynamic-bound while, which is
  exactly the gap this tier fills; the unrolled program is the semantic
  ground truth at the pinned trip counts),
* **VM-free**: ``analyze_blockers`` empty after the pipeline.

Plus: grad-of-grad of while and scan, the ``checkpoint_policy`` ladder,
the CompileOptions surface of every entry point (same structural hash), a
2×1 SPMD smoke of a loop adjoint, and an AOT warm restart of grad-of-scan
with ``xla_compiles == 0`` across a process boundary (subprocess, slow).
"""

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import build_grad_graph, parse_function
from repro.core.ad import build_value_and_grad_graph
from repro.core.api import (
    CompileOptions,
    MyiaFunction,
    compile_pipeline,
    grad,
    myia,
    value_and_grad,
    vjp,
)
from repro.core.closure import analyze_blockers
from repro.core.infer import abstract_of_value
from repro.core.lowering import lower_graph, lowering_blockers
from repro.core.primitives import reduce_sum as _rsum, tanh as _tanh
from repro.core.serialize import structural_hash

_SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..", "src"))


# -- corpus: parsed loop programs + statically-unrolled jax oracles ----------
# Each oracle is a single-argument closure (grad always wrt arg 0) with the
# trip count baked in, so jax.grad can differentiate it by unrolling.


def p_while_pow(x, n):
    i = 0
    acc = x
    while i < n:
        acc = acc * x
        i = i + 1
    return acc


def p_scan_fold(x):
    s = 0.0
    for i in range(10):
        s = s + x * x
    return s


def p_nested(x, n):
    i = 0
    s = 0.0
    while i < n:
        j = 0
        while j < i:
            s = s + x
            j = j + 1
        i = i + 1
    return s


def p_fold_rec(x, n):
    if n == 0:
        return 1.0
    return x * p_fold_rec(x, n - 1)


def p_scan_mlp(w, x):
    h = x
    for i in range(4):
        h = _tanh(h @ w)
    return _rsum(h, None, False)


_X = jnp.asarray(1.3, jnp.float32)
_N = jnp.asarray(4)
_W = jnp.ones((4, 4), jnp.float32) * 0.3
_XM = jnp.ones((2, 4), jnp.float32) * 0.7


def o_while_pow(x):  # x * x^4 = x^5
    acc = x
    for _ in range(4):
        acc = acc * x
    return acc


def o_scan_fold(x):  # 10 x^2
    s = jnp.float32(0.0)
    for _ in range(10):
        s = s + x * x
    return s


def o_nested(x):  # (0+1+2+3)·x = 6x
    s = jnp.float32(0.0)
    for i in range(4):
        for _ in range(i):
            s = s + x
    return s


def o_fold_rec(x):  # x^5
    acc = jnp.float32(1.0)
    for _ in range(5):
        acc = acc * x
    return acc


def o_scan_mlp(w):
    h = _XM
    for _ in range(4):
        h = jnp.tanh(h @ w)
    return jnp.sum(h)


#: name -> (parsed program, args, unrolled single-arg oracle)
CORPUS = {
    "while_pow": (p_while_pow, (_X, _N), o_while_pow),
    "scan_fold": (p_scan_fold, (_X,), o_scan_fold),
    "nested": (p_nested, (_X, _N), o_nested),
    "fold_rec": (p_fold_rec, (_X, jnp.asarray(5)), o_fold_rec),
    "scan_mlp": (p_scan_mlp, (_W, _XM), o_scan_mlp),
}


def _pipeline(g, args):
    return compile_pipeline(g, tuple(abstract_of_value(a) for a in args))


def _grad_graph(fn, args, **kw):
    return build_grad_graph(parse_function(fn), 0, example_args=args, **kw)


@pytest.mark.parametrize("name", list(CORPUS))
class TestLoopAdjoints:
    def test_grad_lowers_vm_free(self, name):
        fn, args, _oracle = CORPUS[name]
        og = _pipeline(_grad_graph(fn, args), args)
        assert lowering_blockers(og) == []
        assert analyze_blockers(og) == []

    def test_grad_differential(self, name):
        from repro.core.jax_backend import trace_graph

        fn, args, oracle = CORPUS[name]
        og = _pipeline(_grad_graph(fn, args), args)
        got = jax.jit(lower_graph(og))(*args)
        # bit-identical: the VM tracing the SAME optimized graph under jit
        vm_same = jax.jit(trace_graph(og))(*args)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(vm_same))
        # allclose: jax.grad of the statically-unrolled program
        want = jax.grad(oracle)(args[0])
        np.testing.assert_allclose(
            np.asarray(got, np.float64),
            np.asarray(want, np.float64),
            rtol=1e-5,
            atol=1e-7,
        )

    def test_value_and_grad_matches(self, name):
        fn, args, oracle = CORPUS[name]
        g = build_value_and_grad_graph(parse_function(fn), 0, example_args=args)
        og = _pipeline(g, args)
        assert lowering_blockers(og) == []
        v, dv = jax.jit(lower_graph(og))(*args)
        wv, wd = jax.value_and_grad(oracle)(args[0])
        np.testing.assert_allclose(float(v), float(wv), rtol=1e-5)
        np.testing.assert_allclose(
            np.asarray(dv, np.float64),
            np.asarray(wd, np.float64),
            rtol=1e-5,
            atol=1e-7,
        )


class TestGradOfGrad:
    def test_grad2_of_scan(self):
        # d²/dx² of 10x² ≡ 20
        g1 = _grad_graph(p_scan_fold, (_X,))
        g2 = build_grad_graph(g1, 0, example_args=(_X,))
        og = _pipeline(g2, (_X,))
        assert analyze_blockers(og) == []
        got = jax.jit(lower_graph(og))(_X)
        assert float(got) == pytest.approx(20.0, rel=1e-5)

    def test_grad2_of_while(self):
        # f = x^5 → f'' = 20 x^3 (reverse-over-reverse of a dynamic while:
        # the stage-2 adjoint differentiates the stage-1 backward loop,
        # including its checkpoint-replay inner while)
        g1 = _grad_graph(p_while_pow, (_X, _N))
        g2 = build_grad_graph(g1, 0, example_args=(_X, _N))
        og = _pipeline(g2, (_X, _N))
        assert analyze_blockers(og) == []
        got = jax.jit(lower_graph(og))(_X, _N)
        want = jax.grad(jax.grad(o_while_pow))(_X)
        assert float(got) == pytest.approx(float(want), rel=1e-5)


class TestCheckpointPolicy:
    @pytest.mark.parametrize("policy", ["auto", "save_all", "recompute"])
    def test_policies_agree(self, policy):
        og = _pipeline(
            _grad_graph(p_while_pow, (_X, _N), checkpoint_policy=policy),
            (_X, _N),
        )
        assert analyze_blockers(og) == []
        got = jax.jit(lower_graph(og))(_X, _N)
        want = jax.grad(o_while_pow)(_X)
        np.testing.assert_allclose(float(got), float(want), rtol=1e-5)

    def test_long_horizon_exceeds_slot_budget(self):
        # trip count 300 > the auto slot budget (128): segmented
        # recomputation from sparse checkpoints must still be exact.
        # f = x^301 → f' = 301 x^300.
        n = jnp.asarray(300)
        x = jnp.asarray(1.001, jnp.float32)
        og = _pipeline(
            _grad_graph(p_while_pow, (x, n), checkpoint_policy="auto"), (x, n)
        )
        got = jax.jit(lower_graph(og))(x, n)
        want = 301.0 * 1.001**300
        np.testing.assert_allclose(float(got), want, rtol=1e-4)


# -- CompileOptions parity ---------------------------------------------------

ENTRY_POINTS = {
    "MyiaFunction": lambda fn, **kw: MyiaFunction(fn, **kw),
    "myia": lambda fn, **kw: myia(fn, **kw),
    "grad": lambda fn, **kw: grad(fn, 0, **kw),
    "value_and_grad": lambda fn, **kw: value_and_grad(fn, 0, **kw),
    "vjp": lambda fn, **kw: vjp(fn, **kw),
}


@pytest.mark.parametrize("entry", list(ENTRY_POINTS))
class TestCompileOptionsParity:
    def test_options_is_the_only_spelling(self, entry):
        """``options`` is the one way to configure a compile: omitted, it
        is ``CompileOptions()``; given, it arrives whole and two functions
        built from the same object compile the identical artifact (equal
        structural hashes); a former per-tier keyword is a TypeError, so a
        stale caller fails instead of being silently ignored."""
        make = ENTRY_POINTS[entry]
        assert make(p_scan_fold).options == CompileOptions()
        opts = CompileOptions(opt=True, fuse=False, patterns=False)
        f1 = make(p_scan_fold, options=opts)
        f2 = make(p_scan_fold, options=opts)
        assert f1.options is opts
        args = (_X,) if entry != "vjp" else (_X, jnp.asarray(1.0, jnp.float32))
        h1 = structural_hash(f1.optimized_graph(*args))
        h2 = structural_hash(f2.optimized_graph(*args))
        assert h1 == h2
        np.testing.assert_array_equal(np.asarray(f1(*args)), np.asarray(f2(*args)))
        with pytest.raises(TypeError):
            make(p_scan_fold, fuse=True)

    def test_full_tier_set_accepted(self, entry):
        """Every entry point takes the full tier set (grad/value_and_grad
        used to silently drop program_cache/trace; vjp dropped in_specs)."""
        make = ENTRY_POINTS[entry]
        opts = CompileOptions(
            in_specs=(None,),
            program_cache=None,
            trace=None,
            checkpoint_policy="save_all",
        )
        f = make(p_scan_fold, options=opts)
        assert f.options is opts
        assert f.options.in_specs == (None,)

    def test_mixing_spellings_rejected(self, entry):
        """A per-tier keyword beside ``options=`` is no second spelling:
        the call fails naming the keyword."""
        make = ENTRY_POINTS[entry]
        with pytest.raises(TypeError, match="fuse"):
            make(p_scan_fold, options=CompileOptions(), fuse=True)


class TestLazyEntryPoints:
    def test_grad_of_loop_through_entry_point(self):
        """The public ``grad`` defers the transform for loop primals (the
        primal pipelines — loops lower — before J), so the compiled runner
        is the lowered tier, not the VM."""
        gl = grad(p_while_pow)
        assert gl.transforms == (("grad", 0),)
        got = gl(_X, _N)
        want = jax.grad(o_while_pow)(_X)
        np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
        assert gl.specialize((_X, _N)).lowered is True

    def test_chained_grad_entry_point(self):
        gg = grad(grad(p_scan_fold))
        assert gg.transforms == (("grad", 0), ("grad", 0))
        assert float(gg(_X)) == pytest.approx(20.0, rel=1e-5)

    def test_checkpoint_policy_reaches_adjoint(self):
        got = grad(
            p_while_pow, options=CompileOptions(checkpoint_policy="recompute")
        )(_X, _N)
        want = jax.grad(o_while_pow)(_X)
        np.testing.assert_allclose(float(got), float(want), rtol=1e-5)

    def test_vjp_of_loop(self):
        """vjp pulls a cotangent back through a scan adjoint."""
        f = vjp(p_scan_fold)
        ct = jnp.asarray(2.0, jnp.float32)
        (dx,) = jax.tree.leaves(f(_X, ct))
        np.testing.assert_allclose(float(dx), 2.0 * 20.0 * 1.3, rtol=1e-5)

    def test_straightline_grad_still_eager(self):
        """Straight-line primals keep the eager build: ``.graph`` IS the
        adjoint (back-compat for graph introspection)."""

        def cube(x):
            return x * x * x

        gc = grad(cube)
        assert gc.transforms == ()
        assert gc.graph.name.startswith("grad_")


# -- SPMD smoke --------------------------------------------------------------


@pytest.mark.slow
class TestLoopAdjointSpmd:
    def test_grad_scan_mlp_shards_2x1(self, tmp_path):
        """A loop adjoint runs through the SPMD tier on a 2×1 host-device
        mesh (loop operands gathered/replicated — sound contraction) and
        matches the single-device lowering.  Subprocess: the device count
        flag must be set before jax initializes."""
        script = textwrap.dedent(
            f"""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
            import sys
            sys.path.insert(0, {repr(_SRC)})
            import jax, jax.numpy as jnp, numpy as np
            from repro.core import build_grad_graph, parse_function
            from repro.core.api import compile_pipeline
            from repro.core.infer import abstract_of_value
            from repro.core.jax_backend import compile_graph_spmd
            from repro.core.lowering import lower_graph
            from repro.core.primitives import reduce_sum as _rsum, tanh as _tanh
            from repro.launch.mesh import make_local_mesh

            def scan_mlp(w, x):
                h = x
                for i in range(4):
                    h = _tanh(h @ w)
                return _rsum(h, None, False)

            w = jnp.ones((4, 4), jnp.float32) * 0.3
            x = jnp.ones((2, 4), jnp.float32) * 0.7
            args = (w, x)
            g = build_grad_graph(parse_function(scan_mlp), 0, example_args=args)
            og = compile_pipeline(g, tuple(abstract_of_value(a) for a in args))
            oracle = jax.jit(lower_graph(og))(*args)
            mesh = make_local_mesh(2, 1)
            runner = compile_graph_spmd(og, mesh, (None, ("data",)))
            got = runner(*args)
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(oracle), rtol=2e-6, atol=1e-7
            )
            print("LOOPSPMD OK")
            """
        )
        path = tmp_path / "loop_adjoint_spmd.py"
        path.write_text(script)
        res = subprocess.run(
            [sys.executable, str(path)], capture_output=True, text=True, timeout=600
        )
        assert res.returncode == 0, res.stderr[-4000:]
        assert "LOOPSPMD OK" in res.stdout


# -- AOT warm restart --------------------------------------------------------

_AOT_SCRIPT = textwrap.dedent(
    """
    import json, sys
    sys.path.insert(0, {src!r})
    import jax.numpy as jnp
    from repro.core.api import CompileOptions, grad
    from repro.core.jax_backend import ProgramCache
    from repro.core.primitives import reduce_sum as _rsum, tanh as _tanh

    def scan_mlp(w, x):
        h = x
        for i in range(4):
            h = _tanh(h @ w)
        return _rsum(h, None, False)

    cache = ProgramCache(sys.argv[1])
    gl = grad(scan_mlp, options=CompileOptions(program_cache=cache))
    w = jnp.ones((4, 4), jnp.float32) * 0.3
    x = jnp.ones((2, 4), jnp.float32) * 0.7
    out = gl(w, x)
    runner = gl.specialize((w, x))
    print(json.dumps({{
        "stats": cache.stats.as_dict(),
        "aot": bool(getattr(runner, "aot", False)),
        "sum": float(out.sum()),
    }}))
    """
)


@pytest.mark.slow
def test_loop_adjoint_aot_warm_restart_zero_compiles(tmp_path):
    """Acceptance criterion: a grad-of-scan workload round-trips the AOT
    program cache — the warm process restart answers from disk with
    ``xla_compiles == 0`` and identical numerics."""
    script = tmp_path / "aot_once.py"
    script.write_text(_AOT_SCRIPT.format(src=_SRC))
    cachedir = tmp_path / "cache"
    runs = []
    for _ in range(2):
        res = subprocess.run(
            [sys.executable, str(script), str(cachedir)],
            capture_output=True,
            text=True,
            timeout=600,
        )
        assert res.returncode == 0, res.stderr[-4000:]
        runs.append(json.loads(res.stdout.strip().splitlines()[-1]))
    cold, warm = runs
    assert cold["aot"] and warm["aot"]
    assert cold["stats"]["misses"] > 0 and cold["stats"]["xla_compiles"] > 0
    assert warm["stats"]["misses"] == 0
    assert warm["stats"]["xla_compiles"] == 0
    assert warm["stats"]["hits"] > 0
    assert warm["sum"] == cold["sum"]


def test_tanh_lm_optimized_graph_is_unchanged():
    """The tanh LM's loss+gradient graph, after the optimizer, hashes as it
    did before the loop-adjoint accumulation rule and the shared step
    builder: at a tiny size and at the benchmark's widths."""
    from repro.launch.myia_step import MyiaLMDims, make_myia_train_step

    want = {
        512: "83ccd9e8228d6c90f687dee3e24b86bdcfe8b2f7d976a13cb22ac7d2354a750b",
        92544: "c69f16bbe7c9e2bf086561d19a107f80999ee37714629fe3865ae1417811d06d",
    }
    for V, D, H, B, S in [(512, 64, 256, 4, 32), (92544, 2048, 8192, 16, 256)]:
        vag = make_myia_train_step(MyiaLMDims(V, D, H), B, S, 0.1, fuse=True)[0].vag
        f32, i32 = jnp.float32, jnp.int32
        shapes = [((V, D), f32), ((D, H), f32), ((H, D), f32), ((D, V), f32),
                  ((B, S), i32), ((B, S), i32)]
        g = vag.optimized_graph(*[jax.ShapeDtypeStruct(s, d) for s, d in shapes])
        assert structural_hash(g) == want[V]
