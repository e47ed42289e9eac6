"""Fusion subsystem tests (tentpole of the Pallas code path).

* the partitioner produces *legal* clusters (single output, dominated
  inputs, uniform body shape) and ≥3 nodes/cluster on the MLP adjoint,
* fused execution is **bit-identical** to the unfused lowering — under
  ``jax.jit``, in both ``ref`` (jnp oracle) and ``pallas_interpret``
  kernel modes — across the corpus, including ``grad()`` adjoints,
* per-cluster kernels: Pallas-interpret output equals the pure-jnp
  oracle bitwise,
* declines fall back to the per-node jnp path (never lose the graph),
* ``lowering_blockers`` de-duplicates; ``try_lower`` caches per graph.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import P, build_grad_graph, parse_function
from repro.core import api as myia
from repro.core.api import compile_pipeline
from repro.core.fusion import classify, partition_graph
from repro.core.infer import abstract_of_value
from repro.core.ir import toposort
from repro.core.lowering import lower_graph, lowering_blockers, try_lower
from repro.kernels import get_kernel_mode, set_kernel_mode
from repro.kernels.codegen import emit_cluster
from repro.obs.trace import Tracer, tracing


@pytest.fixture(autouse=True)
def _restore_kernel_mode():
    mode = get_kernel_mode()
    yield
    set_kernel_mode(mode)


# --- corpus (mirrors tests/core/test_lowering.py, plus reduce chains) ------


def _cube(x):
    return x**3


def _mlp(x, w):
    return P.reduce_sum(P.tanh(x @ w), None, False)


def _two_layer(w1, w2, x):
    h = P.tanh(x @ w1)
    return P.reduce_sum(P.tanh(h @ w2), (0, 1), False)


def _reduce_chain(x):
    return P.reduce_sum(P.tanh(x) * P.sigmoid(x) + 1.0, (0, 1), False)


def _softplusish(x, w):
    h = x @ w
    return P.reduce_sum(P.log(1.0 + P.exp(h)) * P.sigmoid(h), (0, 1), False)


def _masked_tanh(x, y):
    # gt -> cast -> mul -> add: a map cluster holding a cast, whose dtype
    # operand must stay a closure-bound constant of the kernel
    return P.reduce_sum(P.tanh(x) * P.cast(P.gt(x, y), _F32DT) + y, (0, 1), False)


_F32 = jax.ShapeDtypeStruct((), jnp.float32)
_F32DT = np.dtype("float32")


# --- operands smaller than the body: broadcast per block in the kernel


def _row_keepdims(x, m):
    # the reduce_max adjoint's mask: a (B, S, 1) row max against the body
    return P.cast(P.eq(x, P.unreduce(m, P.shape(x), (2,), True)), _F32DT)


def _row_dropdims(x, m):
    return P.cast(P.eq(x, P.unreduce(m, P.shape(x), (2,), False)), _F32DT)


def _lane_vector(x, b):
    return P.tanh(x) * P.broadcast_to(b, P.shape(x)) + x


def _scalar_input(x, s):
    return P.tanh(x) * s + x


def _middle_axis(x, b):
    return P.tanh(x) * P.broadcast_to(b, P.shape(x)) + x


def _mask_tile(x, m):
    # the SSD's causal mask: a (Q, Q) bool tile under every leading axis
    return P.where(m, P.tanh(x) * 2.0, x)


_BX = jnp.linspace(-2, 2, 4 * 16 * 1024).reshape(4, 16, 1024)  # (R, C) = (64, 1024)
_BODY_BYTES = 64 * 1024 * 4
#: a small SSD-shaped body: (batch, heads, chunks, rows, columns)
_X5 = jnp.linspace(-2, 2, 2 * 2 * 2 * 16 * 1024).reshape(2, 2, 2, 16, 1024)
_BODY5_BYTES = 2 * 2 * 2 * 16 * 1024 * 4
_MASK = jnp.arange(16).reshape(16, 1) >= jnp.arange(1024).reshape(1, 1024)

#: (name, fn, example, bytes_moved at the default budget, at the tiled
#: one) — one full read + the output, plus the operand broadcast in the
#: kernel as the kernel reads it: at its own size, once more for each step
#: of a grid axis it is 1 along that lies outside the innermost one it
#: spans.  A lane is fetched again per row block (8 of them when tiled, 4
#: for bf16), a (B, 1, ..) middle-axis operand once per row block too (2
#: when tiled); a tile under leading axes, or a head-axis broadcast, is
#: read once, since the axes it repeats along vary fastest in the grid; a
#: bool enters as int32
SMALL_OPERANDS = [
    ("row_keepdims", _row_keepdims, (_BX, jnp.max(_BX, 2, keepdims=True)),
     2 * _BODY_BYTES + 64 * 4, 2 * _BODY_BYTES + 64 * 4),
    ("row_dropdims", _row_dropdims, (_BX, jnp.max(_BX, 2)),
     2 * _BODY_BYTES + 64 * 4, 2 * _BODY_BYTES + 64 * 4),
    ("lane_broadcast_to", _lane_vector, (_BX, jnp.linspace(0, 1, 1024)),
     2 * _BODY_BYTES + 1024 * 4, 2 * _BODY_BYTES + 8 * 1024 * 4),
    ("scalar", _scalar_input, (_BX, jnp.asarray(0.5, jnp.float32)),
     2 * _BODY_BYTES + 4, 2 * _BODY_BYTES + 4),
    # a packed scalar is widened to a (1, C) lane, which Mosaic can broadcast
    ("scalar_bf16", _scalar_input,
     (_BX.astype(jnp.bfloat16), jnp.asarray(0.5, jnp.bfloat16)),
     _BODY_BYTES + 1024 * 2, _BODY_BYTES + 4 * 1024 * 2),
    ("middle_axis", _middle_axis, (_BX, jnp.linspace(0, 1, 4096).reshape(4, 1, 1024)),
     2 * _BODY_BYTES + 4096 * 4, 2 * _BODY_BYTES + 2 * 4096 * 4),
    ("tile_leading", _middle_axis, (_X5, jnp.linspace(0, 1, 16 * 1024).reshape(16, 1024)),
     2 * _BODY5_BYTES + 16 * 1024 * 4, 2 * _BODY5_BYTES + 16 * 1024 * 4),
    ("head_axis", _scalar_input,
     (_X5, jnp.linspace(0, 1, 2 * 2 * 16 * 1024).reshape(2, 1, 2, 16, 1024)),
     2 * _BODY5_BYTES + 2 * 2 * 16 * 1024 * 4, 2 * _BODY5_BYTES + 2 * 2 * 16 * 1024 * 4),
    ("second_to_last", _scalar_input,
     (_X5, jnp.linspace(0, 1, 2 * 2 * 2 * 1024).reshape(2, 2, 2, 1, 1024)),
     2 * _BODY5_BYTES + 8 * 1024 * 4, 2 * _BODY5_BYTES + 2 * 8 * 1024 * 4),
    ("bool_mask_tile", _mask_tile, (_X5, _MASK),
     2 * _BODY5_BYTES + 16 * 1024 * 4, 2 * _BODY5_BYTES + 16 * 1024 * 4),
]

CORPUS = [
    ("grad_cube", build_grad_graph, _cube, 0, (_F32,)),
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
        (jnp.ones((8, 8)) * 0.1, jnp.ones((8, 8)) * 0.2, jnp.ones((4, 8)) * 0.7),
    ),
    ("fwd_reduce_chain", None, _reduce_chain, 0, (jnp.linspace(-2, 2, 32).reshape(4, 8),)),
    ("grad_reduce_chain", build_grad_graph, _reduce_chain, 0,
     (jnp.linspace(-2, 2, 32).reshape(4, 8),)),
    (
        "fwd_masked_tanh",
        None,
        _masked_tanh,
        0,
        (jnp.linspace(-2, 2, 32).reshape(4, 8), jnp.full((4, 8), 0.25)),
    ),
    (
        "grad_masked_tanh",
        build_grad_graph,
        _masked_tanh,
        0,
        (jnp.linspace(-2, 2, 32).reshape(4, 8), jnp.full((4, 8), 0.25)),
    ),
    (
        "grad_softplusish",
        build_grad_graph,
        _softplusish,
        1,
        (jnp.linspace(-1, 1, 24).reshape(4, 6), jnp.ones((6, 8)) * 0.3),
    ),
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


def _flat(r):
    return r if isinstance(r, tuple) else (r,)


@pytest.mark.parametrize("name,build,fn,wrt,example", CORPUS, ids=[c[0] for c in CORPUS])
class TestFusedBitIdentical:
    @pytest.mark.parametrize("mode", ["ref", "pallas_interpret"])
    def test_fused_matches_unfused_under_jit(self, name, build, fn, wrt, example, mode):
        g = _optimized(build, fn, wrt, example)
        unfused = lower_graph(g)
        fused = lower_graph(g, fuse=True)
        args = tuple(_concrete(a) for a in example)
        r_unf = jax.jit(unfused)(*args)
        set_kernel_mode(mode)
        r_fus = jax.jit(fused)(*args)
        for u, v in zip(_flat(r_unf), _flat(r_fus)):
            np.testing.assert_array_equal(np.asarray(u), np.asarray(v))

    def test_fused_eager_matches(self, name, build, fn, wrt, example):
        # eager bit-exactness is the ref-oracle contract: pin the mode so
        # the CI kernel-mode matrix (MYIA_KERNEL_MODE=pallas_interpret,
        # where eager interpreter execution differs at ULP level) doesn't
        # change what this test measures
        set_kernel_mode("ref")
        g = _optimized(build, fn, wrt, example)
        args = tuple(_concrete(a) for a in example)
        r_unf = lower_graph(g)(*args)
        r_fus = lower_graph(g, fuse=True)(*args)
        for u, v in zip(_flat(r_unf), _flat(r_fus)):
            np.testing.assert_array_equal(np.asarray(u), np.asarray(v))


class TestPartitioner:
    def test_mlp_adjoint_cluster_density(self):
        """Acceptance: ≥3 average nodes per cluster on the MLP adjoint."""
        g = _optimized(build_grad_graph, _two_layer, (0, 1), CORPUS[2][4])
        plan = partition_graph(g)
        assert plan.clusters, "MLP adjoint must produce fusion clusters"
        assert plan.nodes_per_cluster >= 3.0, plan.stats()
        assert plan.launches_after < plan.launches_before, plan.stats()

    def test_clusters_are_legal(self):
        """Single output: no interior member is used outside its cluster
        (live users only); every input is an ancestor of the root."""
        for name, build, fn, wrt, example in CORPUS:
            g = _optimized(build, fn, wrt, example)
            plan = partition_graph(g)
            live = {n._id for n in toposort(g) if n.is_apply}
            for c in plan.clusters:
                interior = c.members - {c.root._id}
                for n in c.order:
                    if n._id not in interior:
                        continue
                    for user, _ in n.users:
                        if user._id in live:
                            assert user._id in c.members, (name, c, n)
                assert g.return_._id not in interior
                for inp in c.inputs:
                    assert inp._id not in c.members

    def test_uniform_body_shape(self):
        g = _optimized(build_grad_graph, _two_layer, (0, 1), CORPUS[2][4])
        for c in partition_graph(g).clusters:
            for n in c.order:
                if classify(n) == "reduction":
                    continue  # root: output lives at the reduced shape
                assert n.abstract.shape == c.body_shape

    def test_classifier(self):
        g = _optimized(None, _reduce_chain, 0, (jnp.ones((4, 8)),))
        kinds = {}
        for n in toposort(g):
            if n.is_apply:
                kinds.setdefault(classify(n), []).append(n.fn.value.name)
        assert "tanh" in kinds["elementwise"]
        assert "reduce_sum" in kinds["reduction"]
        # scalar-only programs never partition into clusters (rank-0 body)
        gs = _optimized(build_grad_graph, _cube, 0, (_F32,))
        assert partition_graph(gs).clusters == []

    def test_reduce_cluster_collapses_forward_chain(self):
        g = _optimized(None, _reduce_chain, 0, (jnp.ones((4, 8)),))
        plan = partition_graph(g)
        assert len(plan.clusters) == 1
        (c,) = plan.clusters
        assert c.kind == "reduce"
        assert plan.launches_after == 1  # the whole graph is one kernel


class TestCodegen:
    def _clusters(self):
        g = _optimized(build_grad_graph, _two_layer, (0, 1), CORPUS[2][4])
        plan = partition_graph(g)
        return [(c, emit_cluster(c)) for c in plan.clusters]

    def test_kernels_emit_and_carry_source(self):
        for c, k in self._clusters():
            assert k is not None, c
            assert "pl.pallas_call" in k.source and "def _kernel" in k.source
            assert k.n_nodes == len(c)

    def test_interpret_matches_oracle_bitwise_under_jit(self):
        """Per-cluster differential: under jit the interpreted kernel and
        the jnp oracle are the same XLA computation, hence bit-identical.
        (Eagerly they may differ by 1 ulp in transcendentals — eager
        dispatch and the interpreter compile tanh/sigmoid separately.)"""
        rng = np.random.RandomState(0)
        for c, k in self._clusters():
            args = [
                jnp.asarray(rng.randn(*i.abstract.shape), jnp.float32)
                for i in c.inputs
            ]
            np.testing.assert_array_equal(
                np.asarray(jax.jit(k.pallas_interpret)(*args)),
                np.asarray(jax.jit(k.oracle)(*args)),
            )

    def test_mode_dispatch(self):
        (c, k) = self._clusters()[0]
        args = [jnp.ones(i.abstract.shape, jnp.float32) for i in c.inputs]
        set_kernel_mode("ref")
        r_ref = jax.jit(lambda *a: k(*a))(*args)
        set_kernel_mode("pallas_interpret")
        r_int = jax.jit(lambda *a: k(*a))(*args)
        np.testing.assert_array_equal(np.asarray(r_ref), np.asarray(r_int))

    @pytest.mark.parametrize(
        "R,C,sub,elem_bytes",
        [
            (2048, 92544, 8, 32),  # internlm2-1.8b logits: columns blocked too
            (2048, 8192, 8, 40),  # whole rows fit: rows blocked only
            (5, 300000, 8, 32),  # fewer rows than a tile: the whole R
            (7, 5, 32, 24),  # small body: one whole-array block
            (4096, 2048, 16, 24),  # bf16 rows tile by 16
        ],
    )
    def test_map_blocks_are_aligned_and_fit_vmem(self, R, C, sub, elem_bytes):
        """Mosaic's rule: each block dim is the whole array dim or a
        multiple of the tiling (sublanes for rows, 128 lanes for
        columns); and the block fits the VMEM budget."""
        from repro.kernels import codegen

        br, bc = codegen._map_block(R, C, sub, elem_bytes)
        assert br == R or (br % sub == 0 and br < R)
        assert bc == C or (bc % codegen._LANE == 0 and bc < C)
        assert br * bc * elem_bytes <= codegen._VMEM_BUDGET

    @pytest.mark.parametrize("tiled", [False, True], ids=["whole", "tiled"])
    @pytest.mark.parametrize(
        "name,fn,example,nbytes,nbytes_tiled",
        SMALL_OPERANDS,
        ids=[c[0] for c in SMALL_OPERANDS],
    )
    def test_small_operand_broadcast(
        self, monkeypatch, name, fn, example, nbytes, nbytes_tiled, tiled
    ):
        """An operand that broadcasts into the body along any axes — a row,
        a lane, a scalar, a middle axis, a tile under leading axes, one
        head axis, a bool mask — enters the map kernel at its own size and
        is broadcast per block in VMEM; the wrapper expands nothing to the
        body shape.  Tiled (a 160 KiB budget blocks every axis, the lane
        and middle-axis cases with a partial edge block) exercises the
        blocks' index maps and the grid order.  Either way the kernel is
        bit-identical to the oracle under jit."""
        from repro.kernels import codegen

        if tiled:
            monkeypatch.setattr(codegen, "_VMEM_BUDGET", 160 * 1024)
        g = _optimized(None, fn, 0, example)
        (c,) = partition_graph(g).clusters
        k = emit_cluster(c)
        assert c.kind == "map" and k is not None
        kernel_src, wrapper_src = k.source.split("def _oracle")[0].split("def _make")
        grid = [int(n) for n in re.search(r"grid=\(([\d, ]+)\)", wrapper_src)[1].split(",")]
        assert all(n > 1 for n in grid) if tiled else all(n == 1 for n in grid)
        assert "jnp.broadcast_to(x1_ref[...]" in kernel_src
        assert "(pre)" not in wrapper_src
        assert not re.search(rf"broadcast_to\(.*, {re.escape(str(c.body_shape))}\)", wrapper_src)
        assert (k.broadcast_in_kernel, k.broadcast_materialized) == (1, 0)
        assert k.bytes_moved == (nbytes_tiled if tiled else nbytes)

        tracer = Tracer()
        with tracing(tracer):
            lower_graph(g, fuse=True)
        (span,) = tracer.find("lower")
        assert (span.attrs["broadcast_in_kernel"], span.attrs["broadcast_materialized"]) == (
            k.broadcast_in_kernel, k.broadcast_materialized
        )

        args = [example[g.parameters.index(i)] for i in c.inputs]
        got = np.asarray(jax.jit(k.pallas_interpret)(*args))
        np.testing.assert_array_equal(got, np.asarray(jax.jit(k.oracle)(*args)))
        if name.startswith("row"):
            assert got.sum() == 64  # one max per row: the mask is not empty

    @pytest.mark.parametrize("batch", [16, 32])
    def test_tanh_lm_kernels_keep_their_source(self, batch):
        """The tanh LM's three kernels at the benchmark's widths
        (``unreduce_eq_cast`` and the two ``mul_sub``), whose operands are
        all full, rows, lanes or scalars, are generated byte for byte as
        in ``tests/core/golden`` (the numbered kernel name masked, since
        it depends on what the process emitted before)."""
        from repro.launch.myia_step import MyiaLMDims, make_myia_train_step

        V, D, H, S = 92544, 2048, 8192, 256
        vag = make_myia_train_step(MyiaLMDims(V, D, H), batch, S, 3e-4, fuse=True)[0].vag
        args = [jax.ShapeDtypeStruct(s, jnp.float32) for s in ((V, D), (D, H), (H, D), (D, V))]
        args += [jax.ShapeDtypeStruct((batch, S), jnp.int32)] * 2
        kernels = [emit_cluster(c) for c in partition_graph(vag.optimized_graph(*args)).clusters]
        got = "".join(
            re.sub(r"fused_map\d+_", "fused_mapN_", k.source) for k in kernels if k is not None
        )
        golden = os.path.join(os.path.dirname(__file__), "golden", f"tanh_lm_b{batch}_s{S}.txt")
        with open(golden) as f:
            assert got == f.read()

    def test_scalar_graph_declines_but_lowers(self):
        """An all-opaque graph (scalar adjoint) produces no clusters and
        fused lowering degenerates to the plain one — and the attached
        plan reports zero saved launches (declined ≠ fused)."""
        g = _optimized(build_grad_graph, _cube, 0, (_F32,))
        fn = lower_graph(g, fuse=True)
        assert fn.__fused_kernels__ == []
        plan = fn.__fusion_plan__
        assert plan.launches_after == plan.launches_before
        assert float(jax.jit(fn)(jnp.asarray(2.0))) == pytest.approx(12.0)


class TestApiTier:
    def test_myia_fuse_flag_end_to_end(self):
        w1, w2, x = CORPUS[2][4]
        plain = myia.grad(_two_layer, (0, 1))
        fused = myia.grad(_two_layer, (0, 1), options=myia.CompileOptions(fuse=True))
        r0, r1 = plain(w1, w2, x), fused(w1, w2, x)
        for u, v in zip(_flat(r0), _flat(r1)):
            np.testing.assert_array_equal(np.asarray(u), np.asarray(v))
        assert fused.specialize((w1, w2, x)).lowered is True

    def test_compile_graph_mode_switch_retraces(self):
        """compile_graph's fused runner keeps one jit per kernel mode, so
        the documented flip-and-rerun flow executes the new mode instead
        of replaying the first trace."""
        from repro.core.jax_backend import compile_graph

        args = CORPUS[2][4]
        g = _optimized(build_grad_graph, _two_layer, (0, 1), args)
        run = compile_graph(g, fuse=True)
        set_kernel_mode("ref")
        r0 = run(*args)
        set_kernel_mode("pallas_interpret")
        r1 = run(*args)
        for u, v in zip(_flat(r0), _flat(r1)):
            np.testing.assert_array_equal(np.asarray(u), np.asarray(v))

    def test_fused_source_mentions_kernels(self):
        w1, w2, x = CORPUS[2][4]
        fused = myia.grad(_two_layer, (0, 1), options=myia.CompileOptions(fuse=True))
        g = fused.optimized_graph(w1, w2, x)
        fn = lower_graph(g, fuse=True)
        assert "_fused_" in fn.__lowered_source__
        assert fn.__fusion_plan__.nodes_per_cluster >= 3.0


class TestLoweringSatellites:
    def test_blockers_deduped(self):
        def power_rec(x, n):
            if n == 0:
                return 1.0
            return x * power_rec(x, n - 1)

        def use(x):
            return power_rec(x, 5)

        g = compile_pipeline(
            build_grad_graph(parse_function(use), 0), (abstract_of_value(_F32),)
        )
        blockers = lowering_blockers(g)
        assert blockers
        assert len(blockers) == len(set(blockers))

    def test_try_lower_cached_per_graph_and_tier(self):
        g = _optimized(build_grad_graph, _two_layer, (0, 1), CORPUS[2][4])
        f1 = try_lower(g)
        assert try_lower(g) is f1  # second probe: cache hit, no re-walk
        f2 = try_lower(g, fuse=True)
        assert f2 is not f1
        assert try_lower(g, fuse=True) is f2
        assert set(g.flags["_lower_cache"][1]) == {False, True}

    def test_try_lower_cache_not_inherited_by_clones(self):
        """clone_graph shallow-copies flags: a pre-optimization verdict
        (None — closure calls still present) must not leak into the
        optimized clone, which lowers fine."""
        raw = build_grad_graph(parse_function(_two_layer), (0, 1))
        assert try_lower(raw) is None  # probe & poison the raw graph
        g = compile_pipeline(
            raw, tuple(abstract_of_value(a) for a in CORPUS[2][4])
        )
        assert try_lower(g) is not None

    def test_kernel_mode_switch_respecializes(self):
        """A fused runner bakes the kernel mode in at trace time, so
        flipping set_kernel_mode must select a fresh specialization."""
        w1, w2, x = CORPUS[2][4]
        fused = myia.grad(_two_layer, (0, 1), options=myia.CompileOptions(fuse=True))
        set_kernel_mode("ref")
        r_ref = fused(w1, w2, x)
        run_ref = fused.specialize((w1, w2, x))
        set_kernel_mode("pallas_interpret")
        r_int = fused(w1, w2, x)
        assert fused.specialize((w1, w2, x)) is not run_ref
        for u, v in zip(_flat(r_ref), _flat(r_int)):
            np.testing.assert_array_equal(np.asarray(u), np.asarray(v))
