"""SPMD tier unit tests: propagation, the per-shard transform, fusion
legality across resharding points, and the 1×1-mesh identity.

Propagation and the transform are pure graph passes — no devices needed;
mesh axes are plain ``{name: size}`` dicts.  Multi-device *execution* is
covered by tests/distributed/test_spmd_exec.py (subprocesses: the main
pytest process has a locked 1-device backend).
"""

import jax
import numpy as np
import pytest

import repro.core.primitives as P
from repro.core import build_grad_graph, parse_function
from repro.core.api import compile_pipeline, value_and_grad
from repro.core.fusion import COLLECTIVES, classify, partition_graph
from repro.core.infer import AArray, abstract_of_value
from repro.core.ir import Apply, Constant
from repro.core.lowering import lower_graph
from repro.core.opt import optimize
from repro.core.spmd import (
    SpmdError,
    normalize_spec,
    propagate,
    shard_graph,
    spec_to_partition,
)

AXES = {"data": 2, "model": 2}


def _two_layer(w1, w2, x):
    h = P.tanh(x @ w1)
    return P.reduce_sum(P.tanh(h @ w2), (0, 1), False)


def _pipeline(fn, args, wrt=None):
    g = parse_function(fn) if wrt is None else build_grad_graph(parse_function(fn), wrt)
    return compile_pipeline(g, tuple(abstract_of_value(a) for a in args))


def _mlp_args(b=8, d=16):
    k = jax.random.PRNGKey
    return (
        jax.random.normal(k(0), (d, d)) * 0.1,
        jax.random.normal(k(1), (d, d)) * 0.1,
        jax.random.normal(k(2), (b, d)),
    )


def _prims_of(graph):
    return [
        n.fn.value.name
        for n in graph.nodes()
        if isinstance(n, Apply) and isinstance(n.fn, Constant)
    ]


class TestNormalize:
    def test_divisibility_falls_back_to_replication(self):
        ab = AArray(np.float32, (6, 3))
        # dim 3 does not divide by model=2 -> replicated
        assert normalize_spec((("data",), ("model",)), ab, AXES) == (("data",), ())

    def test_unknown_axes_dropped_and_axis_used_once(self):
        ab = AArray(np.float32, (8, 8))
        assert normalize_spec((("pod",), None), ab, AXES) == ((), ())
        assert normalize_spec((("data",), ("data",)), ab, AXES) == (("data",), ())

    def test_none_is_fully_replicated_and_partition_roundtrip(self):
        from jax.sharding import PartitionSpec as PS

        ab = AArray(np.float32, (8, 8))
        spec = normalize_spec(None, ab, AXES)
        assert spec == ((), ())
        assert spec_to_partition(spec) == PS(None, None)
        assert normalize_spec(PS("data", None), ab, AXES) == (("data",), ())


class TestPropagate:
    def test_data_parallel_mlp_adjoint(self):
        g = _pipeline(_two_layer, _mlp_args(), wrt=(0, 1))
        plan = propagate(g, (None, None, ("data",)), AXES)
        # both weight grads contract over the sharded batch -> 2 psums
        assert plan.stats["n_psum"] == 2
        assert plan.stats["params_sharded"] == 1
        assert plan.stats["nodes_sharded"] > plan.stats["nodes"] // 2

    def test_tensor_parallel_megatron_pair(self):
        g = _pipeline(_two_layer, _mlp_args(), wrt=(0, 1))
        plan = propagate(g, (("model",), (None, "model"), ("data",)), AXES)
        # forward row-sharded contraction adds a third psum
        assert plan.stats["n_psum"] >= 3

    def test_replicated_inputs_insert_no_collectives(self):
        g = _pipeline(_two_layer, _mlp_args(), wrt=(0, 1))
        plan = propagate(g, (None, None, None), AXES)
        assert plan.stats["n_psum"] == 0
        assert plan.stats["nodes_sharded"] == 0

    def test_arity_mismatch_raises(self):
        g = _pipeline(_two_layer, _mlp_args(), wrt=(0, 1))
        with pytest.raises(SpmdError):
            propagate(g, (None, None), AXES)


class TestShardGraph:
    def test_collectives_inserted_and_shapes_localized(self):
        args = _mlp_args(b=8)
        g = _pipeline(_two_layer, args, wrt=(0, 1))
        sg = shard_graph(g, (None, None, ("data",)), AXES)
        prims = _prims_of(sg.graph)
        assert prims.count("psum_axes") == 2
        # the scalar cotangent's unreduce targets the LOCAL batch block
        unreduce = [
            n
            for n in sg.graph.nodes()
            if isinstance(n, Apply) and n.fn.value.name == "unreduce"
        ]
        assert unreduce and unreduce[0].args[1].value == (4, 16)
        # re-inference annotated per-shard shapes
        assert unreduce[0].abstract.shape == (4, 16)

    def test_broadcast_refinement_avoids_gathers(self):
        g = _pipeline(_two_layer, _mlp_args(), wrt=(0, 1))
        sg = shard_graph(g, (None, None, ("data",)), AXES)
        assert sg.stats["all_gather"] == 0
        assert sg.stats["shard_slice"] == 0

    def test_out_partition_matches_return_structure(self):
        from jax.sharding import PartitionSpec as PS

        g = _pipeline(_two_layer, _mlp_args(), wrt=(0, 1))
        sg = shard_graph(g, (None, None, ("data",)), AXES)
        assert sg.out_partition == (PS(None, None), PS(None, None))

    def test_non_first_order_graph_raises(self):
        def rec(n):
            if n <= 0:
                return 0
            return rec(n - 1)

        # a residually-recursive (non-lowerable) graph: skip optimization
        g_raw = compile_pipeline(parse_function(rec), None, opt=False)
        with pytest.raises(SpmdError):
            shard_graph(g_raw, ((),), AXES)


class TestFusionBoundaries:
    def test_collectives_classify_opaque(self):
        g = _pipeline(_two_layer, _mlp_args(), wrt=(0, 1))
        sg = shard_graph(g, (None, None, ("data",)), AXES)
        coll = [
            n
            for n in sg.graph.nodes()
            if isinstance(n, Apply) and n.fn.value.name in COLLECTIVES
        ]
        assert coll
        assert all(classify(n) == "opaque" for n in coll)

    def test_no_cluster_spans_a_resharding_point(self):
        g = _pipeline(_two_layer, _mlp_args(), wrt=(0, 1))
        sg = shard_graph(g, (None, None, ("data",)), AXES)
        plan = partition_graph(sg.graph)
        assert plan.clusters  # sharded graphs still fuse
        for c in plan.clusters:
            assert all(n.fn.value.name not in COLLECTIVES for n in c.order)

    def test_resharding_point_splits_fusable_chain(self):
        # sum over the sharded batch dim sits mid-chain: elementwise ops on
        # either side may not fuse across the psum
        def chain(x):
            s = P.reduce_sum(P.tanh(x) * P.sigmoid(x) + 1.0, (0,), True)
            return P.reduce_sum(P.exp(s) * 2.0, (0, 1), False)

        x = jax.random.normal(jax.random.PRNGKey(0), (8, 16))
        g = _pipeline(chain, (x,))
        sg = shard_graph(g, (("data",),), AXES)
        prims = _prims_of(sg.graph)
        assert "psum_axes" in prims
        plan = partition_graph(sg.graph)
        ids_by_cluster = [c.members for c in plan.clusters]
        coll_ids = {
            n._id
            for n in sg.graph.nodes()
            if isinstance(n, Apply) and n.fn.value.name in COLLECTIVES
        }
        for members in ids_by_cluster:
            assert not (members & coll_ids)


class TestOptGuard:
    def test_optimizer_never_touches_collectives(self):
        g = _pipeline(_two_layer, _mlp_args(), wrt=(0, 1))
        sg = shard_graph(g, (None, None, ("data",)), AXES)
        before = _prims_of(sg.graph).count("psum_axes")
        optimize(sg.graph)
        assert _prims_of(sg.graph).count("psum_axes") == before


class TestMesh1x1Identity:
    """On a 1×1 mesh the per-shard program IS the global program — the
    spmd tier must agree with the single-device lowering exactly (these
    run in the main pytest process: one device is enough)."""

    def test_spmd_runner_matches_plain_lowering(self):
        from repro.core.jax_backend import compile_graph_spmd
        from repro.launch.mesh import make_local_mesh

        args = _mlp_args()
        g = _pipeline(_two_layer, args, wrt=(0, 1))
        ref = jax.jit(lower_graph(g))(*args)
        mesh = make_local_mesh(1, 1)
        run = compile_graph_spmd(g, mesh, (None, None, ("data",)))
        got = run(*args)
        for a, b in zip(got, ref):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_api_dispatch_and_fallback(self):
        from repro.launch.mesh import make_local_mesh
        from repro.parallel import mesh_context

        args = _mlp_args()
        vag = value_and_grad(_two_layer, (0, 1), in_specs=(None, None, ("data",)))
        loss0, grads0 = vag(*args)
        assert not getattr(vag.specialize(args), "spmd", False)
        with mesh_context(make_local_mesh(1, 1), {}):
            loss1, grads1 = vag(*args)
            assert getattr(vag.specialize(args), "spmd", False)
        # fp-tolerant: the single-device first call answers from the tier-0
        # (low-opt XLA) executable, which may reorder contractions
        np.testing.assert_allclose(float(loss0), float(loss1), rtol=1e-5)
        for a, b in zip(grads0, grads1):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7)

    def test_abstract_mesh_context_does_not_engage_spmd(self):
        from jax.sharding import AbstractMesh

        from repro.parallel import mesh_context

        args = _mlp_args()
        vag = value_and_grad(_two_layer, (0, 1), in_specs=(None, None, ("data",)))
        mesh = AbstractMesh((16, 16), ("data", "model"))
        with mesh_context(mesh, {}):
            runner = vag.specialize(args)
        assert not getattr(runner, "spmd", False)
