"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Nothing runs: each kernel is compiled from ``ShapeDtypeStruct``s for a
*described* ``v5e:2x2`` topology, which catches what interpret mode
cannot — blocks not aligned to the tiling, VMEM overruns.  The topology
is described inside a module fixture (never at import), so only the
worker that runs this file loads the TPU compiler, and every worker
collects the same tests.
"""

import json
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core import P, parse_function
from repro.core.api import compile_pipeline
from repro.core.fusion import DeclineReason, partition_graph
from repro.core.infer import abstract_of_value
from repro.core.ir import Constant, Graph, toposort
from repro.core.primitives import LOOP_GRAPH_ARGS
from repro.kernels.codegen import emit_cluster, emit_cluster_explained
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.rmsnorm import rmsnorm_bwd, rmsnorm_fwd
from repro.kernels.ssd_scan import ssd_scan_fwd
from repro.launch.myia_hybrid import HybridDims, init_hybrid_params, make_hybrid_train_step
from repro.launch.myia_step import MyiaLMDims, make_myia_train_step

_KERNEL = 'custom_call_target="tpu_custom_call"'
_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler to describe the chip with
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile can be written to the persistent cache but
    # never read back without a chip: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def sds(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return make


def _compiled_text(fn, *avals) -> str:
    return jax.jit(fn).lower(*avals).compile().as_text()


@pytest.fixture(scope="module")
def lm_clusters(sds):
    """The fusion clusters of the LM loss+gradient at internlm2-1.8b
    widths, batch 8 x seq 256, each with its codegen verdict."""
    dims = MyiaLMDims.from_config(get_config("internlm2-1.8b"))
    B, S = 8, 256
    vag = make_myia_train_step(dims, B, S, lr=0.1, fuse=True)[0].vag
    V, D, H = dims.vocab, dims.d_model, dims.d_hidden
    args = (
        sds((V, D)), sds((D, H)), sds((H, D)), sds((D, V)),
        sds((B, S), jnp.int32), sds((B, S), jnp.int32),
    )
    g = vag.optimized_graph(*args)
    return [(c, *emit_cluster_explained(c)) for c in partition_graph(g).clusters]


def test_lm_train_step_kernels(lm_clusters, sds):
    kernels = [(c, k) for c, k, _ in lm_clusters if k is not None]
    assert len(kernels) >= 3
    for cluster, kernel in kernels:
        avals = [sds(n.abstract.shape, n.abstract.dtype) for n in cluster.inputs]
        assert _KERNEL in _compiled_text(kernel.pallas_compiled, *avals), kernel.name


#: one HLO instruction: name, result dims, opcode, operand list
_HLO_INSTR = re.compile(r"%([\w.-]+) = \w+\[([\d,]*)\]\S* ([\w-]+)\(([^)]*)\)")


def test_lm_max_adjoint_mask_broadcasts_in_vmem(lm_clusters, sds):
    """The reduce_max adjoint's mask, ``cast(eq(logits, unreduce(m)))``,
    takes its (B, S, 1) row max as a (B*S, 1) operand and broadcasts it
    in the kernel: no broadcast at the logits' size is left in the HLO,
    and the kernel reads exactly one body-size operand.  The tanh
    adjoints have no small operand."""
    by_name = {k.name: (c, k) for c, k, _ in lm_clusters if k is not None}
    (name,) = [n for n in by_name if n.endswith("_unreduce_eq_cast")]
    cluster, kernel = by_name[name]
    body = math.prod(cluster.body_shape)
    assert (kernel.broadcast_in_kernel, kernel.broadcast_materialized) == (1, 0)
    # one read of the logits, one write of the mask, the row max once
    assert kernel.bytes_moved == 2 * body * 4 + body // cluster.body_shape[-1] * 4
    avals = [sds(n.abstract.shape, n.abstract.dtype) for n in cluster.inputs]
    instrs = [
        (n, math.prod(int(d) for d in dims.split(",") if d), op, args)
        for n, dims, op, args in _HLO_INSTR.findall(
            _compiled_text(kernel.pallas_compiled, *avals)
        )
    ]
    elems = {n: size for n, size, _, _ in instrs}
    assert not [n for n, size, op, _ in instrs if op == "broadcast" and size == body]
    (call_args,) = [args for _, _, op, args in instrs if op == "custom-call"]
    operands = re.findall(r"%([\w.-]+)", call_args)
    assert [elems[o] == body for o in operands].count(True) == 1
    mul_sub = [k for n, (_, k) in by_name.items() if n.endswith("_mul_sub")]
    assert len(mul_sub) == 2 and all(k.broadcast_in_kernel == 0 for k in mul_sub)


def _row_scale(x, m):
    return P.tanh(x) * P.unreduce(m, P.shape(x), (2,), True) + x


def _lane_scale(x, b):
    return P.tanh(x) * P.broadcast_to(b, P.shape(x)) + x


def _scalar_scale(x, s):
    return P.tanh(x) * s + x


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize(
    "fn,small_shape",
    [(_row_scale, (16, 256, 1)), (_lane_scale, (92544,)), (_scalar_scale, ())],
    ids=["row", "lane", "scalar"],
)
def test_small_operand_kernels(sds, fn, small_shape, dtype):
    """Each small-operand kind broadcasts in VMEM and compiles at the
    logits' widths, a packed (bf16) scalar included."""
    body = (16, 256, 92544)
    g = compile_pipeline(
        parse_function(fn),
        (abstract_of_value(sds(body, dtype)), abstract_of_value(sds(small_shape, dtype))),
    )
    (cluster,) = partition_graph(g).clusters
    kernel = emit_cluster(cluster)
    assert kernel.broadcast_in_kernel == 1
    avals = [sds(n.abstract.shape, n.abstract.dtype) for n in cluster.inputs]
    assert _KERNEL in _compiled_text(kernel.pallas_compiled, *avals)


def _clusters_with_loop_bodies(g):
    """The fusion clusters of ``g`` and of every loop body in it, as the
    lowering partitions each of them."""
    out = partition_graph(g).clusters
    for n in toposort(g):
        if n.is_apply and isinstance(n.fn, Constant):
            for sub in n.args[: LOOP_GRAPH_ARGS.get(getattr(n.fn.value, "name", None), 0)]:
                if isinstance(sub, Constant) and isinstance(sub.value, Graph):
                    out += _clusters_with_loop_bodies(sub.value)
    return out


@pytest.fixture(scope="module")
def hybrid_clusters():
    """The Granite 4.0-H Micro configuration and the fusion clusters of
    its loss+gradient at published widths, B 2 x S 2048 (the benchmark
    cell's), loop bodies included."""
    with open(os.path.join(_ROOT, "bench", "configs", "myia-granite4h-micro.json")) as f:
        dims = HybridDims(json.load(f))
    B, S = 2, 2048
    vag = make_hybrid_train_step(dims, B, S, 3e-4, fuse=True)[0].vag
    params = jax.eval_shape(lambda: init_hybrid_params(dims, jax.random.PRNGKey(0)))
    tokens = jax.ShapeDtypeStruct((B, S), jnp.int32)
    g = vag.optimized_graph(*params, tokens, tokens)
    return dims, B, S, _clusters_with_loop_bodies(g)


def _distinct_kernels(clusters, body):
    """The distinct generated kernels at ``body``, by kernel name with its
    number dropped."""
    found = {}
    for c in clusters:
        if tuple(c.body_shape) == body:
            k = emit_cluster(c)
            found.setdefault(re.sub(r"^fused_map\d+_", "", k.name), (c, k))
    return found


@pytest.fixture(scope="module")
def hybrid_ssd_kernels(hybrid_clusters):
    """The distinct generated kernels at the SSD's (B, H, NC, Q, Q) body:
    the forward's masked decays times C B^T, the recompute's decays and
    the adjoint's product."""
    dims, B, S, clusters = hybrid_clusters
    body = (B, dims.heads, S // dims.chunk, dims.chunk, dims.chunk)
    return body, _distinct_kernels(clusters, body)


@pytest.mark.parametrize("kernel", ["sub_where_exp_mul", "sub_where_exp", "mul"])
def test_hybrid_ssd_kernels_broadcast_in_vmem(sds, hybrid_ssd_kernels, kernel):
    """Each SSD kernel takes its cumulative-decay rows and columns, the
    causal (Q, Q) mask and C B^T (one for all 64 heads) at their own
    sizes and broadcasts them per block in VMEM: it compiles through
    Mosaic, and no broadcast at the body's size is left in the HLO."""
    body, found = hybrid_ssd_kernels
    cluster, k = found[kernel]
    assert k.broadcast_materialized == 0 and k.broadcast_in_kernel >= 2
    avals = [sds(n.abstract.shape, n.abstract.dtype) for n in cluster.inputs]
    text = _compiled_text(k.pallas_compiled, *avals)
    assert _KERNEL in text
    size = math.prod(body)
    assert not [
        n for n, dims, op, _ in _HLO_INSTR.findall(text)
        if op == "broadcast" and math.prod(int(d) for d in dims.split(",") if d) == size
    ]


@pytest.mark.parametrize(
    "kernel", ["pad_zeros_axis_slice_axis_mul_add_sigmoid", "pad_zeros_axis_slice_axis_mul_add"]
)
def test_hybrid_conv_kernel_reads_its_input_once(sds, hybrid_clusters, kernel):
    """The Mamba-2 mixer's width-4 causal conv over ``xBC`` (B, S, conv
    width), in the forward and in the backward's recompute: the kernel
    takes ``xBC`` once at its own size, whole along S, and forms the four
    shifted windows in VMEM; it compiles through Mosaic, and no shifted
    slice or pad of ``xBC`` is left in the HLO around it."""
    dims, B, S, clusters = hybrid_clusters
    body = (B, S, dims.conv_dim)
    cluster, k = _distinct_kernels(clusters, body)[kernel]
    assert (k.window_in_kernel, k.window_materialized) == (4, 0) and k.cluster is cluster
    size = math.prod(body)
    full = [n for n in cluster.inputs if math.prod(n.abstract.shape) == size]
    assert [tuple(n.abstract.shape) for n in full] == [body]
    # one read of xBC and one write, and the conv's (C,) weights and bias
    assert k.bytes_moved == 2 * size * 4 + 5 * dims.conv_dim * 4
    avals = [sds(n.abstract.shape, n.abstract.dtype) for n in cluster.inputs]
    text = _compiled_text(k.pallas_compiled, *avals)
    assert _KERNEL in text
    instrs = _HLO_INSTR.findall(text)
    assert not [n for n, _, op, _ in instrs if op in ("slice", "pad", "concatenate")]
    (call_args,) = [args for _, _, op, args in instrs if op == "custom-call"]
    elems = {n: math.prod(int(d) for d in dims_.split(",") if d) for n, dims_, _, _ in instrs}
    assert [elems.get(o) == size for o in re.findall(r"%([\w.-]+)", call_args)].count(True) == 1


def test_lm_reduce_cluster_over_vmem_declines(lm_clusters):
    """The cross-entropy sum reduces a (8, 256, 92544) body: its
    whole-array block cannot fit VMEM, so codegen declines it instead of
    emitting a kernel Mosaic would refuse."""
    reasons = [r for c, _, r in lm_clusters if c.kind == "reduce"]
    assert reasons and all(r.kind == DeclineReason.VMEM for r in reasons)


def test_flash_attention_fwd(sds):
    q = sds((1, 16, 4096, 128), jnp.bfloat16)
    kv = sds((1, 8, 4096, 128), jnp.bfloat16)

    def fwd(q, k, v):
        return flash_attention_fwd(q, k, v, causal=True, interpret=False)

    assert _KERNEL in _compiled_text(fwd, q, kv, kv)


def test_rmsnorm_fwd(sds):
    def fwd(x, w):
        return rmsnorm_fwd(x, w, interpret=False)

    assert _KERNEL in _compiled_text(fwd, sds((8192, 2048)), sds((2048,)))


def test_rmsnorm_bwd(sds):
    def bwd(x, w, dy):
        return rmsnorm_bwd(x, w, dy, interpret=False)

    x = sds((8192, 2048))
    assert _KERNEL in _compiled_text(bwd, x, sds((2048,)), x)


def test_ssd_scan_fwd_mamba2_370m(sds):
    """mamba2-370m: d_inner 2048 = 32 heads of 64, state 128, one group."""
    cfg = get_config("mamba2-370m")
    H, P, N, S = cfg.n_ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, 4096

    def fwd(x, dt, A, B, C):
        return ssd_scan_fwd(x, dt, A, B, C, interpret=False)

    text = _compiled_text(
        fwd,
        sds((1, S, H, P)), sds((1, S, H)), sds((H,)),
        sds((1, S, 1, N)), sds((1, S, 1, N)),
    )
    assert _KERNEL in text
