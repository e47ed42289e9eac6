"""Primitive operations of the IR.

Every primitive carries
* ``impl``   — the runtime implementation (jnp, with Python-scalar fast
  paths so that loop counters stay concrete and control flow can unroll),
* ``bprop``  — its *backpropagator definition*: a Python function in the
  Myia subset, parsed lazily into an IR graph by the frontend.  Per the
  paper §3.2: "The backpropagators of primitives are known."  Because the
  bprop is itself IR, the AD transform can be applied to it again —
  reverse-over-reverse works.
* an optional ``infer`` rule (structural prims); array prims default to
  abstract evaluation via ``jax.eval_shape`` in the inferencer.

Pallas TPU kernels register themselves here as primitives with hand-written
backpropagators (see ``repro.kernels``) — exactly the paper's "write
efficient low-level kernels and their derivatives in a low-level language
and expose them to Myia as primitives".
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from .values import EnvInstance, gadd_values, zeros_like_value

__all__ = ["Primitive", "PRIMITIVES", "register_primitive", "COLLECTIVE_NAMES"]

_PY_NUM = (bool, int, float)


def _all_py(*xs: Any) -> bool:
    return all(isinstance(x, _PY_NUM) for x in xs)


class Primitive:
    """A named primitive with implementation + backpropagator definition."""

    def __init__(
        self,
        name: str,
        impl: Callable,
        *,
        bprop: Callable | str | None = None,
        vararg: bool = False,
        infer: Callable | None = None,
    ) -> None:
        self.name = name
        self.impl = impl
        #: Python function (Myia subset) computing input gradients, with
        #: signature ``(x1..xn, out, dout) -> (dx1..dxn)``; the string
        #: "zeros" means all-zero gradients (non-differentiable prim);
        #: None means AD must special-case it (make_tuple, …).
        self.bprop = bprop
        self.vararg = vararg
        self.infer = infer
        self._bprop_graph = None  # parsed lazily by repro.core.ad

    def __call__(self, *args: Any) -> Any:
        return self.impl(*args)

    def __repr__(self) -> str:  # pragma: no cover
        return f"<Prim {self.name}>"


PRIMITIVES: dict[str, Primitive] = {}


def register_primitive(
    name: str,
    impl: Callable,
    *,
    bprop: Callable | str | None = None,
    vararg: bool = False,
    infer: Callable | None = None,
) -> Primitive:
    p = Primitive(name, impl, bprop=bprop, vararg=vararg, infer=infer)
    PRIMITIVES[name] = p
    return p


# ===========================================================================
# Implementations
# ===========================================================================


def _impl_add(x, y):
    return x + y if _all_py(x, y) else jnp.add(x, y)


def _impl_sub(x, y):
    return x - y if _all_py(x, y) else jnp.subtract(x, y)


def _impl_mul(x, y):
    return x * y if _all_py(x, y) else jnp.multiply(x, y)


def _impl_div(x, y):
    return x / y if _all_py(x, y) else jnp.divide(x, y)


def _impl_pow(x, y):
    return x**y if _all_py(x, y) else jnp.power(x, y)


def _impl_floordiv(x, y):
    return x // y if _all_py(x, y) else jnp.floor_divide(x, y)


def _impl_mod(x, y):
    return x % y if _all_py(x, y) else jnp.mod(x, y)


def _impl_neg(x):
    return -x if _all_py(x) else jnp.negative(x)


def _cmp(py, jx):
    def impl(a, b):
        return py(a, b) if _all_py(a, b) else jx(a, b)

    return impl


def _impl_switch(c, t, f):
    if isinstance(c, (bool, np.bool_)):
        return t if c else f
    if isinstance(c, jnp.ndarray) and not isinstance(c, jax.core.Tracer):
        return t if bool(c) else f
    # traced condition: only valid for array-like branches
    return jnp.where(c, t, f)


def _impl_shape(x):
    if isinstance(x, _PY_NUM):
        return ()
    return tuple(int(d) for d in x.shape)


def _impl_unbroadcast(x, shp):
    shp = tuple(shp)
    if isinstance(x, _PY_NUM):
        return x
    if shp == ():
        return jnp.sum(x)
    ndiff = x.ndim - len(shp)
    if ndiff > 0:
        x = jnp.sum(x, axis=tuple(range(ndiff)))
    axes = tuple(i for i, (a, b) in enumerate(zip(x.shape, shp)) if b == 1 and a != 1)
    if axes:
        x = jnp.sum(x, axis=axes, keepdims=True)
    return x


def _norm_axes(axes, ndim):
    if axes is None:
        return tuple(range(ndim))
    if isinstance(axes, int):
        axes = (axes,)
    return tuple(sorted(a % ndim for a in axes))


def _impl_reduce_sum(x, axes, keepdims):
    return jnp.sum(x, axis=axes if axes is None else tuple(axes), keepdims=keepdims)


def _impl_reduce_max(x, axes, keepdims):
    return jnp.max(x, axis=axes if axes is None else tuple(axes), keepdims=keepdims)


def _impl_unreduce(x, shp, axes, keepdims):
    shp = tuple(shp)
    x = jnp.asarray(x)
    if not keepdims:
        for a in _norm_axes(axes, len(shp)):
            x = jnp.expand_dims(x, a)
    return jnp.broadcast_to(x, shp)


def _impl_axes_size(x, axes):
    shp = _impl_shape(x)
    return int(np.prod([shp[a] for a in _norm_axes(axes, len(shp))])) if shp else 1


def _impl_mT(x):
    return jnp.swapaxes(x, -1, -2)


def _impl_take(x, idx):
    return jnp.take(x, idx, axis=0)


def _impl_index_add(base, idx, val):
    return jnp.asarray(base).at[idx].add(val)


def _impl_slice_axis(x, axis, start, stop):
    return jax.lax.slice_in_dim(x, start, stop, axis=axis)


def _impl_pad_zeros_axis(x, axis, before, after):
    pads = [(0, 0)] * jnp.ndim(x)
    pads[axis] = (before, after)
    return jnp.pad(x, pads)


def _impl_concat_axis(xs, axis):
    return jnp.concatenate(list(xs), axis=axis)


def _impl_concat_grad(xs, axis, dout):
    outs = []
    off = 0
    for x in xs:
        n = x.shape[axis]
        outs.append(jax.lax.slice_in_dim(dout, off, off + n, axis=axis))
        off += n
    return tuple(outs)


def _impl_cumsum(x, axis, reverse):
    return jax.lax.cumsum(x, axis=axis, reverse=bool(reverse))


def _impl_cast(x, dtype):
    return jnp.asarray(x, dtype=dtype)


def _impl_dtype_of(x):
    if isinstance(x, (bool, np.bool_)):
        return jnp.bool_.dtype if hasattr(jnp.bool_, "dtype") else np.dtype(bool)
    if isinstance(x, int):
        return np.dtype("int32")
    if isinstance(x, float):
        return np.dtype("float32")
    return x.dtype


def _impl_stop_gradient(x):
    return x if _all_py(x) else jax.lax.stop_gradient(x)


def _impl_env_setitem(env: EnvInstance, key, val):
    return env.set(key, val)


def _impl_env_getitem(env: EnvInstance, key, default):
    return env.get(key, default)


def _impl_invert_permutation(perm):
    return tuple(int(i) for i in np.argsort(np.asarray(perm)))


def _impl_tuple_getitem(t, i):
    return t[i]


def _impl_tuple_setitem(t, i, v):
    lst = list(t)
    lst[i] = v
    return tuple(lst)


def _impl_one_hot(idx, num, dtype):
    return jax.nn.one_hot(idx, num, dtype=dtype)


# ---------------------------------------------------------------------------
# Collectives (SPMD tier).  These primitives only execute inside a
# ``shard_map`` region: their axis names must be bound by the surrounding
# mesh.  They are inserted by ``repro.core.spmd`` *after* AD and
# optimization (resharding points of the propagated sharding), so they
# carry no backpropagators — differentiating through one is a pipeline
# ordering bug and must fail loudly, not return zeros.  ``axes`` is a
# tuple of mesh axis names; ``sizes`` the matching mesh axis sizes
# (baked in by the SPMD transform so shape inference needs no mesh).
# ---------------------------------------------------------------------------


def _impl_psum_axes(x, axes):
    return jax.lax.psum(x, tuple(axes))


def _impl_pmax_axes(x, axes):
    return jax.lax.pmax(x, tuple(axes))


def _impl_all_gather_axes(x, axes, dim, sizes):
    out = x
    # gather innermost axis first so the outermost axis ends up as the
    # slowest-varying block — matching shard_slice's linearized index
    for a in reversed(tuple(axes)):
        out = jax.lax.all_gather(out, a, axis=dim, tiled=True)
    return out


def _impl_shard_slice(x, axes, dim, sizes):
    idx = 0
    for a, s in zip(tuple(axes), tuple(sizes)):
        idx = idx * s + jax.lax.axis_index(a)
    block = x.shape[dim] // int(np.prod(sizes))
    return jax.lax.dynamic_slice_in_dim(x, idx * block, block, axis=dim)


#: primitive names that communicate across shards (or re-partition a
#: replicated value).  Fusion classifies these as opaque — a cluster can
#: never span a resharding point — and the optimizer never folds them.
COLLECTIVE_NAMES = frozenset(
    {"psum_axes", "pmax_axes", "all_gather_axes", "shard_slice"}
)


# ---------------------------------------------------------------------------
# Structured loops (closure-elimination tier).  ``repro.core.closure``
# rewrites residual recursive families (parsed while/for loops, nested
# loop SCCs, affine non-tail self-recursion) into these primitives.
# They register with ``bprop=None`` like the collectives, but for a
# different reason: their adjoints are not pointwise VJP rules — they are
# *loop-shaped* ("don't unroll the adjoint"), so ``repro.core.ad``'s
# JTransformer differentiates the primitive applies directly, emitting a
# reversed scan over saved-carry stacks (``scan_loop``) or a trip-counted,
# checkpointed backward while (``while_loop``).  The pre-grad pipeline
# (``ad._prepare_primal``) lowers parsed loops *before* J so grad sees
# these primitives rather than raw recursion.  ``cond``/``step``/``exit``
# arrive as *closed first-order graphs* (bound as lowered callables on the
# direct path, as Closures on the VM path); the trailing arguments split
# at ``n_carry`` into the loop carry (the header parameters) and the
# loop-invariant closure environment (threaded unchanged to every call).
# ---------------------------------------------------------------------------


def _call_loop_fn(f: Any, args: tuple) -> Any:
    """Call a loop sub-function: a lowered Python callable (direct path)
    or a Graph/Closure evaluated by the reference VM (fallback path)."""
    from .ir import Graph
    from .values import Closure

    if isinstance(f, (Graph, Closure)):
        from .vm import VM

        return VM().call(f, tuple(args))
    return f(*args)


def _loop_retype_carry(step_f: Callable, carry: tuple) -> tuple:
    """Promote the init carry to the step's output types.  jax requires the
    while/scan carry to be type-stable; Python-literal inits (weak types)
    routinely disagree with the step's strong jnp results, and one
    promotion round resolves every case our rewriter can produce."""
    spec = jax.eval_shape(step_f, carry)
    return jax.tree_util.tree_map(lambda i, s: jnp.asarray(i, s.dtype), carry, spec)


def _impl_while_loop(cond, step, exit_, n_carry, *args):
    carry = tuple(args[:n_carry])
    extras = tuple(args[n_carry:])

    def cond_f(c):
        return _call_loop_fn(cond, (*c, *extras))

    def step_f(c):
        return tuple(_call_loop_fn(step, (*c, *extras)))

    try:
        out = jax.lax.while_loop(cond_f, step_f, carry)
    except TypeError:
        out = jax.lax.while_loop(cond_f, step_f, _loop_retype_carry(step_f, carry))
    return _call_loop_fn(exit_, (*tuple(out), *extras))


def _impl_scan_loop(step, exit_, length, n_carry, *args):
    carry = tuple(args[:n_carry])
    extras = tuple(args[n_carry:])

    def step_f(c):
        return tuple(_call_loop_fn(step, (*c, *extras)))

    def body(c, _):
        return step_f(c), None

    try:
        out, _ = jax.lax.scan(body, carry, None, length=int(length))
    except TypeError:
        out, _ = jax.lax.scan(
            body, _loop_retype_carry(step_f, carry), None, length=int(length)
        )
    return _call_loop_fn(exit_, (*tuple(out), *extras))


#: loop primitives and, per name, how many leading arguments are
#: graph-valued sub-functions (legal graph constants for the lowerer)
LOOP_GRAPH_ARGS: dict[str, int] = {"while_loop": 3, "scan_loop": 2}
LOOP_NAMES = frozenset(LOOP_GRAPH_ARGS)


# ===========================================================================
# Registration.  bprop functions are defined at the end of this module and
# attached afterwards (they reference the prim globals below).
# ===========================================================================

add = register_primitive("add", _impl_add)
sub = register_primitive("sub", _impl_sub)
mul = register_primitive("mul", _impl_mul)
div = register_primitive("div", _impl_div)
power = register_primitive("power", _impl_pow)


def _impl_integer_pow(x, n):
    if _all_py(x, n):
        return x**n
    return jax.lax.integer_pow(x, int(n))


integer_pow = register_primitive("integer_pow", _impl_integer_pow)
floordiv = register_primitive("floordiv", _impl_floordiv, bprop="zeros")
mod = register_primitive("mod", _impl_mod, bprop="zeros")
neg = register_primitive("neg", _impl_neg)

exp = register_primitive("exp", lambda x: jnp.exp(x))
log = register_primitive("log", lambda x: jnp.log(x))
tanh = register_primitive("tanh", lambda x: jnp.tanh(x))
sigmoid = register_primitive("sigmoid", lambda x: jax.nn.sigmoid(x))
softplus = register_primitive("softplus", lambda x: jax.nn.softplus(x))
relu = register_primitive("relu", lambda x: jnp.maximum(x, 0))
sqrt = register_primitive("sqrt", lambda x: jnp.sqrt(x))
rsqrt = register_primitive(
    "rsqrt", lambda x: jax.lax.rsqrt(jnp.asarray(x, jnp.result_type(x, 1.0)))
)
sin = register_primitive("sin", lambda x: jnp.sin(x))
cos = register_primitive("cos", lambda x: jnp.cos(x))
square = register_primitive("square", lambda x: jnp.square(x))
absolute = register_primitive("absolute", lambda x: abs(x) if _all_py(x) else jnp.abs(x))
sign = register_primitive("sign", lambda x: jnp.sign(x), bprop="zeros")
erf = register_primitive("erf", lambda x: jax.lax.erf(jnp.asarray(x, jnp.result_type(x, 1.0))))

lt = register_primitive("lt", _cmp(lambda a, b: a < b, jnp.less), bprop="zeros")
gt = register_primitive("gt", _cmp(lambda a, b: a > b, jnp.greater), bprop="zeros")
le = register_primitive("le", _cmp(lambda a, b: a <= b, jnp.less_equal), bprop="zeros")
ge = register_primitive("ge", _cmp(lambda a, b: a >= b, jnp.greater_equal), bprop="zeros")
eq = register_primitive("eq", _cmp(lambda a, b: a == b, jnp.equal), bprop="zeros")
ne = register_primitive("ne", _cmp(lambda a, b: a != b, jnp.not_equal), bprop="zeros")
bool_and = register_primitive(
    "bool_and", _cmp(lambda a, b: a and b, jnp.logical_and), bprop="zeros"
)
bool_or = register_primitive("bool_or", _cmp(lambda a, b: a or b, jnp.logical_or), bprop="zeros")
bool_not = register_primitive(
    "bool_not", lambda x: (not x) if _all_py(x) else jnp.logical_not(x), bprop="zeros"
)

maximum = register_primitive(
    "maximum", lambda x, y: max(x, y) if _all_py(x, y) else jnp.maximum(x, y)
)
minimum = register_primitive(
    "minimum", lambda x, y: min(x, y) if _all_py(x, y) else jnp.minimum(x, y)
)
where = register_primitive("where", lambda c, a, b: jnp.where(c, a, b))

matmul = register_primitive("matmul", lambda a, b: jnp.matmul(a, b))
mT = register_primitive("mT", _impl_mT)
transpose = register_primitive("transpose", lambda x, perm: jnp.transpose(x, tuple(perm)))
reshape = register_primitive("reshape", lambda x, shp: jnp.reshape(x, tuple(shp)))
broadcast_to = register_primitive("broadcast_to", lambda x, shp: jnp.broadcast_to(x, tuple(shp)))
unbroadcast = register_primitive("unbroadcast", _impl_unbroadcast)
reduce_sum = register_primitive("reduce_sum", _impl_reduce_sum)
reduce_max = register_primitive("reduce_max", _impl_reduce_max)
unreduce = register_primitive("unreduce", _impl_unreduce)

shape = register_primitive("shape", _impl_shape, bprop="zeros")
axes_size = register_primitive("axes_size", _impl_axes_size, bprop="zeros")
dtype_of = register_primitive("dtype_of", _impl_dtype_of, bprop="zeros")
invert_permutation = register_primitive(
    "invert_permutation", _impl_invert_permutation, bprop="zeros"
)
cast = register_primitive("cast", _impl_cast)

take = register_primitive("take", _impl_take)
index_add = register_primitive("index_add", _impl_index_add)
slice_axis = register_primitive("slice_axis", _impl_slice_axis)
pad_zeros_axis = register_primitive("pad_zeros_axis", _impl_pad_zeros_axis)
concat_axis = register_primitive("concat_axis", _impl_concat_axis)
concat_grad = register_primitive("concat_grad", _impl_concat_grad)
cumsum = register_primitive("cumsum", _impl_cumsum)
one_hot = register_primitive("one_hot", _impl_one_hot, bprop="zeros")

# collectives: bprop=None — AD through a resharding point must fail loudly
psum_axes = register_primitive("psum_axes", _impl_psum_axes)
pmax_axes = register_primitive("pmax_axes", _impl_pmax_axes)
all_gather_axes = register_primitive("all_gather_axes", _impl_all_gather_axes)
shard_slice = register_primitive("shard_slice", _impl_shard_slice)

# structured loops: bprop=None — their adjoints are loop-shaped, built by
# ad.JTransformer._j_while/_j_scan rather than a pointwise VJP rule
while_loop = register_primitive("while_loop", _impl_while_loop, vararg=True)
scan_loop = register_primitive("scan_loop", _impl_scan_loop, vararg=True)

switch = register_primitive("switch", _impl_switch)
stop_gradient = register_primitive("stop_gradient", _impl_stop_gradient)

make_tuple = register_primitive("make_tuple", lambda *xs: tuple(xs), vararg=True, bprop=None)
tuple_getitem = register_primitive("tuple_getitem", _impl_tuple_getitem)
tuple_setitem = register_primitive("tuple_setitem", _impl_tuple_setitem)
tuple_len = register_primitive("tuple_len", lambda t: len(t), bprop="zeros")

gadd = register_primitive("gadd", gadd_values)
zeros_like = register_primitive("zeros_like", zeros_like_value)

env_setitem = register_primitive("env_setitem", _impl_env_setitem)
env_getitem = register_primitive("env_getitem", _impl_env_getitem)

# ===========================================================================
# Backpropagator definitions (Myia-subset Python; parsed, never executed).
# Signature: (args..., out, dout) -> tuple of gradients w.r.t. args.
# ===========================================================================


def _bprop_add(x, y, out, dout):
    return (unbroadcast(dout, shape(x)), unbroadcast(dout, shape(y)))


def _bprop_sub(x, y, out, dout):
    return (unbroadcast(dout, shape(x)), unbroadcast(neg(dout), shape(y)))


def _bprop_mul(x, y, out, dout):
    return (unbroadcast(mul(dout, y), shape(x)), unbroadcast(mul(dout, x), shape(y)))


def _bprop_div(x, y, out, dout):
    return (
        unbroadcast(div(dout, y), shape(x)),
        unbroadcast(neg(div(mul(dout, x), mul(y, y))), shape(y)),
    )


def _bprop_power(x, y, out, dout):
    return (
        unbroadcast(mul(dout, mul(y, power(x, sub(y, 1)))), shape(x)),
        unbroadcast(mul(dout, mul(out, log(x))), shape(y)),
    )


def _bprop_integer_pow(x, n, out, dout):
    # no log term: safe for negative bases (cf. jax.lax.integer_pow)
    return (mul(dout, mul(n, integer_pow(x, sub(n, 1)))), zeros_like(n))


def _bprop_neg(x, out, dout):
    return (neg(dout),)


def _bprop_exp(x, out, dout):
    return (mul(dout, out),)


def _bprop_log(x, out, dout):
    return (div(dout, x),)


def _bprop_tanh(x, out, dout):
    return (mul(dout, sub(1.0, mul(out, out))),)


def _bprop_sigmoid(x, out, dout):
    return (mul(dout, mul(out, sub(1.0, out))),)


def _bprop_softplus(x, out, dout):
    return (mul(dout, sigmoid(x)),)


def _bprop_relu(x, out, dout):
    return (mul(dout, cast(gt(x, 0), dtype_of(dout))),)


def _bprop_sqrt(x, out, dout):
    return (div(mul(dout, 0.5), out),)


def _bprop_rsqrt(x, out, dout):
    return (div(mul(mul(dout, -0.5), out), x),)


def _bprop_sin(x, out, dout):
    return (mul(dout, cos(x)),)


def _bprop_cos(x, out, dout):
    return (neg(mul(dout, sin(x))),)


def _bprop_square(x, out, dout):
    return (mul(dout, mul(2.0, x)),)


def _bprop_absolute(x, out, dout):
    return (mul(dout, sign(x)),)


def _bprop_erf(x, out, dout):
    return (mul(dout, mul(1.1283791670955126, exp(neg(mul(x, x))))),)


def _bprop_maximum(x, y, out, dout):
    return (
        unbroadcast(mul(dout, cast(ge(x, y), dtype_of(dout))), shape(x)),
        unbroadcast(mul(dout, cast(lt(x, y), dtype_of(dout))), shape(y)),
    )


def _bprop_minimum(x, y, out, dout):
    return (
        unbroadcast(mul(dout, cast(le(x, y), dtype_of(dout))), shape(x)),
        unbroadcast(mul(dout, cast(gt(x, y), dtype_of(dout))), shape(y)),
    )


def _bprop_where(c, a, b, out, dout):
    return (
        zeros_like(c),
        unbroadcast(mul(dout, cast(c, dtype_of(dout))), shape(a)),
        unbroadcast(mul(dout, cast(bool_not(c), dtype_of(dout))), shape(b)),
    )


def _bprop_matmul(a, b, out, dout):
    return (
        unbroadcast(matmul(dout, mT(b)), shape(a)),
        unbroadcast(matmul(mT(a), dout), shape(b)),
    )


def _bprop_mT(x, out, dout):
    return (mT(dout),)


def _bprop_transpose(x, perm, out, dout):
    return (transpose(dout, invert_permutation(perm)), zeros_like(perm))


def _bprop_reshape(x, shp, out, dout):
    return (reshape(dout, shape(x)), zeros_like(shp))


def _bprop_broadcast_to(x, shp, out, dout):
    return (unbroadcast(dout, shape(x)), zeros_like(shp))


def _bprop_unbroadcast(x, shp, out, dout):
    return (broadcast_to(dout, shape(x)), zeros_like(shp))


def _bprop_reduce_sum(x, axes, keepdims, out, dout):
    return (unreduce(dout, shape(x), axes, keepdims), zeros_like(axes), zeros_like(keepdims))


def _bprop_unreduce(x, shp, axes, keepdims, out, dout):
    return (
        reduce_sum(dout, axes, keepdims),
        zeros_like(shp),
        zeros_like(axes),
        zeros_like(keepdims),
    )


def _bprop_reduce_max(x, axes, keepdims, out, dout):
    m = cast(eq(x, unreduce(out, shape(x), axes, keepdims)), dtype_of(dout))
    cnt = reduce_sum(m, axes, keepdims)
    return (
        mul(m, unreduce(div(dout, cnt), shape(x), axes, keepdims)),
        zeros_like(axes),
        zeros_like(keepdims),
    )


def _bprop_cast(x, dtype, out, dout):
    return (cast(dout, dtype_of(x)), zeros_like(dtype))


def _bprop_take(x, idx, out, dout):
    return (index_add(zeros_like(x), idx, dout), zeros_like(idx))


def _bprop_index_add(base, idx, val, out, dout):
    return (dout, zeros_like(idx), take(dout, idx))


def _bprop_slice_axis(x, axis, start, stop, out, dout):
    total = tuple_getitem(shape(x), axis)
    return (
        pad_zeros_axis(dout, axis, start, sub(total, stop)),
        zeros_like(axis),
        zeros_like(start),
        zeros_like(stop),
    )


def _bprop_pad_zeros_axis(x, axis, before, after, out, dout):
    n = tuple_getitem(shape(x), axis)
    return (
        slice_axis(dout, axis, before, add(before, n)),
        zeros_like(axis),
        zeros_like(before),
        zeros_like(after),
    )


def _bprop_concat_axis(xs, axis, out, dout):
    return (concat_grad(xs, axis, dout), zeros_like(axis))


def _bprop_concat_grad(xs, axis, dout_in, out, dout):
    return (zeros_like(xs), zeros_like(axis), concat_axis(dout, axis))


def _bprop_cumsum(x, axis, reverse, out, dout):
    return (cumsum(dout, axis, bool_not(reverse)), zeros_like(axis), zeros_like(reverse))


def _bprop_switch(c, t, f, out, dout):
    return (zeros_like(c), switch(c, dout, zeros_like(t)), switch(c, zeros_like(f), dout))


def _bprop_stop_gradient(x, out, dout):
    return (zeros_like(x),)


def _bprop_gadd(x, y, out, dout):
    return (dout, dout)


def _bprop_zeros_like(x, out, dout):
    return (zeros_like(x),)


def _bprop_tuple_getitem(t, i, out, dout):
    return (tuple_setitem(zeros_like(t), i, dout), zeros_like(i))


def _bprop_tuple_setitem(t, i, v, out, dout):
    return (tuple_setitem(dout, i, zeros_like(v)), zeros_like(i), tuple_getitem(dout, i))


def _bprop_env_setitem(env, key, val, out, dout):
    return (
        env_setitem(dout, key, zeros_like(val)),
        zeros_like(key),
        env_getitem(dout, key, zeros_like(val)),
    )


def _bprop_env_getitem(env, key, default, out, dout):
    return (
        env_setitem(zeros_like(env), key, dout),
        zeros_like(key),
        zeros_like(default),
    )


_BPROPS = {
    "add": _bprop_add,
    "sub": _bprop_sub,
    "mul": _bprop_mul,
    "div": _bprop_div,
    "power": _bprop_power,
    "integer_pow": _bprop_integer_pow,
    "neg": _bprop_neg,
    "exp": _bprop_exp,
    "log": _bprop_log,
    "tanh": _bprop_tanh,
    "sigmoid": _bprop_sigmoid,
    "softplus": _bprop_softplus,
    "relu": _bprop_relu,
    "sqrt": _bprop_sqrt,
    "rsqrt": _bprop_rsqrt,
    "sin": _bprop_sin,
    "cos": _bprop_cos,
    "square": _bprop_square,
    "absolute": _bprop_absolute,
    "erf": _bprop_erf,
    "maximum": _bprop_maximum,
    "minimum": _bprop_minimum,
    "where": _bprop_where,
    "matmul": _bprop_matmul,
    "mT": _bprop_mT,
    "transpose": _bprop_transpose,
    "reshape": _bprop_reshape,
    "broadcast_to": _bprop_broadcast_to,
    "unbroadcast": _bprop_unbroadcast,
    "reduce_sum": _bprop_reduce_sum,
    "unreduce": _bprop_unreduce,
    "reduce_max": _bprop_reduce_max,
    "cast": _bprop_cast,
    "take": _bprop_take,
    "index_add": _bprop_index_add,
    "slice_axis": _bprop_slice_axis,
    "pad_zeros_axis": _bprop_pad_zeros_axis,
    "concat_axis": _bprop_concat_axis,
    "concat_grad": _bprop_concat_grad,
    "cumsum": _bprop_cumsum,
    "switch": _bprop_switch,
    "stop_gradient": _bprop_stop_gradient,
    "gadd": _bprop_gadd,
    "zeros_like": _bprop_zeros_like,
    "tuple_getitem": _bprop_tuple_getitem,
    "tuple_setitem": _bprop_tuple_setitem,
    "env_setitem": _bprop_env_setitem,
    "env_getitem": _bprop_env_getitem,
}

for _name, _fn in _BPROPS.items():
    PRIMITIVES[_name].bprop = _fn
