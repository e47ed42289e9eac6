"""Fused RMSNorm Pallas kernels (forward + backward).

One HBM round-trip per tensor: rows are tiled ``block_rows`` at a time into
VMEM, the f32 mean-square/rsqrt is computed in-register, and the scaled
output is written back in the input dtype.  The backward kernel emits
``dx`` plus a per-block partial ``dw`` (summed by the caller) so no
cross-block communication is needed inside the kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _fwd_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)  # (br, D)
    w = w_ref[...].astype(jnp.float32)  # (1, D)
    var = jnp.mean(jnp.square(x), axis=1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(var + eps) * w).astype(o_ref.dtype)


def _bwd_kernel(x_ref, w_ref, dy_ref, dx_ref, dwp_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)  # (br, D)
    w = w_ref[...].astype(jnp.float32)  # (1, D)
    dy = dy_ref[...].astype(jnp.float32)  # (br, D)
    D = x.shape[1]
    var = jnp.mean(jnp.square(x), axis=1, keepdims=True)
    r = jax.lax.rsqrt(var + eps)  # (br, 1)
    dyw = dy * w
    # dx = r·dy·w − x·r³·mean(dy·w·x)
    proj = jnp.sum(dyw * x, axis=1, keepdims=True) / D
    dx_ref[...] = (r * dyw - x * (r * r * r) * proj).astype(dx_ref.dtype)
    dwp_ref[...] = jnp.sum(dy * x * r, axis=0, keepdims=True)  # (1, D) f32


@functools.partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def rmsnorm_fwd(
    x: jax.Array,
    w: jax.Array,
    *,
    eps: float = 1e-6,
    block_rows: int = 256,
    interpret: bool,
) -> jax.Array:
    """x: (..., D); w: (D,).  Returns same shape/dtype as x."""
    orig_shape = x.shape
    D = orig_shape[-1]
    x2 = x.reshape(-1, D)
    R = x2.shape[0]
    br = min(block_rows, R)
    assert R % br == 0, (R, br)
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps),
        grid=(R // br,),
        in_specs=[
            pl.BlockSpec((br, D), lambda i: (i, 0)),
            pl.BlockSpec((1, D), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((br, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, D), x.dtype),
        interpret=interpret,
        name="rmsnorm_fwd",
    )(x2, w.reshape(1, D))
    return out.reshape(orig_shape)


@functools.partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def rmsnorm_bwd(
    x: jax.Array,
    w: jax.Array,
    dy: jax.Array,
    *,
    eps: float = 1e-6,
    block_rows: int = 256,
    interpret: bool,
) -> tuple[jax.Array, jax.Array]:
    """Returns (dx, dw) with dx in x.dtype and dw in w.dtype."""
    orig_shape = x.shape
    D = orig_shape[-1]
    x2 = x.reshape(-1, D)
    dy2 = dy.reshape(-1, D)
    R = x2.shape[0]
    br = min(block_rows, R)
    assert R % br == 0, (R, br)
    nb = R // br
    dx, dw_part = pl.pallas_call(
        functools.partial(_bwd_kernel, eps=eps),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((br, D), lambda i: (i, 0)),
            pl.BlockSpec((1, D), lambda i: (0, 0)),
            pl.BlockSpec((br, D), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, D), lambda i: (i, 0)),
            # (nb, 1, D) with a squeezed leading dim: the block's last two
            # dims are then the array's own, as Mosaic requires
            pl.BlockSpec((None, 1, D), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, D), x.dtype),
            jax.ShapeDtypeStruct((nb, 1, D), jnp.float32),
        ],
        interpret=interpret,
        name="rmsnorm_bwd",
    )(x2, w.reshape(1, D), dy2)
    return dx.reshape(orig_shape), jnp.sum(dw_part, axis=(0, 1)).astype(w.dtype)
