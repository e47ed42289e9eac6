"""Explicit collectives for distributed optimization (shard_map level).

* :func:`int8_allreduce` — bandwidth-compressed gradient all-reduce with
  error feedback: 4× fewer wire bytes than f32 (2× vs bf16).  Two-phase
  reduce-scatter/all-gather, both phases carrying int8 on the wire with
  per-shard f32 scales; the stage-1 quantization error is returned for
  error-feedback accumulation (carried in the optimizer loop, so the bias
  vanishes over steps).
* :func:`ring_reduce_scatter_matmul` — collective matmul: y = x·W with
  both operands sharded on the contraction dim; the reduce-scatter is
  unrolled into a ring of ``ppermute`` steps, each overlapped with one
  row-block partial matmul — the compute/communication-overlap trick
  XLA's async collectives perform, expressed manually so the schedule is
  explicit and tunable.

Both are used through ``jax.shard_map`` and verified numerically on a
host-device mesh (tests/distributed).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["int8_allreduce", "ring_reduce_scatter_matmul", "compressed_psum_grads"]


def _quantize(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    scale = jnp.max(jnp.abs(x)) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def int8_allreduce(
    x: jax.Array, axis_name: str, err: jax.Array | None = None
) -> tuple[jax.Array, jax.Array]:
    """All-reduce ``x`` (identical shape per device) over ``axis_name``
    with int8 wire traffic.  Returns (reduced, new_error_feedback).

    Phase 1 (reduce-scatter): quantize locally, ``all_to_all`` int8 so
    device d receives everyone's d-th chunk, dequantize+sum.
    Phase 2 (all-gather): re-quantize the reduced chunk, ``all_gather``
    int8 + scales, dequantize.
    """
    n = jax.lax.axis_size(axis_name)
    orig_shape = x.shape
    xf = x.reshape(-1).astype(jnp.float32)
    if err is not None:
        xf = xf + err.reshape(-1)
    pad = (-xf.size) % n
    if pad:
        xf = jnp.concatenate([xf, jnp.zeros((pad,), xf.dtype)])

    q, scale = _quantize(xf)
    new_err = xf - q.astype(jnp.float32) * scale  # stage-1 EF residual

    chunks = q.reshape(n, -1)  # (n, chunk)
    recv = jax.lax.all_to_all(chunks, axis_name, split_axis=0, concat_axis=0, tiled=False)
    scales = jax.lax.all_gather(scale, axis_name)  # (n,)
    partial = jnp.sum(recv.astype(jnp.float32) * scales[:, None], axis=0)  # (chunk,)

    q2, s2 = _quantize(partial)
    qs = jax.lax.all_gather(q2, axis_name)  # (n, chunk)
    ss = jax.lax.all_gather(s2, axis_name)  # (n,)
    out = (qs.astype(jnp.float32) * ss[:, None]).reshape(-1)
    if pad:
        out = out[:-pad]
        new_err = new_err[:-pad]
    return out.reshape(orig_shape).astype(x.dtype), new_err.reshape(orig_shape)


def ring_reduce_scatter_matmul(
    x_shard: jax.Array, w_shard: jax.Array, axis_name: str
) -> jax.Array:
    """Collective matmul (Megatron row-parallel with overlap):
    ``y = X @ W`` where X (m, K) and W (K, N) are both sharded on the
    contraction dim K.  Devices hold x_shard (m, K/n) and w_shard (K/n, N);
    the result is returned *row-sharded*: device d gets rows
    ``[d·m/n, (d+1)·m/n)`` of y, fully reduced.

    Instead of a monolithic partial-matmul + reduce-scatter, each ring
    step matmuls ONE row-block against the local W while the accumulator
    for another block is in flight (``ppermute``) — the transfer of step
    s hides behind the matmul of step s+1.
    """
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    m = x_shard.shape[0]
    assert m % n == 0, (m, n)
    mb = m // n
    perm = [(i, (i - 1) % n) for i in range(n)]  # accumulator moves "down"

    def body(s, acc):
        # the accumulator visiting this device at step s is the one that
        # finishes (after its remaining hops) at device (idx + s) % n —
        # contribute the local partial for that block, then pass it down.
        blk = (idx + s) % n
        rows = jax.lax.dynamic_slice_in_dim(x_shard, blk * mb, mb, axis=0)
        part = jax.lax.dot_general(
            rows, w_shard, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc = acc + part
        return jax.lax.ppermute(acc, axis_name, perm)

    acc0 = jax.lax.pcast(
        jnp.zeros((mb, w_shard.shape[1]), jnp.float32), (axis_name,), to="varying"
    )
    acc = jax.lax.fori_loop(0, n, body, acc0)
    return acc.astype(jnp.promote_types(x_shard.dtype, w_shard.dtype))


def compressed_psum_grads(grads, axis_name: str, errs=None):
    """Tree-wide int8 error-feedback all-reduce (mean) for gradients."""
    n = jax.lax.axis_size(axis_name)
    if errs is None:
        errs = jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.float32), grads)
    out = jax.tree.map(
        lambda g, e: int8_allreduce(g, axis_name, e), grads, errs
    )
    reduced = jax.tree.map(lambda o: o[0] / n, out, is_leaf=lambda x: isinstance(x, tuple))
    new_errs = jax.tree.map(lambda o: o[1], out, is_leaf=lambda x: isinstance(x, tuple))
    return reduced, new_errs
