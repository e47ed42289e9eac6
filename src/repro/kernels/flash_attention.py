"""FlashAttention forward kernel, re-blocked for TPU (VMEM + MXU).

TPU adaptation of the GPU algorithm (DESIGN.md §3):

* The grid is ``(batch, q_heads, q_blocks, kv_blocks)`` with the KV axis
  innermost and *sequential* ("arbitrary" dimension semantics): the online-
  softmax running state (acc, m, l) lives in VMEM scratch and is carried
  across KV grid steps instead of a CUDA thread-block loop.
* Block shapes are multiples of the MXU tile (128 on the contracted and
  lane dims).  Per step the working set is q(bq×D) + k,v(bk×D) + acc —
  streamed HBM→VMEM by ``BlockSpec``; nothing quadratic is materialized.
* GQA is free at the ``index_map`` level: KV blocks are fetched with head
  index ``h // group`` so kv tensors are never physically repeated.
* Causal and sliding-window masking skip fully-masked KV blocks via
  ``pl.when`` (the MXU work is gated; block fetch still occurs — the XLA
  grid is static).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _fa_kernel(
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    acc_ref,
    m_ref,
    l_ref,
    *,
    sm_scale: float,
    causal: bool,
    window: int | None,
    block_q: int,
    block_k: int,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qi * block_q
    k_start = ki * block_k

    # Block-level visibility: skip the MXU work for fully-masked blocks.
    run = jnp.bool_(True)
    if causal:
        run &= k_start <= q_start + block_q - 1
    if window is not None:
        # newest visible column for the oldest row is q_start - window + 1
        run &= k_start + block_k - 1 >= q_start - window + 1

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32) * sm_scale  # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)  # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)  # (bk, D)

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (bq, bk)

        if causal or window is not None:
            rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            mask = jnp.ones((block_q, block_k), dtype=jnp.bool_)
            if causal:
                mask &= rows >= cols
            if window is not None:
                mask &= cols > rows - window
            s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[...]  # (bq, 1)
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)  # (bq, bk)
        l_ref[...] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_new
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc_ref[...] = acc_ref[...] * alpha + pv

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_ref[...]
        o = acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = o.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "sm_scale", "block_q", "block_k", "interpret"),
)
def flash_attention_fwd(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    window: int | None = None,
    sm_scale: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool,
) -> jax.Array:
    """q: (B, H, Sq, D); k, v: (B, KVH, Skv, D).  Returns (B, H, Sq, D)."""
    B, H, Sq, D = q.shape
    KVH, Skv = k.shape[1], k.shape[2]
    assert H % KVH == 0, (H, KVH)
    group = H // KVH
    block_q = min(block_q, Sq)
    block_k = min(block_k, Skv)
    assert Sq % block_q == 0 and Skv % block_k == 0, (Sq, Skv, block_q, block_k)
    scale = sm_scale if sm_scale is not None else 1.0 / (D**0.5)

    grid = (B, H, Sq // block_q, Skv // block_k)
    kernel = functools.partial(
        _fa_kernel,
        sm_scale=scale,
        causal=causal,
        window=window,
        block_q=block_q,
        block_k=block_k,
    )
    kwargs = {}
    if not interpret:  # pragma: no cover - requires TPU
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")
        )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, i, j, g=group: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, i, j, g=group: (b, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_fwd",
        **kwargs,
    )(q, k, v)
