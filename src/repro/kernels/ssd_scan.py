"""Mamba-2 SSD (state-space duality) chunked-scan Pallas kernel.

TPU adaptation (DESIGN.md §3): the sequence is split into chunks of length
``L``.  Within a chunk the recurrence is *dualized* into attention-like
matmuls (MXU work); across chunks only the small (N × P) state is carried
— in VMEM scratch across sequential grid steps, exactly like the flash-
attention online-softmax carry.

Per chunk (head h, group g = h // (H/G)), with a_t = A_h·dt_t and
``cum`` the inclusive cumsum of a over the chunk:

    intra:   y_i += Σ_{j≤i} (C_i·B_j) · exp(cum_i − cum_j) · dt_j · x_j
    inter:   y_i += exp(cum_i) · C_i · h_in
    state:   h_out = exp(cum_L) · h_in + Σ_j exp(cum_L − cum_j) · dt_j · B_j ⊗ x_j

All three are (L×N)@(N×L/P) matmuls — MXU-aligned for L, N, P multiples
of 128 (P=64 heads still fill half the MXU; acceptable).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, hT_ref, h_ref):
    h_idx = pl.program_id(1)
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[...].astype(jnp.float32)  # (L, P)
    dt_col = dt_ref[...].astype(jnp.float32)  # (L, 1)
    a = a_ref[h_idx]  # () from SMEM
    bm = b_ref[...].astype(jnp.float32)  # (L, N)
    cm = c_ref[...].astype(jnp.float32)  # (L, N)

    # The chunk's cumulative log-decay, as a column and as a row.  Mosaic
    # has no cumsum or (L,1)<->(1,L) transpose, so both are masked
    # reductions over an (L, L) broadcast.
    L = x.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    diag = rows == cols
    dt_row = jnp.sum(jnp.where(diag, jnp.broadcast_to(dt_col, (L, L)), 0.0), axis=0, keepdims=True)
    da_col = a * dt_col  # (L, 1) log-decay increments (a < 0)
    da_row = a * dt_row  # (1, L)
    cum_col = jnp.sum(  # (L, 1): inclusive cumsum, cum_i = Σ_{j≤i} da_j
        jnp.where(cols <= rows, jnp.broadcast_to(da_row, (L, L)), 0.0), axis=1, keepdims=True
    )
    cum_row = jnp.sum(  # (1, L): the same values laid out as a row
        jnp.where(rows <= cols, jnp.broadcast_to(da_col, (L, L)), 0.0), axis=0, keepdims=True
    )
    total = jnp.sum(da_row, axis=1, keepdims=True)  # (1, 1) = cum_{L-1}

    # -- intra-chunk (dual / attention-like form) ---------------------------
    s = jax.lax.dot_general(
        cm, bm, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (L, L):  C_i · B_j
    seg = cum_col - cum_row  # log decay j→i
    decay = jnp.where(rows >= cols, jnp.exp(seg), 0.0)
    s = s * decay * dt_row
    y = jax.lax.dot_general(
        s, x, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (L, P)

    # -- inter-chunk: carried state contribution ----------------------------
    h = h_ref[...]  # (N, P) f32
    y = y + jnp.exp(cum_col) * jax.lax.dot_general(
        cm, h, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    # -- state update --------------------------------------------------------
    w = jnp.exp(total - cum_col) * dt_col  # (L, 1)
    h_new = jnp.exp(total) * h + jax.lax.dot_general(
        bm * w, x, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (N, P)
    h_ref[...] = h_new

    y_ref[...] = y.astype(y_ref.dtype)

    @pl.when(ci == nc - 1)
    def _emit_state():
        hT_ref[...] = h_new


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan_fwd(
    x: jax.Array,
    dt: jax.Array,
    A: jax.Array,
    B: jax.Array,
    C: jax.Array,
    *,
    chunk: int = 128,
    interpret: bool,
) -> tuple[jax.Array, jax.Array]:
    """x: (Bt,S,H,P); dt: (Bt,S,H); A: (H,); B,C: (Bt,S,G,N).

    Returns (y: (Bt,S,H,P) in x.dtype, final_state: (Bt,H,N,P) f32).

    The kernel runs head-major: the wrapper moves the head axis ahead of
    the sequence so every block is a ``(chunk, P)``/``(chunk, N)`` tile
    (``chunk`` a multiple of 8, or the whole sequence), ``dt`` travels as
    a ``(chunk, 1)`` column and ``A`` sits whole in SMEM."""
    Bt, S, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    assert H % G == 0, (H, G)
    rep = H // G
    L = min(chunk, S)
    assert S % L == 0, (S, L)

    xh = jnp.transpose(x, (0, 2, 1, 3))  # (Bt, H, S, P)
    dth = jnp.transpose(dt, (0, 2, 1))[..., None]  # (Bt, H, S, 1)
    Bh = jnp.transpose(B, (0, 2, 1, 3))  # (Bt, G, S, N)
    Ch = jnp.transpose(C, (0, 2, 1, 3))

    grid = (Bt, H, S // L)
    kwargs = {}
    if not interpret:  # pragma: no cover - requires TPU
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        )
    y, hT = pl.pallas_call(
        _ssd_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, L, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((None, None, L, 1), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((None, None, L, N), lambda b, h, c, r=rep: (b, h // r, c, 0)),
            pl.BlockSpec((None, None, L, N), lambda b, h, c, r=rep: (b, h // r, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, L, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((None, None, N, P), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bt, H, S, P), x.dtype),
            jax.ShapeDtypeStruct((Bt, H, N, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        interpret=interpret,
        name="ssd_scan_fwd",
        **kwargs,
    )(xh, dth, A.astype(jnp.float32), Bh, Ch)
    return jnp.transpose(y, (0, 2, 1, 3)), hT
