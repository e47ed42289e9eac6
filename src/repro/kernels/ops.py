"""Public kernel API: dispatch + AD + Myia primitive registration.

Each op has three interchangeable implementations selected by
:func:`set_kernel_mode` (or a per-call ``impl=`` override):

* ``"ref"``              — the pure-jnp oracle (the default off a TPU;
                            what CPU tests and the dry-run execute),
* ``"pallas_interpret"`` — the Pallas TPU kernel executed by the Pallas
                            interpreter (correctness validation on CPU;
                            only ever chosen explicitly),
* ``"pallas"``           — the compiled Pallas TPU kernel (the default on
                            a TPU backend).

AD: every op is a ``jax.custom_vjp``.  Backward passes recompute from the
reference formulas (flash-attention/SSD) or run the dedicated Pallas
backward kernel (rmsnorm).  The ops are ALSO registered as *Myia
primitives* with hand-written backpropagators — the paper's "write
efficient low-level kernels and their derivatives in a low-level language,
and expose them to Myia as primitives" (§3, Myia's intended use case).
"""

from __future__ import annotations

import functools
import os

import jax

from repro.core.primitives import register_primitive, zeros_like
from . import ref
from .flash_attention import flash_attention_fwd
from .rmsnorm import rmsnorm_bwd, rmsnorm_fwd
from .ssd_scan import ssd_scan_fwd

__all__ = [
    "set_kernel_mode",
    "get_kernel_mode",
    "flash_attention",
    "rmsnorm",
    "ssd_scan",
    "ssd_step",
]

_MODES = ("ref", "chunked", "pallas_interpret", "pallas")


def _validate(mode: str) -> str:
    """Invalid values fail loudly — a typo'd CI matrix entry must not
    silently green the ref path."""
    if mode not in _MODES:
        raise ValueError(f"MYIA_KERNEL_MODE must be one of {_MODES}, got {mode!r}")
    return mode


# ``MYIA_KERNEL_MODE`` (the CI matrix axis: the fast job runs ``ref``, the
# full job also ``pallas_interpret``) is re-read on every query, so a
# process that changes the environment afterwards — the serve engine
# flipping modes between workloads, or a test driving the mode matrix
# in-process — never keeps a stale mode: a *change* to it takes effect
# immediately, while an explicit ``set_kernel_mode`` wins until the env
# var next changes.  With neither, the mode follows the backend.
_ENV_SEEN = os.environ.get("MYIA_KERNEL_MODE")
# validate the RAW value when the var is set: an empty/typo'd CI matrix
# expansion must fail loudly, not silently green the ref path
_MODE: str | None = None if _ENV_SEEN is None else _validate(_ENV_SEEN)


def set_kernel_mode(mode: str) -> None:
    global _MODE, _ENV_SEEN
    _MODE = _validate(mode)
    # sync the watermark: a later env-var CHANGE still overrides
    _ENV_SEEN = os.environ.get("MYIA_KERNEL_MODE")


def get_kernel_mode() -> str:
    """The explicit choice (``set_kernel_mode`` / ``MYIA_KERNEL_MODE``),
    else ``"pallas"`` on a TPU backend and ``"ref"`` anywhere else — so
    a chip never silently runs the jnp oracle in place of the kernels."""
    global _MODE, _ENV_SEEN
    env = os.environ.get("MYIA_KERNEL_MODE")
    if env != _ENV_SEEN:
        if env is not None:
            # validate BEFORE moving the watermark: a typo'd value keeps
            # failing on every query instead of raising once and going quiet
            _MODE = _validate(env)
        _ENV_SEEN = env
    if _MODE is None:
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    return _MODE


def _resolve(impl: str | None) -> str:
    return impl if impl is not None else get_kernel_mode()


# ===========================================================================
# flash attention
# ===========================================================================


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_attention(q, k, v, causal, window, sm_scale, impl):
    return _flash_fwd_dispatch(q, k, v, causal, window, sm_scale, impl)


def _flash_fwd_dispatch(q, k, v, causal, window, sm_scale, impl):
    if impl == "ref":
        return ref.flash_attention_ref(q, k, v, causal=causal, window=window, sm_scale=sm_scale)
    if impl == "chunked":
        return ref.flash_attention_ref_chunked(
            q, k, v, causal=causal, window=window, sm_scale=sm_scale
        )
    return flash_attention_fwd(
        q, k, v, causal=causal, window=window, sm_scale=sm_scale,
        interpret=(impl == "pallas_interpret"),
    )


def _flash_fwd_vjp(q, k, v, causal, window, sm_scale, impl):
    if impl in ("chunked", "pallas", "pallas_interpret"):
        # chunked/flash backward needs (o, lse) residuals
        o, lse = ref.flash_attention_fwd_lse_chunked(
            q, k, v, causal=causal, window=window, sm_scale=sm_scale
        )
        if impl != "chunked":  # the kernel produces o; lse from the twin
            o = _flash_fwd_dispatch(q, k, v, causal, window, sm_scale, impl)
        return o, (q, k, v, o, lse)
    return _flash_fwd_dispatch(q, k, v, causal, window, sm_scale, impl), (q, k, v)


def _flash_bwd_vjp(causal, window, sm_scale, impl, res, dout):
    if impl in ("chunked", "pallas", "pallas_interpret"):
        q, k, v, o, lse = res
        return ref.flash_attention_bwd_chunked(
            q, k, v, o, lse, dout, causal=causal, window=window, sm_scale=sm_scale
        )
    q, k, v = res
    # naive recompute backward (paper-faithful baseline): materializes the
    # O(S²) score matrix — the §Perf hillclimb replaces it with the
    # chunked backward above
    _, vjp_fn = jax.vjp(
        lambda q_, k_, v_: ref.flash_attention_ref(
            q_, k_, v_, causal=causal, window=window, sm_scale=sm_scale
        ),
        q, k, v,
    )
    return vjp_fn(dout)


_flash_attention.defvjp(_flash_fwd_vjp, _flash_bwd_vjp)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    window: int | None = None,
    sm_scale: float | None = None,
    impl: str | None = None,
) -> jax.Array:
    """GQA attention. q: (B,H,Sq,D); k,v: (B,KVH,Skv,D) → (B,H,Sq,D)."""
    scale = float(sm_scale) if sm_scale is not None else 1.0 / (q.shape[-1] ** 0.5)
    return _flash_attention(q, k, v, bool(causal), window, scale, _resolve(impl))


# ===========================================================================
# rmsnorm
# ===========================================================================


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _rmsnorm(x, w, eps, impl):
    if impl in ("ref", "chunked"):
        return ref.rmsnorm_ref(x, w, eps)
    return rmsnorm_fwd(x, w, eps=eps, interpret=(impl == "pallas_interpret"))


def _rmsnorm_fwd_vjp(x, w, eps, impl):
    return _rmsnorm(x, w, eps, impl), (x, w)


def _rmsnorm_bwd_vjp(eps, impl, res, dy):
    x, w = res
    if impl in ("ref", "chunked"):
        _, vjp_fn = jax.vjp(lambda x_, w_: ref.rmsnorm_ref(x_, w_, eps), x, w)
        return vjp_fn(dy)
    return rmsnorm_bwd(x, w, dy, eps=eps, interpret=(impl == "pallas_interpret"))


_rmsnorm.defvjp(_rmsnorm_fwd_vjp, _rmsnorm_bwd_vjp)


def rmsnorm(x: jax.Array, w: jax.Array, *, eps: float = 1e-6, impl: str | None = None) -> jax.Array:
    """Fused RMSNorm over the last axis."""
    return _rmsnorm(x, w, float(eps), _resolve(impl))


# ===========================================================================
# SSD scan (Mamba-2)
# ===========================================================================


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _ssd_scan_y(x, dt, A, B, C, impl):
    return _ssd_dispatch(x, dt, A, B, C, impl)[0]


#: SSD chunk length: 128 keeps the (L,L) intra-chunk matmuls MXU-aligned;
#: the bytes-vs-flops sweep (EXPERIMENTS.md §Perf) showed 64 within 0.3%
#: on bytes, so alignment wins the tie.
_SSD_CHUNK = int(os.environ.get("REPRO_SSD_CHUNK", "128"))


def _ssd_dispatch(x, dt, A, B, C, impl):
    if impl == "ref":
        return ref.ssd_scan_ref(x, dt, A, B, C)
    if impl == "chunked":
        return ref.ssd_scan_ref_chunked(x, dt, A, B, C, chunk=_SSD_CHUNK)
    return ssd_scan_fwd(x, dt, A, B, C, interpret=(impl == "pallas_interpret"))


def _ssd_fwd_vjp(x, dt, A, B, C, impl):
    return _ssd_scan_y(x, dt, A, B, C, impl), (x, dt, A, B, C)


def _ssd_bwd_vjp(impl, res, dy):
    x, dt, A, B, C = res
    if impl in ("chunked", "pallas", "pallas_interpret"):
        # vjp through the chunked form: residuals are per-CHUNK states
        # (S/L × N×P) instead of per-timestep (S × N×P)
        _, vjp_fn = jax.vjp(
            lambda *a: ref.ssd_scan_ref_chunked(*a, chunk=_SSD_CHUNK)[0], x, dt, A, B, C
        )
    else:
        _, vjp_fn = jax.vjp(lambda *a: ref.ssd_scan_ref(*a)[0], x, dt, A, B, C)
    return vjp_fn(dy)


_ssd_scan_y.defvjp(_ssd_fwd_vjp, _ssd_bwd_vjp)


def ssd_scan(
    x: jax.Array,
    dt: jax.Array,
    A: jax.Array,
    B: jax.Array,
    C: jax.Array,
    *,
    return_final_state: bool = False,
    impl: str | None = None,
):
    """Mamba-2 SSD over a sequence.  With ``return_final_state`` the call is
    NOT differentiable (serving path); the training path returns only y."""
    mode = _resolve(impl)
    if return_final_state:
        return _ssd_dispatch(x, dt, A, B, C, mode)
    return _ssd_scan_y(x, dt, A, B, C, mode)


def ssd_step(h, x_t, dt_t, A, B_t, C_t):
    """Single decode step (state carried explicitly; pure jnp — the state
    update is bandwidth-bound elementwise work, no kernel needed)."""
    return ref.ssd_step_ref(h, x_t, dt_t, A, B_t, C_t)


# ===========================================================================
# Myia primitive registration (paper §3: kernels as primitives with known
# backpropagators; bprops are Myia-subset functions, so reverse-over-reverse
# stays possible through *other* ops while kernel vjps terminate the chain).
# ===========================================================================


def _prim_flash_impl(q, k, v, causal, window, sm_scale):
    return flash_attention(q, k, v, causal=causal, window=window, sm_scale=sm_scale)


def _prim_flash_vjp_impl(q, k, v, causal, window, sm_scale, dout):
    scale = float(sm_scale) if sm_scale is not None else 1.0 / (q.shape[-1] ** 0.5)
    return _flash_bwd_vjp(bool(causal), window, scale, _resolve(None), (q, k, v), dout)


flash_attention_vjp = register_primitive(
    "flash_attention_vjp", _prim_flash_vjp_impl, bprop="zeros"
)


def _bprop_flash_attention(q, k, v, causal, window, sm_scale, out, dout):
    g = flash_attention_vjp(q, k, v, causal, window, sm_scale, dout)
    return (
        g[0],
        g[1],
        g[2],
        zeros_like(causal),
        zeros_like(window),
        zeros_like(sm_scale),
    )


flash_attention_prim = register_primitive(
    "flash_attention", _prim_flash_impl, bprop=_bprop_flash_attention
)


def _prim_rmsnorm_impl(x, w, eps):
    return rmsnorm(x, w, eps=eps)


def _prim_rmsnorm_vjp_impl(x, w, eps, dy):
    return _rmsnorm_bwd_vjp(float(eps), _resolve(None), (x, w), dy)


rmsnorm_vjp = register_primitive("rmsnorm_vjp", _prim_rmsnorm_vjp_impl, bprop="zeros")


def _bprop_rmsnorm(x, w, eps, out, dout):
    g = rmsnorm_vjp(x, w, eps, dout)
    return (g[0], g[1], zeros_like(eps))


rmsnorm_prim = register_primitive("rmsnorm", _prim_rmsnorm_impl, bprop=_bprop_rmsnorm)


def _prim_ssd_impl(x, dt, A, B, C):
    return ssd_scan(x, dt, A, B, C)


def _prim_ssd_vjp_impl(x, dt, A, B, C, dy):
    return _ssd_bwd_vjp(_resolve(None), (x, dt, A, B, C), dy)


ssd_scan_vjp = register_primitive("ssd_scan_vjp", _prim_ssd_vjp_impl, bprop="zeros")


def _bprop_ssd_scan(x, dt, A, B, C, out, dout):
    g = ssd_scan_vjp(x, dt, A, B, C, dout)
    return (g[0], g[1], g[2], g[3], g[4])


ssd_scan_prim = register_primitive("ssd_scan", _prim_ssd_impl, bprop=_bprop_ssd_scan)
