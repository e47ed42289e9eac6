"""Plain reference of the Granite 4.0-H hybrid (Mamba-2 + NoPE GQA), in
jax.numpy.

Per layer ``h + res * mixer(rmsnorm(h))``, then ``h + res * mlp(rmsnorm(h))``
with a SwiGLU MLP; the embedding times ``embedding_multiplier``, the tied
head's logits divided by ``logits_scaling``, mean next-token
cross-entropy, SGD steps over it.  The Mamba-2 mixer follows Granite's
``torch_forward``: in-projection to ``[z | xBC | dt]``, a width-4 causal
depthwise convolution and SiLU, ``dt = softplus(dt + dt_bias)``,
``A = -exp(A_log)``, the SSD in the chunked "minimal" form of
arXiv:2405.21060 (Listing 1: diagonal blocks, chunk states, the
inter-chunk recurrence as a segment-sum matrix, state to output) with a
stable segment sum, then ``+ D x``, the gated RMSNorm and the
out-projection.  Attention is materialised: ``softmax(q k^T *
attention_multiplier + causal mask) v`` with the KV heads repeated.

Departures from the published model: float32 where it ships bfloat16;
packed rows with no cross-document masking (synthetic traffic has no
documents).  It imports nothing of the program and takes nothing the
program made: the weights come from :func:`param_maker`, which the
harness also hands the program, and the parameter tuple is laid out as
the configuration file's ``stands_for`` program takes it: embedding,
final norm, the first Mamba run's 13 leaves (stacked on a layer axis),
the attention layer's 9, the second Mamba run's 13.

The matmuls run at the precision the configuration states
(``matmul_precision``).  The control computes the same loss with every
array in bfloat16 (float32 master weights, float32 update).  The loss
and gradient run one sequence at a time, each layer under
``jax.checkpoint``, so that ten layers of a 2,048-token row fit beside
the weights.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bench.compare import leaf_norms

#: the matmul precision each spelling in the configuration files stands for
PRECISION = {"highest": lax.Precision.HIGHEST, "default": None}
#: leaves of one Mamba-2 + MLP layer and of the attention + MLP layer
N_MAMBA, N_ATTENTION = 13, 9


def runs(cfg: dict) -> tuple[int, int]:
    """(Mamba layers before the attention layer, Mamba layers after it)."""
    types = list(cfg["layer_types"][: int(cfg["num_hidden_layers"])])
    at = types.index("attention")
    return at, len(types) - at - 1


def param_maker(cfg: dict):
    """The jitted key -> parameter tuple, float32, made on the device in one
    call; the initialisation the configuration's ``assumed.init`` states."""
    V, D, F = cfg["vocab_size"], cfg["hidden_size"], cfg["shared_intermediate_size"]
    H, P, N = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    DI, CONV = H * P, H * P + 2 * N
    KV = cfg["num_key_value_heads"] * (D // cfg["num_attention_heads"])
    std = float(cfg["init_std"])
    na, nb = runs(cfg)

    def mamba(key, n):
        ks = jax.random.split(key, 9)
        dt = jnp.exp(jax.random.uniform(ks[0], (n, H), jnp.float32, np.log(1e-3), np.log(1e-1)))
        dt = jnp.maximum(dt, 1e-4)
        return (
            jnp.ones((n, D), jnp.float32),
            jax.random.normal(ks[1], (n, D, DI + CONV + H), jnp.float32) * std,
            jax.random.uniform(ks[2], (n, 4, CONV), jnp.float32, -0.5, 0.5),
            jax.random.uniform(ks[3], (n, CONV), jnp.float32, -0.5, 0.5),
            dt + jnp.log(-jnp.expm1(-dt)),
            jnp.log(jax.random.uniform(ks[4], (n, H), jnp.float32, 1.0, 16.0)),
            jnp.ones((n, H), jnp.float32),
            jnp.ones((n, DI), jnp.float32),
            jax.random.normal(ks[5], (n, DI, D), jnp.float32) * std,
            jnp.ones((n, D), jnp.float32),
            jax.random.normal(ks[6], (n, D, F), jnp.float32) * std,
            jax.random.normal(ks[7], (n, D, F), jnp.float32) * std,
            jax.random.normal(ks[8], (n, F, D), jnp.float32) * std,
        )

    @jax.jit
    def make(key):
        k_emb, k_a, k_t, k_b = jax.random.split(key, 4)
        kt = jax.random.split(k_t, 7)
        attention = (
            jnp.ones((D,), jnp.float32),
            jax.random.normal(kt[0], (D, D), jnp.float32) * std,
            jax.random.normal(kt[1], (D, KV), jnp.float32) * std,
            jax.random.normal(kt[2], (D, KV), jnp.float32) * std,
            jax.random.normal(kt[3], (D, D), jnp.float32) * std,
            jnp.ones((D,), jnp.float32),
            jax.random.normal(kt[4], (D, F), jnp.float32) * std,
            jax.random.normal(kt[5], (D, F), jnp.float32) * std,
            jax.random.normal(kt[6], (F, D), jnp.float32) * std,
        )
        return (
            jax.random.normal(k_emb, (V, D), jnp.float32) * std,
            jnp.ones((D,), jnp.float32),
            *mamba(k_a, na),
            *attention,
            *mamba(k_b, nb),
        )

    return make


def _rmsnorm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _segsum(x):
    """(..., T) -> (..., T, T): sum_{k=j+1..i} x_k for j <= i, else -inf,
    summed from the masked terms (no difference of cumulative sums)."""
    T = x.shape[-1]
    xe = jnp.where(jnp.tril(jnp.ones((T, T), bool), -1), x[..., :, None], 0)
    return jnp.where(jnp.tril(jnp.ones((T, T), bool)), jnp.cumsum(xe, axis=-2), -jnp.inf)


def ssd(x, dt, A, B, C, chunk, precision=None):
    """x (b, l, h, p), dt (b, l, h), A (h,), B / C (b, l, n), one group:
    arXiv:2405.21060, Listing 1."""
    b, l, h, p = x.shape
    c = l // chunk
    X = (x * dt[..., None]).reshape(b, c, chunk, h, p)
    Ad = (dt * A).reshape(b, c, chunk, h).transpose(0, 3, 1, 2)
    Bc, Cc = B.reshape(b, c, chunk, -1), C.reshape(b, c, chunk, -1)
    cs = jnp.cumsum(Ad, -1)
    L = jnp.exp(_segsum(Ad))
    y_diag = jnp.einsum("bcln,bcsn,bhcls,bcshp->bclhp", Cc, Bc, L, X, precision=precision)
    decay = jnp.exp(cs[..., -1:] - cs)
    states = jnp.einsum("bcln,bhcl,bclhp->bchpn", Bc, decay, X, precision=precision)
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], axis=1)
    decay_chunk = jnp.exp(_segsum(jnp.pad(cs[..., -1], ((0, 0), (0, 0), (1, 0)))))
    states = jnp.einsum("bhzc,bchpn->bzhpn", decay_chunk, states, precision=precision)[:, :-1]
    y_off = jnp.einsum("bcln,bchpn,bhcl->bclhp", Cc, states, jnp.exp(cs), precision=precision)
    return (y_diag + y_off).reshape(b, l, h, p)


def _mlp(x, wg, wu, wd, precision):
    mm = lambda a, b: jnp.matmul(a, b, precision=precision)  # noqa: E731
    return mm(jax.nn.silu(mm(x, wg)) * mm(x, wu), wd)


def mamba_layer(cfg, h, p, precision=None):
    """One Mamba-2 + MLP layer; ``p`` its 13 leaves."""
    n1, w_in, cw, cb, dtb, alog, dsk, gn, w_out, n2, wg, wu, wd = p
    eps, res = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    H, P, N = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    DI = H * P
    b, l, _ = h.shape
    zxbcdt = jnp.matmul(_rmsnorm(h, n1, eps), w_in, precision=precision)
    z, xbc, dt = jnp.split(zxbcdt, [DI, 2 * DI + 2 * N], axis=-1)
    xp = jnp.pad(xbc, ((0, 0), (3, 0), (0, 0)))
    xbc = jax.nn.silu(sum(xp[:, k : k + l] * cw[k] for k in range(4)) + cb)
    xs, B, C = jnp.split(xbc, [DI, DI + N], axis=-1)
    xs = xs.reshape(b, l, H, P)
    dt = jax.nn.softplus(dt + dtb)
    A = -jnp.exp(alog)
    y = ssd(xs, dt, A, B, C, cfg["mamba_chunk_size"], precision) + xs * dsk[:, None]
    y = _rmsnorm(y.reshape(b, l, DI) * jax.nn.silu(z), gn, eps)
    h = h + res * jnp.matmul(y, w_out, precision=precision)
    return h + res * _mlp(_rmsnorm(h, n2, eps), wg, wu, wd, precision)


def attention_layer(cfg, h, p, precision=None):
    """The NoPE grouped-query attention + MLP layer; ``p`` its 9 leaves."""
    n1, wq, wk, wv, wo, n2, wg, wu, wd = p
    eps, res = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    nh, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    b, l, d = h.shape
    hd = d // nh
    mm = lambda a, c: jnp.matmul(a, c, precision=precision)  # noqa: E731
    x = _rmsnorm(h, n1, eps)
    q = mm(x, wq).reshape(b, l, nh, hd)
    k = jnp.repeat(mm(x, wk).reshape(b, l, kvh, hd), nh // kvh, axis=2)
    v = jnp.repeat(mm(x, wv).reshape(b, l, kvh, hd), nh // kvh, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=precision) * cfg["attention_multiplier"]
    s = jnp.where(jnp.tril(jnp.ones((l, l), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v, precision=precision)
    h = h + res * mm(o.reshape(b, l, d), wo)
    return h + res * _mlp(_rmsnorm(h, n2, eps), wg, wu, wd, precision)


def loss_sum(cfg, params, tokens, labels, *, dtype=jnp.float32, precision=None):
    """Sum over rows of the next-token cross-entropy, computed in ``dtype``,
    each layer under ``jax.checkpoint``."""
    p = tuple(x.astype(dtype) for x in params)
    na, nb = runs(cfg)
    emb, fnorm = p[0], p[1]
    run_a, att = p[2 : 2 + N_MAMBA], p[2 + N_MAMBA : 2 + N_MAMBA + N_ATTENTION]
    run_b = p[2 + N_MAMBA + N_ATTENTION :]
    mamba = jax.checkpoint(lambda h, w: mamba_layer(cfg, h, w, precision))
    attention = jax.checkpoint(lambda h, w: attention_layer(cfg, h, w, precision))
    h = jnp.take(emb, tokens, axis=0) * cfg["embedding_multiplier"]
    for i in range(na):
        h = mamba(h, tuple(w[i] for w in run_a))
    h = attention(h, att)
    for i in range(nb):
        h = mamba(h, tuple(w[i] for w in run_b))
    logits = jnp.matmul(_rmsnorm(h, fnorm, cfg["rms_norm_eps"]), emb.T, precision=precision)
    logp = jax.nn.log_softmax(logits / cfg["logits_scaling"], axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[..., None], axis=-1))


def mean_loss(params, tokens, labels, *, cfg, precision=None):
    """The loss as the program states it (float32, default precision) over
    the whole batch in one piece: the jnp copy that
    ``myia_over_jax.train_hybrid`` times against."""
    return loss_sum(cfg, params, tokens, labels, precision=precision) / tokens.size


def make_value_and_grad(cfg, *, dtype=jnp.float32, precision: str = "default"):
    """jitted (params, tokens (B, S), labels (B, S)) -> (mean loss, grads),
    one sequence at a time, accumulated in float32."""
    precision = PRECISION[precision]

    def row(params, tok, lab):
        return jax.value_and_grad(
            lambda p: loss_sum(cfg, p, tok[None], lab[None], dtype=dtype, precision=precision)
        )(params)

    @jax.jit
    def value_and_grad(params, tokens, labels):
        def body(carry, xs):
            acc_loss, acc_grads = carry
            loss, grads = row(params, *xs)
            acc_grads = tuple(a + g.astype(jnp.float32) for a, g in zip(acc_grads, grads))
            return (acc_loss + loss.astype(jnp.float32), acc_grads), None

        zeros = tuple(jnp.zeros(p.shape, jnp.float32) for p in params)
        (loss, grads), _ = lax.scan(body, (jnp.float32(0.0), zeros), (tokens, labels))
        return loss / tokens.size, tuple(g / tokens.size for g in grads)

    return value_and_grad


def sgd_readings(params0: tuple, batches, lr: float, *, cfg: dict, dtype=jnp.float32,
                 precision: str = "default") -> dict:
    """Follow SGD from ``params0`` over ``batches`` and read what the
    harness compares: each step's loss, the per-leaf norm of the first
    gradient as the update applied it, and the per-leaf norm of the
    parameters' change after the last step.

    ``params0`` goes to the host at once and the device keeps one set of
    weights, so the caller should hold no other reference to it: two sets
    of weights, the gradients and a row's activations would not fit."""
    vag = make_value_and_grad(cfg, dtype=dtype, precision=precision)
    start = jax.device_get(params0)

    @jax.jit
    def update(params, grads):
        return tuple(p - lr * g for p, g in zip(params, grads))

    params, losses, first = params0, [], None
    del params0
    for i, (tokens, labels) in enumerate(batches):
        loss, grads = vag(params, jnp.asarray(tokens), jnp.asarray(labels))
        new = update(params, grads)
        del grads
        if i == 0:
            first = np.asarray(leaf_norms(params, new)) / lr
        params = new
        losses.append(float(loss))
    change = [float(np.asarray(leaf_norms((p,), (jnp.asarray(s),)))[0])
              for p, s in zip(params, start)]
    return {
        "losses": losses,
        "first_grad_norms": [float(x) for x in first],
        "change_norms": change,
    }
