"""The Granite 4.0-H hybrid written in the Myia subset (``launch/myia_hybrid``)
against a plain jax.numpy reference, at a tiny size on the CPU.

The reference below is the model side of ``bench/configs/granite4h_ref.py``
(kept here so the tests import nothing of the benchmark): float32, the
SSD in the chunked "minimal" form of arXiv:2405.21060 (Listing 1) with a
stable segment sum and no loop, attention materialised, gradients from
``jax.grad``.  Everything runs at ``precision="highest"``.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import api
from repro.core.ad import LoopAdjointStats, build_value_and_grad_graph
from repro.core.parser import parse_function
from repro.launch import myia_hybrid as mh
from repro.obs.trace import Tracer, tracing

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CFG = json.load(open(os.path.join(ROOT, "bench", "configs", "myia-granite4h-micro.json")))
#: hidden 64, 8 SSD heads of 16, state 16, chunk 8, 4 attention heads over
#: 2 KV heads, MLP 256, vocab 512; layers mamba, mamba, attention, mamba
TINY = dict(CFG, **CFG["tiny"])
BATCH, SEQ = 2, 32
HI = jax.lax.Precision.HIGHEST
#: program against reference, both float32 at highest precision: they
#: differ by summation order only (the SSD's chunk states pass in a loop
#: in one, in a segment-sum matrix in the other), a few float32 ulps of
#: each value; 1e-4 relative leaves room for ten layers of it
RTOL, ATOL = 1e-4, 1e-6


# -- the reference ------------------------------------------------------------


def ref_rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def ref_segsum(x):
    """(..., T) -> (..., T, T): sum_{k=j+1..i} x_k for j <= i, else -inf."""
    T = x.shape[-1]
    xe = jnp.where(jnp.tril(jnp.ones((T, T), bool), -1), x[..., :, None], 0.0)
    return jnp.where(jnp.tril(jnp.ones((T, T), bool)), jnp.cumsum(xe, axis=-2), -jnp.inf)


def ref_ssd(x, dt, A, B, C, chunk):
    """x (b, l, h, p), dt (b, l, h), A (h,), B / C (b, l, n): Listing 1."""
    b, l, h, p = x.shape
    c = l // chunk
    X = (x * dt[..., None]).reshape(b, c, chunk, h, p)
    Ad = (dt * A).reshape(b, c, chunk, h).transpose(0, 3, 1, 2)
    Bc, Cc = B.reshape(b, c, chunk, -1), C.reshape(b, c, chunk, -1)
    cs = jnp.cumsum(Ad, -1)
    L = jnp.exp(ref_segsum(Ad))
    y_diag = jnp.einsum("bcln,bcsn,bhcls,bcshp->bclhp", Cc, Bc, L, X, precision=HI)
    decay = jnp.exp(cs[..., -1:] - cs)
    states = jnp.einsum("bcln,bhcl,bclhp->bchpn", Bc, decay, X, precision=HI)
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], axis=1)
    decay_chunk = jnp.exp(ref_segsum(jnp.pad(cs[..., -1], ((0, 0), (0, 0), (1, 0)))))
    states = jnp.einsum("bhzc,bchpn->bzhpn", decay_chunk, states, precision=HI)[:, :-1]
    y_off = jnp.einsum("bcln,bchpn,bhcl->bclhp", Cc, states, jnp.exp(cs), precision=HI)
    return (y_diag + y_off).reshape(b, l, h, p)


def ref_recurrence(x, dt, A, B, C):
    """The SSM token by token: h_t = e^{dt_t A} h_{t-1} + dt_t x_t B_t^T,
    y_t = h_t C_t (the D x term is added outside the SSD)."""
    b, l, h, p = x.shape
    state = jnp.zeros((b, h, p, B.shape[-1]))
    ys = []
    for t in range(l):
        da = jnp.exp(dt[:, t] * A)[:, :, None, None]
        state = da * state + (dt[:, t, :, None] * x[:, t])[..., None] * B[:, t, None, None, :]
        ys.append(jnp.einsum("bhpn,bn->bhp", state, C[:, t], precision=HI))
    return jnp.stack(ys, 1)


def ref_mlp(x, wg, wu, wd):
    return jnp.matmul(jax.nn.silu(x @ wg) * (x @ wu), wd, precision=HI)


def ref_mamba_layer(cfg, h, p):
    (n1, w_in, cw, cb, dtb, alog, dsk, gn, w_out, n2, wg, wu, wd) = p
    eps, res = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    H, P, N = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    DI = H * P
    b, l, _ = h.shape
    x = ref_rmsnorm(h, n1, eps)
    zxbcdt = jnp.matmul(x, w_in, precision=HI)
    z, xbc, dt = jnp.split(zxbcdt, [DI, 2 * DI + 2 * N], axis=-1)
    xp = jnp.pad(xbc, ((0, 0), (3, 0), (0, 0)))
    xbc = jax.nn.silu(sum(xp[:, k : k + l] * cw[k] for k in range(4)) + cb)
    xs, B, C = jnp.split(xbc, [DI, DI + N], axis=-1)
    xs = xs.reshape(b, l, H, P)
    dt = jax.nn.softplus(dt + dtb)
    y = ref_ssd(xs, dt, -jnp.exp(alog), B, C, cfg["mamba_chunk_size"]) + xs * dsk[:, None]
    y = ref_rmsnorm(y.reshape(b, l, DI) * jax.nn.silu(z), gn, eps)
    h = h + res * jnp.matmul(y, w_out, precision=HI)
    return h + res * ref_mlp(ref_rmsnorm(h, n2, eps), wg, wu, wd)


def ref_attention_layer(cfg, h, p):
    n1, wq, wk, wv, wo, n2, wg, wu, wd = p
    eps, res = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    nh, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    b, l, d = h.shape
    hd = d // nh
    x = ref_rmsnorm(h, n1, eps)
    q = (x @ wq).reshape(b, l, nh, hd)
    k = jnp.repeat((x @ wk).reshape(b, l, kvh, hd), nh // kvh, axis=2)
    v = jnp.repeat((x @ wv).reshape(b, l, kvh, hd), nh // kvh, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) * cfg["attention_multiplier"]
    s = jnp.where(jnp.tril(jnp.ones((l, l), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v, precision=HI)
    h = h + res * jnp.matmul(o.reshape(b, l, d), wo, precision=HI)
    return h + res * ref_mlp(ref_rmsnorm(h, n2, eps), wg, wu, wd)


def ref_loss(cfg, params, tokens, labels, head=None):
    """Mean next-token cross-entropy; the head reads ``head`` where given
    (else the tied embedding, ``params[0]``)."""
    dims = mh.HybridDims(cfg)
    nm, na = len(mh.MAMBA_LEAVES), len(mh.ATTENTION_LEAVES)
    emb, fnorm = params[:2]
    run_a, att, run_b = params[2 : 2 + nm], params[2 + nm : 2 + nm + na], params[2 + nm + na :]
    h = emb[tokens] * cfg["embedding_multiplier"]
    for i in range(dims.runs[0]):
        h = ref_mamba_layer(cfg, h, [w[i] for w in run_a])
    h = ref_attention_layer(cfg, h, att)
    for i in range(dims.runs[1]):
        h = ref_mamba_layer(cfg, h, [w[i] for w in run_b])
    head = emb if head is None else head
    logits = jnp.matmul(ref_rmsnorm(h, fnorm, cfg["rms_norm_eps"]), head.T, precision=HI)
    logp = jax.nn.log_softmax(logits / cfg["logits_scaling"], -1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1))


# -- fixtures -------------------------------------------------------------------


@pytest.fixture(scope="module")
def dims():
    return mh.HybridDims(TINY)


@pytest.fixture(scope="module")
def params(dims):
    return mh.init_hybrid_params(dims, jax.random.PRNGKey(3), std=0.1)


@pytest.fixture(scope="module")
def batch():
    rng = np.random.default_rng(0)
    toks = rng.integers(0, TINY["vocab_size"], (BATCH, SEQ + 1)).astype(np.int32)
    return jnp.asarray(toks[:, :-1]), jnp.asarray(toks[:, 1:])


@pytest.fixture(scope="module")
def program(dims, params, batch):
    """The fused train step's loss+gradient and what set-up traced."""
    tracer = Tracer()
    step_fn, _ = mh.make_hybrid_train_step(dims, BATCH, SEQ, 0.1, fuse=True)
    with tracing(tracer), jax.default_matmul_precision("highest"):
        out = step_fn.vag(*params, *batch)
    return out, tracer


# -- tests ---------------------------------------------------------------------


def test_loss_and_every_gradient_match_the_reference(params, batch, program):
    (loss, grads), _ = program
    with jax.default_matmul_precision("highest"):
        want_loss, want = jax.value_and_grad(
            lambda p: ref_loss(TINY, p, *batch)
        )(params)
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=RTOL)
    assert len(grads) == len(want) == len(params)
    for i, (g, w) in enumerate(zip(grads, want)):
        scale = float(jnp.max(jnp.abs(w)))
        assert scale > 0, i
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=RTOL, atol=RTOL * scale, err_msg=f"leaf {i}"
        )


def test_chunked_ssd_matches_the_token_recurrence(dims):
    """The program's chunked SSD, loop of chunk states included, against
    the SSM run one token at a time."""
    rng = np.random.default_rng(1)
    H, Pd, N = dims.heads, dims.head_dim, dims.d_state
    x = jnp.asarray(rng.normal(size=(BATCH, SEQ, H, Pd)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.5, (BATCH, SEQ, H)), jnp.float32)
    A = -jnp.asarray(rng.uniform(1.0, 16.0, (H,)), jnp.float32)
    B = jnp.asarray(rng.normal(size=(BATCH, SEQ, N)), jnp.float32)
    C = jnp.asarray(rng.normal(size=(BATCH, SEQ, N)), jnp.float32)
    ssd = mh._blocks(dims, BATCH, SEQ)["ssd"]
    with jax.default_matmul_precision("highest"):
        got = api.myia(ssd)(x, dt, A, B, C)
        want = ref_recurrence(x, dt, A, B, C)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5)


def test_layer_loop_adjoint_matches_the_layers_unrolled(dims, params, batch):
    """The first Mamba run as a loop over stacked weights, and its
    gradient through the loop adjoint, against the same layers called
    one after another (no loop)."""
    blk = mh._blocks(dims, BATCH, SEQ)
    run_a, layer = blk["run_a"], blk["mamba_layer"]
    nm = len(mh.MAMBA_LEAVES)
    stacks = params[2 : 2 + nm]
    h = jnp.asarray(np.random.default_rng(2).normal(size=(BATCH, SEQ, dims.d_model)),
                    jnp.float32)
    rsum = mh._rsum

    def looped(h, n1, w_in, cw, cb, dtb, alog, dsk, gn, w_out, n2, wg, wu, wd):
        out = run_a(h, n1, w_in, cw, cb, dtb, alog, dsk, gn, w_out, n2, wg, wu, wd)
        return rsum(out, (0, 1, 2), False)

    def at(w, i):
        return mh._take(w, i)

    def unrolled(h, n1, w_in, cw, cb, dtb, alog, dsk, gn, w_out, n2, wg, wu, wd):
        h = layer(h, at(n1, 0), at(w_in, 0), at(cw, 0), at(cb, 0), at(dtb, 0), at(alog, 0),
                  at(dsk, 0), at(gn, 0), at(w_out, 0), at(n2, 0), at(wg, 0), at(wu, 0),
                  at(wd, 0))
        h = layer(h, at(n1, 1), at(w_in, 1), at(cw, 1), at(cb, 1), at(dtb, 1), at(alog, 1),
                  at(dsk, 1), at(gn, 1), at(w_out, 1), at(n2, 1), at(wg, 1), at(wu, 1),
                  at(wd, 1))
        return rsum(h, (0, 1, 2), False)

    assert dims.runs[0] == 2
    wrt = tuple(range(1 + nm))
    with jax.default_matmul_precision("highest"):
        got = api.value_and_grad(looped, wrt=wrt)(h, *stacks)
        want = api.value_and_grad(unrolled, wrt=wrt)(h, *stacks)
    np.testing.assert_allclose(float(got[0]), float(want[0]), rtol=1e-5)
    for g, w in zip(got[1], want[1]):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-4,
                                   atol=1e-4 * float(jnp.max(jnp.abs(w))))


def test_tied_embedding_gradient_is_take_plus_head(params, batch, program):
    """The embedding's gradient is the sum of its two uses: the lookup's
    rows and the head's matrix."""
    (_, grads), _ = program
    emb, rest = params[0], params[1:]

    def two_tables(e_take, e_head):
        return ref_loss(TINY, (e_take, *rest), *batch, head=e_head)

    with jax.default_matmul_precision("highest"):
        g_take, g_head = jax.grad(two_tables, argnums=(0, 1))(emb, emb)
    np.testing.assert_allclose(np.asarray(grads[0]), np.asarray(g_take + g_head),
                               rtol=RTOL, atol=RTOL * float(jnp.max(jnp.abs(g_head))))
    # the lookup touches only the rows of the tokens seen
    unseen = np.setdiff1d(np.arange(TINY["vocab_size"]), np.asarray(batch[0]))
    assert unseen.size and not np.asarray(g_take)[unseen].any()


def test_ad_span_records_the_loop_adjoints(dims, program):
    """Set-up's ``ad.grad`` span counts the loop adjoints built: the two
    layer loops, the chunk-state loop inside each of their bodies and the
    attention's loop over KV heads, and the bytes of their saved-carry
    stacks from static shapes."""
    _, tracer = program
    (sp,) = [e for e in tracer.events if e.kind == "span" and e.name == "ad.grad"]
    H, Pd, N, NC = dims.heads, dims.head_dim, dims.d_state, SEQ // dims.chunk
    KV, R, HD = dims.kv_heads, dims.att_heads // dims.kv_heads, dims.att_head_dim
    carry_h = 4 + BATCH * SEQ * dims.d_model * 4  # layer counter, hidden state
    chunk_carry = 4 + BATCH * H * Pd * N * 4 * (1 + NC)  # counter, state, states in
    heads_carry = 4 + KV * BATCH * R * SEQ * HD * 4  # counter, heads' outputs
    want = sum(n * carry_h + NC * chunk_carry for n in dims.runs) + KV * heads_carry
    assert sp.attrs["loops"] == 5
    assert sp.attrs["saved_carry_bytes"] == want


def test_saved_carry_bytes_of_a_small_scan():
    def fold(w, x):
        h = x
        for i in range(3):
            h = mh._sigmoid(h @ mh._take(w, i))
        return mh._rsum(h, (0, 1), False)

    w = jnp.ones((3, 8, 8), jnp.float32)
    x = jnp.ones((4, 8), jnp.float32)
    tracer = Tracer()
    with tracing(tracer):
        build_value_and_grad_graph(parse_function(fold), (0,), example_args=(w, x))
    (sp,) = tracer.find("ad.grad")
    # carries: the counter (int32) and h (4, 8) f32, three iterations each
    assert sp.attrs == {"graph": "fold", "loops": 1, "saved_carry_bytes": 3 * (4 + 4 * 8 * 4)}
    stats, loop = LoopAdjointStats(), parse_function(fold).return_
    for _ in range(2):  # one loop recorded twice counts once
        stats.record(loop, 3, [((), np.dtype("int32")), ((4, 8), np.dtype("float32"))])
    assert (stats.loops, stats.saved_carry_bytes) == (1, 3 * (4 + 128))
