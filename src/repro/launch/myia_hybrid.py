"""A hybrid Mamba-2 / attention LM written in the Myia subset.

The model is IBM's Granite 4.0-H (``granitemoehybrid`` with no experts):
layers of Mamba-2 mixers (state-space duality, SSD; arXiv:2405.21060) with
one grouped-query softmax attention layer per period, each followed by a
SwiGLU MLP, between a tied embedding and head.  Its loss+gradient runs
through the whole compiler (parse -> ST-AD -> infer -> optimize -> fuse
-> lower) like the tanh LM of ``launch/myia_step``, whose step builder
it shares.

Per layer, with Granite's multipliers::

    h = h + res * mixer(rmsnorm(h))
    h = h + res * mlp(rmsnorm(h)),   mlp(x) = (silu(x Wg) * (x Wu)) Wd

The Mamba-2 mixer projects ``[z | xBC | dt] = x W_in``, runs a depthwise
causal convolution of width 4 and SiLU over ``xBC``, splits it into
``x`` (heads of ``P``), ``B`` and ``C`` (one group of ``N``), and applies
the SSD with ``dt = softplus(dt + dt_bias)`` and ``A = -exp(A_log)``:
within each chunk of ``Q`` tokens ``Y_diag = (L o C B^T)(dt x)`` with
``L_ij = exp(sum_{k=j+1..i} dt_k A)`` for ``j <= i``; the chunk-end
states pass between chunks in a ``for`` loop (a ``scan_loop``), and
``Y_off = C state_in exp(cumsum)`` adds what earlier chunks left.  Then
``y + D x`` is gated, ``rmsnorm(y * silu(z)) g``, and projected out.

Attention has no positional encoding (NoPE): ``softmax(q k^T * scale +
causal mask) v`` with ``R`` query heads per KV head, the KV head
broadcast in the batched matmul, one KV head at a time in a ``for`` loop
so that one head's scores are live at once, in the backward pass too.

Consecutive Mamba layers run as one ``for`` loop over weights stacked on
a leading layer axis.  The loop adjoint (``core/ad.py``) saves only each
layer's incoming hidden state and recomputes the layer's internals in
the backward pass: the per-layer rematerialisation that lets the step
fit on one chip, with no ``jax.checkpoint`` in the program's path.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

import repro.core.primitives as P
from repro.launch.myia_step import make_train_step

__all__ = [
    "HybridDims",
    "MAMBA_LEAVES",
    "ATTENTION_LEAVES",
    "build_hybrid_loss",
    "build_mamba_layer_loss",
    "init_hybrid_params",
    "make_hybrid_train_step",
]

_take = P.take
_exp = P.exp
_log = P.log
_sigmoid = P.sigmoid
_softplus = P.softplus
_rsqrt = P.rsqrt
_rsum = P.reduce_sum
_rmax = P.reduce_max
_onehot = P.one_hot
_reshape = P.reshape
_transpose = P.transpose
_slice = P.slice_axis
_pad = P.pad_zeros_axis
_where = P.where
_ge = P.ge
_cumsum = P.cumsum
_mT = P.mT
_index_add = P.index_add
_zeros_like = P.zeros_like
_stop = P.stop_gradient
_F32 = np.dtype("float32")

#: the parameters of one Mamba-2 + MLP layer, in the order the loss takes
#: them (each stacked on a leading layer axis for a run of such layers)
MAMBA_LEAVES = (
    "norm1", "w_in", "conv_w", "conv_b", "dt_bias", "a_log", "d_skip", "gate_norm",
    "w_out", "norm2", "w_gate", "w_up", "w_down",
)
#: the parameters of the attention + MLP layer
ATTENTION_LEAVES = ("norm1", "w_q", "w_k", "w_v", "w_o", "norm2", "w_gate", "w_up", "w_down")

#: stands in for minus infinity under a mask: exp of it is 0, and it
#: keeps infinities (and the NaN of 0 * inf in an adjoint) out
_NEG = -1e30


class HybridDims:
    """The widths of a Granite 4.0-H configuration, from its ``config.json``
    keys.  The layer pattern (``layer_types[:num_hidden_layers]``) must be
    a run of Mamba layers, one attention layer, and a run of Mamba layers."""

    def __init__(self, cfg: dict) -> None:
        self.vocab = int(cfg["vocab_size"])
        self.d_model = int(cfg["hidden_size"])
        self.d_ff = int(cfg["shared_intermediate_size"])
        self.heads = int(cfg["mamba_n_heads"])
        self.head_dim = int(cfg["mamba_d_head"])
        self.d_state = int(cfg["mamba_d_state"])
        self.chunk = int(cfg["mamba_chunk_size"])
        self.att_heads = int(cfg["num_attention_heads"])
        self.kv_heads = int(cfg["num_key_value_heads"])
        self.att_head_dim = self.d_model // self.att_heads
        self.embedding_multiplier = float(cfg["embedding_multiplier"])
        self.residual_multiplier = float(cfg["residual_multiplier"])
        self.attention_multiplier = float(cfg["attention_multiplier"])
        self.logits_scaling = float(cfg["logits_scaling"])
        self.eps = float(cfg["rms_norm_eps"])
        self.layer_types = list(cfg["layer_types"][: int(cfg["num_hidden_layers"])])
        self.d_inner = self.heads * self.head_dim
        self.conv_dim = self.d_inner + 2 * self.d_state
        self.d_in_proj = self.d_inner + self.conv_dim + self.heads
        checks = {
            "mamba_n_groups == 1": cfg["mamba_n_groups"] == 1,
            "mamba_d_conv == 4": cfg["mamba_d_conv"] == 4,
            "heads * head_dim == expand * hidden": self.d_inner
            == cfg["mamba_expand"] * self.d_model,
            "tie_word_embeddings": cfg["tie_word_embeddings"],
            "position_embedding_type == nope": cfg["position_embedding_type"] == "nope",
            "mamba_conv_bias and not mamba_proj_bias": cfg["mamba_conv_bias"]
            and not cfg["mamba_proj_bias"],
            "no attention bias, no experts": not cfg["attention_bias"]
            and not cfg["num_local_experts"],
            "att_heads % kv_heads == 0": self.att_heads % self.kv_heads == 0,
        }
        bad = [k for k, ok in checks.items() if not ok]
        if bad:
            raise ValueError(f"unsupported Granite 4.0-H configuration: {bad}")
        if self.layer_types.count("attention") != 1:
            raise ValueError(f"need one attention layer in {self.layer_types}")
        at = self.layer_types.index("attention")
        self.runs = (at, len(self.layer_types) - at - 1)
        if min(self.runs) < 1 or set(self.layer_types) != {"mamba", "attention"}:
            raise ValueError(f"need mamba+, attention, mamba+: {self.layer_types}")

    def n_params(self) -> int:
        return 2 + 2 * len(MAMBA_LEAVES) + len(ATTENTION_LEAVES)

    def mamba_shapes(self) -> tuple:
        D, F = self.d_model, self.d_ff
        H = self.heads
        return (
            (D,), (D, self.d_in_proj), (4, self.conv_dim), (self.conv_dim,), (H,), (H,), (H,),
            (self.d_inner,), (self.d_inner, D), (D,), (D, F), (D, F), (F, D),
        )

    def attention_shapes(self) -> tuple:
        D, F = self.d_model, self.d_ff
        kv = self.kv_heads * self.att_head_dim
        return ((D,), (D, D), (D, kv), (D, kv), (D, D), (D,), (D, F), (D, F), (F, D))


def _blocks(dims: HybridDims, batch: int, seq: int) -> dict:
    """The model's blocks at one (batch, seq), as Myia-subset functions."""
    if seq % dims.chunk:
        raise ValueError(f"seq {seq} is not a multiple of the chunk {dims.chunk}")
    B, S, D = batch, seq, dims.d_model
    H, Pd, N, Q = dims.heads, dims.head_dim, dims.d_state, dims.chunk
    NC = S // Q
    DI, CONV = dims.d_inner, dims.conv_dim
    KV, R, HD = dims.kv_heads, dims.att_heads // dims.kv_heads, dims.att_head_dim
    EPS = dims.eps
    RES = dims.residual_multiplier
    ATT_SCALE = dims.attention_multiplier
    NEG = _NEG
    # position ids, compared in the graph into the causal masks
    Q_ROWS = np.arange(Q, dtype=np.int32).reshape(Q, 1)
    Q_COLS = np.arange(Q, dtype=np.int32).reshape(1, Q)
    S_ROWS = np.arange(S, dtype=np.int32).reshape(S, 1)
    S_COLS = np.arange(S, dtype=np.int32).reshape(1, S)

    def rmsnorm(x, w, width):
        return x * _rsqrt(_rsum(x * x, (2,), True) / width + EPS) * w

    def silu(x):
        return x * _sigmoid(x)

    def mlp(x, w_gate, w_up, w_down):
        return (silu(x @ w_gate) * (x @ w_up)) @ w_down

    def causal_conv(u, w, b):
        # depthwise, width 4: out_t = sum_k w_k u_{t-3+k}, zeros before t=0
        up = _pad(u, 1, 3, 0)
        return (
            _slice(up, 1, 0, S) * _take(w, 0)
            + _slice(up, 1, 1, S + 1) * _take(w, 1)
            + _slice(up, 1, 2, S + 2) * _take(w, 2)
            + _slice(up, 1, 3, S + 3) * _take(w, 3)
            + b
        )

    def pass_states(st, chunk_decay):
        # st (NC, B, H, P, N): each chunk's own end state; returns the
        # state entering each chunk, h_c = decay_{c-1} h_{c-1} + st_{c-1}
        h = _zeros_like(_take(st, 0))
        h_in = _zeros_like(st)
        for c in range(NC):
            h_in = _index_add(h_in, c, h)
            h = h * _take(chunk_decay, c) + _take(st, c)
        return h_in

    def ssd(xs, dt, a, bm, cm):
        # xs (B, S, H, P), dt (B, S, H), a (H,), bm / cm (B, S, N)
        xc = _transpose(
            _reshape(xs * _reshape(dt, (B, S, H, 1)), (B, NC, Q, H, Pd)), (0, 3, 1, 2, 4)
        )
        cs = _cumsum(_transpose(_reshape(dt * a, (B, NC, Q, H)), (0, 3, 1, 2)), 3, False)
        seg = _reshape(cs, (B, H, NC, Q, 1)) - _reshape(cs, (B, H, NC, 1, Q))
        decay = _exp(_where(_ge(Q_ROWS, Q_COLS), seg, NEG))
        bc = _reshape(bm, (B, 1, NC, Q, N))
        cc = _reshape(cm, (B, 1, NC, Q, N))
        y_diag = ((cc @ _mT(bc)) * decay) @ xc
        last = _slice(cs, 3, Q - 1, Q)
        states = _mT(xc * _reshape(_exp(last - cs), (B, H, NC, Q, 1))) @ bc
        chunk_decay = _transpose(_reshape(_exp(last), (B, H, NC, 1, 1)), (2, 0, 1, 3, 4))
        h_in = pass_states(_transpose(states, (2, 0, 1, 3, 4)), chunk_decay)
        y_off = (cc @ _mT(_transpose(h_in, (1, 2, 0, 3, 4)))) * _reshape(
            _exp(cs), (B, H, NC, Q, 1)
        )
        return _reshape(_transpose(y_diag + y_off, (0, 2, 3, 1, 4)), (B, S, H, Pd))

    def mixer(x, w_in, conv_w, conv_b, dt_bias, a_log, d_skip, gate_norm, w_out):
        zxbcdt = x @ w_in
        z = _slice(zxbcdt, 2, 0, DI)
        xbc = silu(causal_conv(_slice(zxbcdt, 2, DI, DI + CONV), conv_w, conv_b))
        dt = _softplus(_slice(zxbcdt, 2, DI + CONV, DI + CONV + H) + dt_bias)
        xs = _reshape(_slice(xbc, 2, 0, DI), (B, S, H, Pd))
        bm = _slice(xbc, 2, DI, DI + N)
        cm = _slice(xbc, 2, DI + N, DI + 2 * N)
        y = ssd(xs, dt, -_exp(a_log), bm, cm) + xs * _reshape(d_skip, (H, 1))
        return rmsnorm(_reshape(y, (B, S, DI)) * silu(z), gate_norm, DI) @ w_out

    def mamba_layer(h, norm1, w_in, conv_w, conv_b, dt_bias, a_log, d_skip, gate_norm,
                    w_out, norm2, w_gate, w_up, w_down):
        h = h + RES * mixer(
            rmsnorm(h, norm1, D), w_in, conv_w, conv_b, dt_bias, a_log, d_skip, gate_norm,
            w_out,
        )
        return h + RES * mlp(rmsnorm(h, norm2, D), w_gate, w_up, w_down)

    def attend(q, k, v):
        # one KV head and its R query heads: q (B, R, S, HD), k / v (B, 1, S, HD)
        s = _where(_ge(S_ROWS, S_COLS), (q @ _mT(k)) * ATT_SCALE, NEG)
        # the shift leaves softmax unchanged: no gradient flows through it
        e = _exp(s - _rmax(_stop(s), (3,), True))
        return (e / _rsum(e, (3,), True)) @ v

    def attend_heads(q, k, v):
        # a loop over the KV heads: its adjoint recomputes one head's
        # (B, R, S, S) scores at a time instead of keeping all of them
        o = _zeros_like(q)
        for g in range(KV):
            o = _index_add(o, g, attend(_take(q, g), _take(k, g), _take(v, g)))
        return o

    def attention(x, w_q, w_k, w_v, w_o):
        q = _transpose(_reshape(x @ w_q, (B, S, KV, R, HD)), (2, 0, 3, 1, 4))
        k = _transpose(_reshape(x @ w_k, (B, S, KV, 1, HD)), (2, 0, 3, 1, 4))
        v = _transpose(_reshape(x @ w_v, (B, S, KV, 1, HD)), (2, 0, 3, 1, 4))
        o = attend_heads(q, k, v)
        return _reshape(_transpose(o, (1, 3, 0, 2, 4)), (B, S, D)) @ w_o

    def attention_layer(h, norm1, w_q, w_k, w_v, w_o, norm2, w_gate, w_up, w_down):
        h = h + RES * attention(rmsnorm(h, norm1, D), w_q, w_k, w_v, w_o)
        return h + RES * mlp(rmsnorm(h, norm2, D), w_gate, w_up, w_down)

    def make_run(n):
        def mamba_run(h, norm1, w_in, conv_w, conv_b, dt_bias, a_log, d_skip, gate_norm,
                      w_out, norm2, w_gate, w_up, w_down):
            for i in range(n):
                h = mamba_layer(
                    h, _take(norm1, i), _take(w_in, i), _take(conv_w, i), _take(conv_b, i),
                    _take(dt_bias, i), _take(a_log, i), _take(d_skip, i),
                    _take(gate_norm, i), _take(w_out, i), _take(norm2, i),
                    _take(w_gate, i), _take(w_up, i), _take(w_down, i),
                )
            return h

        return mamba_run

    return {
        "rmsnorm": rmsnorm,
        "ssd": ssd,
        "mamba_layer": mamba_layer,
        "attention_layer": attention_layer,
        "run_a": make_run(dims.runs[0]),
        "run_b": make_run(dims.runs[1]),
    }


def build_hybrid_loss(dims: HybridDims, batch: int, seq: int):
    """Myia-subset mean next-token cross-entropy of the hybrid over a
    (batch, seq) token grid.  Parameters, in order: the tied embedding
    (V, D), the final norm (D,), the first Mamba run's ``MAMBA_LEAVES``
    (stacked), the attention layer's ``ATTENTION_LEAVES``, the second
    Mamba run's, then tokens and labels."""
    blk = _blocks(dims, batch, seq)
    rmsnorm, attention_layer = blk["rmsnorm"], blk["attention_layer"]
    run_a, run_b = blk["run_a"], blk["run_b"]
    D, V = dims.d_model, dims.vocab
    EMB, LOGITS = dims.embedding_multiplier, dims.logits_scaling
    denom = float(batch * seq)

    def hybrid_loss(emb, final_norm,
                    a_norm1, a_w_in, a_conv_w, a_conv_b, a_dt_bias, a_a_log, a_d_skip,
                    a_gate_norm, a_w_out, a_norm2, a_w_gate, a_w_up, a_w_down,
                    t_norm1, t_w_q, t_w_k, t_w_v, t_w_o, t_norm2, t_w_gate, t_w_up, t_w_down,
                    b_norm1, b_w_in, b_conv_w, b_conv_b, b_dt_bias, b_a_log, b_d_skip,
                    b_gate_norm, b_w_out, b_norm2, b_w_gate, b_w_up, b_w_down,
                    tokens, labels):
        h = _take(emb, tokens) * EMB
        h = run_a(h, a_norm1, a_w_in, a_conv_w, a_conv_b, a_dt_bias, a_a_log, a_d_skip,
                  a_gate_norm, a_w_out, a_norm2, a_w_gate, a_w_up, a_w_down)
        h = attention_layer(h, t_norm1, t_w_q, t_w_k, t_w_v, t_w_o, t_norm2, t_w_gate,
                            t_w_up, t_w_down)
        h = run_b(h, b_norm1, b_w_in, b_conv_w, b_conv_b, b_dt_bias, b_a_log, b_d_skip,
                  b_gate_norm, b_w_out, b_norm2, b_w_gate, b_w_up, b_w_down)
        logits = (rmsnorm(h, final_norm, D) @ _mT(emb)) / LOGITS
        m = _rmax(logits, (2,), True)
        lse = _log(_rsum(_exp(logits - m), (2,), True)) + m
        oh = _onehot(labels, V, _F32)
        return -_rsum(oh * (logits - lse), (0, 1, 2), False) / denom

    return hybrid_loss


def build_mamba_layer_loss(dims: HybridDims, batch: int, seq: int):
    """Myia-subset sum of one Mamba-2 + MLP layer's output over a (batch,
    seq, D) input; parameters: the input, then ``MAMBA_LEAVES`` of one
    layer (not stacked)."""
    mamba_layer = _blocks(dims, batch, seq)["mamba_layer"]

    def mamba_layer_loss(h, norm1, w_in, conv_w, conv_b, dt_bias, a_log, d_skip, gate_norm,
                         w_out, norm2, w_gate, w_up, w_down):
        out = mamba_layer(h, norm1, w_in, conv_w, conv_b, dt_bias, a_log, d_skip, gate_norm,
                          w_out, norm2, w_gate, w_up, w_down)
        return _rsum(out, (0, 1, 2), False)

    return mamba_layer_loss


def _mamba_init(dims: HybridDims, key, n: int, std: float) -> tuple:
    """One run of ``n`` stacked Mamba layers: norms, D and the gate norm
    at 1; ``dt_bias`` the inverse softplus of dt ~ logU(1e-3, 1e-1) and
    ``A_log = log U(1, 16)`` (Mamba-2's conventions); the convolution
    U(-1/2, 1/2) (PyTorch's default for a width-4 depthwise kernel);
    matrices N(0, std)."""
    shapes = [(n, *s) for s in dims.mamba_shapes()]
    ks = jax.random.split(key, len(shapes))
    out = []
    for name, k, shp in zip(MAMBA_LEAVES, ks, shapes):
        if name in ("norm1", "norm2", "gate_norm", "d_skip"):
            out.append(jnp.ones(shp, jnp.float32))
        elif name == "dt_bias":
            dt = jnp.exp(jax.random.uniform(k, shp, jnp.float32, np.log(1e-3), np.log(1e-1)))
            dt = jnp.maximum(dt, 1e-4)
            out.append(dt + jnp.log(-jnp.expm1(-dt)))
        elif name == "a_log":
            out.append(jnp.log(jax.random.uniform(k, shp, jnp.float32, 1.0, 16.0)))
        elif name in ("conv_w", "conv_b"):
            out.append(jax.random.uniform(k, shp, jnp.float32, -0.5, 0.5))
        else:
            out.append(jax.random.normal(k, shp, jnp.float32) * std)
    return tuple(out)


def init_hybrid_params(dims: HybridDims, rng, std: float = 0.02) -> tuple:
    """The loss's parameter tuple, from ``rng``."""
    k_emb, k_a, k_t, k_b = jax.random.split(rng, 4)
    att = []
    for name, k, shp in zip(
        ATTENTION_LEAVES, jax.random.split(k_t, len(ATTENTION_LEAVES)), dims.attention_shapes()
    ):
        if name.startswith("norm"):
            att.append(jnp.ones(shp, jnp.float32))
        else:
            att.append(jax.random.normal(k, shp, jnp.float32) * std)
    return (
        jax.random.normal(k_emb, (dims.vocab, dims.d_model), jnp.float32) * std,
        jnp.ones((dims.d_model,), jnp.float32),
        *_mamba_init(dims, k_a, dims.runs[0], std),
        *att,
        *_mamba_init(dims, k_b, dims.runs[1], std),
    )


def make_hybrid_train_step(dims: HybridDims, batch: int, seq: int, lr: float, *,
                           fuse: bool = True):
    """(step_fn, init_fn) of the hybrid, from the shared step builder."""
    return make_train_step(
        build_hybrid_loss(dims, batch, seq),
        dims.n_params(),
        lr,
        lambda rng: init_hybrid_params(dims, rng),
        fuse=fuse,
    )
