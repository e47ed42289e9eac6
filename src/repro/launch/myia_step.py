"""Myia-compiled train/serve steps for the launch drivers (SPMD tier).

``launch/train.py --compiler myia`` and ``launch/serve.py --compiler
myia`` run an LM whose loss is written in the Myia subset and compiled
through the *whole* paper pipeline — parse → ST-AD → infer → worklist-
optimize → fuse → (SPMD partition) → lower — instead of ``jax.grad``.
Under an active mesh context the optimized+fused adjoint executes as a
per-shard program under ``shard_map`` (``repro.core.spmd``); with no mesh
the identical graph runs on the single-device tier.  That makes the e2e
step the integration point the ROADMAP asks for: the compiler IS the
execution engine, on 1 and N devices.

The model is a deliberately small tanh-MLP LM (embedding → two hidden
matmuls → vocab projection → stable log-softmax cross-entropy): every op
is a Myia primitive, and the sharding story is the classic one — batch
data-parallel, Megatron-style column/row split on the hidden pair, and a
vocab-parallel projection whose softmax reduces with ``pmax``/``psum``
over the model axis.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import api
import repro.core.primitives as P

__all__ = [
    "MyiaLMDims",
    "build_lm_loss",
    "build_lm_logits",
    "lm_in_specs",
    "init_lm_params",
    "make_myia_train_step",
]

_take = P.take
_tanh = P.tanh
_exp = P.exp
_log = P.log
_rsum = P.reduce_sum
_rmax = P.reduce_max
_onehot = P.one_hot
_F32 = np.dtype("float32")


class MyiaLMDims:
    """The tiny LM's dimensions, derived from a ModelConfig when given."""

    __slots__ = ("vocab", "d_model", "d_hidden")

    def __init__(self, vocab: int, d_model: int, d_hidden: int | None = None) -> None:
        self.vocab = int(vocab)
        self.d_model = int(d_model)
        self.d_hidden = int(d_hidden if d_hidden is not None else 4 * d_model)

    @classmethod
    def from_config(cls, cfg) -> "MyiaLMDims":
        return cls(cfg.vocab, cfg.d_model)


def build_lm_logits(dims: MyiaLMDims):
    """Myia-subset forward: tokens → logits (B, S, V)."""

    def lm_logits(emb, w1, w2, wout, tokens):
        h = _take(emb, tokens)
        h = _tanh(h @ w1)
        h = _tanh(h @ w2)
        return h @ wout

    return lm_logits


def build_lm_loss(dims: MyiaLMDims, batch: int, seq: int):
    """Myia-subset mean cross-entropy over a (batch, seq) token grid.

    The log-softmax is the numerically stable spelling (max-shifted) so
    the SPMD tier exercises both collective kinds on the vocab axis:
    ``pmax`` for the shift, ``psum`` for the normalizer.
    """
    vocab = dims.vocab
    denom = float(batch * seq)

    def lm_loss(emb, w1, w2, wout, tokens, labels):
        h = _take(emb, tokens)
        h = _tanh(h @ w1)
        h = _tanh(h @ w2)
        logits = h @ wout
        m = _rmax(logits, (2,), True)
        z = logits - m
        lse = _log(_rsum(_exp(z), (2,), True)) + m
        logp = logits - lse
        oh = _onehot(labels, vocab, _F32)
        return -_rsum(oh * logp, (0, 1, 2), False) / denom

    return lm_loss


def lm_in_specs(*, with_labels: bool = True) -> tuple:
    """Canonical sharding for the LM's arguments: batch data-parallel
    activations, Megatron column/row split on the hidden pair, a
    vocab-parallel output projection, replicated embedding table."""
    specs = (
        None,                  # emb (V, D): replicated (take indexes dim 0)
        (None, "model"),       # w1 (D, H): column-parallel
        ("model", None),       # w2 (H, D): row-parallel (psum after)
        (None, "model"),       # wout (D, V): vocab-parallel
        ("data",),             # tokens (B, S)
    )
    return specs + (("data",),) if with_labels else specs


def init_lm_params(dims: MyiaLMDims, rng: jax.Array) -> tuple:
    k0, k1, k2, k3 = jax.random.split(rng, 4)
    scale = 0.1
    return (
        jax.random.normal(k0, (dims.vocab, dims.d_model), jnp.float32) * scale,
        jax.random.normal(k1, (dims.d_model, dims.d_hidden), jnp.float32) * scale,
        jax.random.normal(k2, (dims.d_hidden, dims.d_model), jnp.float32) * scale,
        jax.random.normal(k3, (dims.d_model, dims.vocab), jnp.float32) * scale,
    )


def make_myia_train_step(
    dims: MyiaLMDims, batch: int, seq: int, lr: float, *, fuse: bool = True
):
    """(step_fn, init_fn) for ``runtime.train_loop``.

    The loss+adjoint is one Myia graph (`value_and_grad` through the ST
    transform); the SGD update is a handful of jax ops outside it.  The
    MyiaFunction carries ``lm_in_specs`` — under an active mesh context
    the step transparently switches to the sharded compilation tier.
    """
    vag = api.value_and_grad(
        build_lm_loss(dims, batch, seq),
        wrt=(0, 1, 2, 3),
        options=api.CompileOptions(fuse=fuse, in_specs=lm_in_specs()),
    )

    @jax.jit
    def _update(params, grads):
        gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in grads))
        new_params = tuple(p - lr * g for p, g in zip(params, grads))
        return new_params, gnorm

    def step_fn(state, batch_dict):
        params = state["params"]
        loss, grads = vag(*params, batch_dict["tokens"], batch_dict["labels"])
        new_params, gnorm = _update(params, grads)
        return (
            {"params": new_params, "step": state["step"] + 1},
            {"loss": loss, "gnorm": gnorm},
        )

    def init_fn(rng=None):
        rng = jax.random.PRNGKey(0) if rng is None else rng
        return {"params": init_lm_params(dims, rng), "step": jnp.zeros((), jnp.int32)}

    step_fn.vag = vag  # introspection: tests/benchmarks reach the runner
    return step_fn, init_fn
