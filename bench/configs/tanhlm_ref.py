"""Plain reference of the tanh-MLP language model, in jax.numpy.

embedding -> tanh(x W1) -> tanh(h W2) -> vocab projection -> mean
cross-entropy of the next token, with SGD steps over it.  It imports
nothing of the program and takes nothing the program made: the weights
come from :func:`param_maker`, which the harness also hands the program.

The reference computes in float32, its matmuls at the precision the
configuration states (``matmul_precision``).  The control computes the same loss with every array in bfloat16 (float32
master weights, float32 update), the step a lower-precision port would
take.  Both run the batch in blocks of rows so that the vocabulary-wide
logits of one block at a time are live.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

import numpy as np

from bench.compare import leaf_norms

#: the matmul precision each spelling in the configuration files stands for
PRECISION = {"highest": lax.Precision.HIGHEST, "default": None}


def param_maker(cfg: dict):
    """The jitted key -> (emb (V, D), w1 (D, H), w2 (H, D), wout (D, V)),
    float32: the weights are made on the device in one call."""
    V, D, H = cfg["vocab_size"], cfg["hidden_size"], cfg["intermediate_size"]
    scale = float(cfg["init_scale"])

    @jax.jit
    def make(key):
        k = jax.random.split(key, 4)
        shapes = ((V, D), (D, H), (H, D), (D, V))
        return tuple(
            jax.random.normal(ki, s, jnp.float32) * scale for ki, s in zip(k, shapes)
        )

    return make


def loss_sum(params, tokens, labels, *, dtype=jnp.float32, precision=None):
    """Sum over rows of the next-token cross-entropy, computed in ``dtype``."""
    emb, w1, w2, wout = (p.astype(dtype) for p in params)
    h = jnp.take(emb, tokens, axis=0)
    h = jnp.tanh(jnp.matmul(h, w1, precision=precision))
    h = jnp.tanh(jnp.matmul(h, w2, precision=precision))
    logp = jax.nn.log_softmax(jnp.matmul(h, wout, precision=precision), axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[..., None], axis=-1))


def mean_loss(params, tokens, labels, *, precision=None):
    """The loss as the program states it (float32, default precision) in one
    piece: the jnp copy that ``myia_over_jax.train`` times against."""
    return loss_sum(params, tokens, labels, precision=precision) / tokens.size


def make_value_and_grad(*, dtype=jnp.float32, precision: str = "default",
                        rows_per_block: int = 1024):
    """jitted (params, tokens (B, S), labels (B, S)) -> (mean loss, grads),
    computed in ``dtype`` with matmuls at ``precision`` and accumulated in
    float32 over blocks of ``rows_per_block`` tokens."""
    precision = PRECISION[precision]

    def block(params, tok, lab):
        return jax.value_and_grad(
            lambda p: loss_sum(p, tok, lab, dtype=dtype, precision=precision)
        )(params)

    @jax.jit
    def value_and_grad(params, tokens, labels):
        n = tokens.size
        rows = min(rows_per_block, n)
        tok = tokens.reshape(n // rows, rows)
        lab = labels.reshape(n // rows, rows)

        def body(carry, xs):
            acc_loss, acc_grads = carry
            loss, grads = block(params, *xs)
            acc_grads = tuple(a + g.astype(jnp.float32) for a, g in zip(acc_grads, grads))
            return (acc_loss + loss.astype(jnp.float32), acc_grads), None

        zeros = tuple(jnp.zeros(p.shape, jnp.float32) for p in params)
        (loss, grads), _ = lax.scan(body, (jnp.float32(0.0), zeros), (tok, lab))
        return loss / n, tuple(g / n for g in grads)

    return value_and_grad


def sgd_readings(params0: tuple, batches, lr: float, *, dtype=jnp.float32,
                 precision: str = "default") -> dict:
    """Follow SGD from ``params0`` over ``batches`` and read what the
    harness compares: each step's loss, the per-leaf norm of the first
    gradient as the update applied it, and the per-leaf norm of the
    parameters' change after the last step."""
    vag = make_value_and_grad(dtype=dtype, precision=precision)

    @jax.jit
    def update(params, grads):
        return tuple(p - lr * g for p, g in zip(params, grads))

    params, losses, first = params0, [], None
    for i, (tokens, labels) in enumerate(batches):
        loss, grads = vag(params, jnp.asarray(tokens), jnp.asarray(labels))
        new = update(params, grads)
        if i == 0:
            first = np.asarray(leaf_norms(params0, new)) / lr
        params = new
        losses.append(float(loss))
    return {
        "losses": losses,
        "first_grad_norms": [float(x) for x in first],
        "change_norms": [float(x) for x in np.asarray(leaf_norms(params, params0))],
    }
