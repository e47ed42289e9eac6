"""The comparisons that decide ``correct``.

Each returns one number, compared against a limit kept in the cell's
file.  ``PERF.md`` gives the readings each limit was set from.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp


def loss_gap(got: list[float], want: list[float]) -> float:
    """Largest relative gap between the program's and the reference's
    loss over the steps both took."""
    return max(abs(g - w) / abs(w) for g, w in zip(got, want))


def kept_leaves(ref_grad_norms: list[float], share: float = 1e-3) -> list[int]:
    """Leaves whose reference gradient is more than ``share`` of the median
    leaf's: the others move under the optimizer by round-off alone."""
    med = float(np.median(ref_grad_norms))
    return [i for i, n in enumerate(ref_grad_norms) if n > share * med]


def worst_leaf_gap(got: list[float], want: list[float], keep: list[int]) -> float:
    """Largest gap between the program's and the reference's per-leaf
    norms, each over the larger of that leaf's reference norm and the
    median leaf's."""
    med = float(np.median([want[i] for i in keep]))
    return max(abs(got[i] - want[i]) / max(want[i], med) for i in keep)


@jax.jit
def leaf_norms(a: tuple, b: tuple):
    """Per-leaf L2 norms of ``a - b``, in float32, on the device."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x - y))) for x, y in zip(a, b)])
