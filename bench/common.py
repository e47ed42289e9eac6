"""Small helpers shared by the harness, the references and the tests."""

from __future__ import annotations

import json
import pathlib

import numpy as np

#: the benchmark's own directory, and the checkout that holds it
BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def key_from_seed(seed: int):
    """A JAX PRNG key from any non-negative seed, also one past 32 bits."""
    import jax

    seed = int(seed)
    key = jax.random.PRNGKey(np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32((seed >> 32) & 0xFFFFFFFF))


def peaks_for(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``; an unknown kind
    is an error, never a default."""
    table = load_json(BENCH / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in bench/peaks.json")
    return table[device_kind]

