"""Parallelism layer: logical-axis sharding rules and the mesh context.

Models never mention physical mesh axes.  They call
:func:`constrain` with *logical* axis names ("batch", "seq", "heads",
"embed", "mlp", "experts", "vocab", "kv_seq", …); the active
:class:`MeshContext` maps logical → physical ("data"/"model"/"pod") and
inserts ``with_sharding_constraint``.  Without an active context (CPU
smoke tests) everything is a no-op, so the same model code runs on one
device and on a 512-chip mesh.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Any, Mapping, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "MeshContext",
    "mesh_context",
    "current_mesh_context",
    "constrain",
    "logical_to_spec",
    "DEFAULT_RULES",
    "named_sharding",
]


#: logical axis → physical mesh axis (or tuple of axes, or None=replicated).
#: ``batch`` spans the pure-data axes; model-parallel dims map to "model".
DEFAULT_RULES: dict[str, Any] = {
    "batch": ("pod", "data"),
    "seq": None,  # activations: sequence replicated by default
    "kv_seq": "model",  # long-context decode: KV cache sharded on sequence
    "embed": None,
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "mlp": "model",
    "experts": "model",
    "expert_mlp": None,
    "vocab": "model",
    "fsdp": "data",  # parameter shard axis for ZeRO/FSDP-style setups
    "conv": None,
    "ssm_heads": "model",
    "ssm_state": None,
    "ssm_proj": "model",
    "image_seq": None,
}


class MeshContext:
    """An active mesh + logical-axis rules."""

    def __init__(self, mesh: Mesh, rules: Mapping[str, Any] | None = None) -> None:
        self.mesh = mesh
        self.rules = dict(DEFAULT_RULES)
        if rules:
            self.rules.update(rules)

    def spec(self, logical: Sequence[str | None], shape: Sequence[int] | None = None) -> P:
        """logical → PartitionSpec.  With ``shape``, axes that do not
        divide their dim (batch=1 on a 16-way axis, kv=8 on model=16)
        fall back to replication, and no mesh axis is used twice."""
        sizes = dict(self.mesh.shape)
        used: set[str] = set()
        axes = []
        for i, name in enumerate(logical):
            phys = None if name is None else self.rules.get(name)
            if phys is None:
                axes.append(None)
                continue
            cand = phys if isinstance(phys, tuple) else (phys,)
            cand = tuple(a for a in cand if a in sizes and a not in used)
            if not cand:
                axes.append(None)
                continue
            if shape is not None:
                total = 1
                for a in cand:
                    total *= sizes[a]
                if shape[i] % total != 0:
                    axes.append(None)
                    continue
            used.update(cand)
            axes.append(cand if len(cand) > 1 else cand[0])
        return P(*axes)

    def sharding(self, logical: Sequence[str | None], shape=None) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec(logical, shape))


_STATE = threading.local()


def current_mesh_context() -> MeshContext | None:
    return getattr(_STATE, "ctx", None)


@contextlib.contextmanager
def mesh_context(mesh: Mesh | None, rules: Mapping[str, Any] | None = None):
    """Activate (mesh, rules) for model code; None deactivates (no-op mode)."""
    prev = current_mesh_context()
    _STATE.ctx = MeshContext(mesh, rules) if mesh is not None else None
    try:
        yield _STATE.ctx
    finally:
        _STATE.ctx = prev


def constrain(x: jax.Array, *logical: str | None) -> jax.Array:
    """``with_sharding_constraint(x, logical axes)`` under the active mesh
    context; identity when no context is active.  Non-divisible dims fall
    back to replication (checked against x.shape)."""
    ctx = current_mesh_context()
    if ctx is None:
        return x
    return jax.lax.with_sharding_constraint(x, ctx.sharding(logical, x.shape))


def logical_to_spec(logical: Sequence[str | None]) -> P:
    ctx = current_mesh_context()
    if ctx is None:
        return P()
    return ctx.spec(logical)


def named_sharding(logical: Sequence[str | None]) -> NamedSharding | None:
    ctx = current_mesh_context()
    if ctx is None:
        return None
    return ctx.sharding(logical)
