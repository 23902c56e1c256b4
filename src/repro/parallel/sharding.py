"""Logical-axis sharding rules -> PartitionSpecs (MaxText-style).

Every parameter and activation carries a tuple of *logical* axis names; a
rule table per execution mode maps logical axes onto mesh axes:

  train:  DP over 'pod', FSDP (ZeRO-3) over 'data', TP over 'model'
  serve:  replicas over ('pod','data'), TP over 'model'  (weight-stationary)

A logical axis mapping to a mesh axis is dropped (replicated) when the axis
size does not divide the mesh axis — e.g. kv_heads=8 on a 16-way model axis.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

LogicalAxes = tuple[str | None, ...]


#: mode -> logical axis -> mesh axis (or tuple of mesh axes)
RULES: dict[str, dict[str, Any]] = {
    "train": {
        "batch": ("pod", "data"),
        "seq": None,
        "embed": "data",        # ZeRO-3: shard the replicated dim over data
        "embed_nofsdp": None,
        "heads": "model",
        "kv_heads": "model",
        "qk": None,
        "ff": "model",
        "vocab": "model",
        "experts": "model",
        "moe_ff": None,
        "lora": None,
        "dstate": None,
        "conv": None,
        "ssm_inner": "model",
        "ssm_heads": "model",
        "attn_q_seq": "model",   # context-parallel fallback for attention
        "frames": None,
        "patches": None,
        "cache_seq": None,
        "cache_heads": "model",
    },
    "serve": {
        "batch": ("pod", "data"),
        "seq": None,
        "embed": None,          # weight-stationary TP: no FSDP gather latency
        "embed_nofsdp": None,
        "heads": "model",
        "kv_heads": "model",
        "qk": None,
        "ff": "model",
        "vocab": "model",
        "experts": "model",
        "moe_ff": None,
        "lora": None,
        "dstate": None,
        "conv": None,
        "ssm_inner": "model",
        "ssm_heads": "model",
        "attn_q_seq": "model",   # context-parallel fallback for attention
        "frames": None,
        "patches": None,
        "cache_seq": "model",
        "cache_heads": "model",
    },
}


def mesh_axis_size(mesh: Mesh, axis: Any) -> int:
    if axis is None:
        return 1
    if isinstance(axis, tuple):
        return int(np.prod([mesh.shape[a] for a in axis]))
    return int(mesh.shape[axis])


def logical_to_spec(
    axes: LogicalAxes,
    shape: tuple[int, ...],
    mesh: Mesh,
    mode: str = "train",
) -> P:
    """Map logical axes to a PartitionSpec, dropping non-divisible shardings."""
    rules = RULES[mode]
    used: set[str] = set()
    parts: list[Any] = []
    for dim, name in zip(shape, axes):
        mesh_axis = rules.get(name) if name else None
        if mesh_axis is None:
            parts.append(None)
            continue
        flat = mesh_axis if isinstance(mesh_axis, tuple) else (mesh_axis,)
        # keep only non-trivial axes present in this mesh, not yet consumed
        flat = tuple(a for a in flat
                     if a in mesh.shape and mesh.shape[a] > 1
                     and a not in used)
        if not flat:
            parts.append(None)
            continue
        if dim % mesh_axis_size(mesh, flat) != 0:
            parts.append(None)          # non-divisible -> replicate
            continue
        used.update(flat)
        parts.append(flat if len(flat) > 1 else flat[0])
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def tree_shardings(
    axes_tree: Any,
    shape_tree: Any,
    mesh: Mesh,
    mode: str = "train",
) -> Any:
    """NamedShardings for a pytree of (axes, shapes)."""

    def one(axes: LogicalAxes, shaped) -> NamedSharding:
        spec = logical_to_spec(axes, tuple(shaped.shape), mesh, mode)
        return NamedSharding(mesh, spec)

    return jax.tree.map(
        one, axes_tree, shape_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x),
    )


def constraint(x: jax.Array, axes: LogicalAxes, mesh: Mesh | None,
               mode: str = "train") -> jax.Array:
    """with_sharding_constraint via logical axes (no-op without a mesh)."""
    if mesh is None or mesh.empty:
        return x
    spec = logical_to_spec(axes, tuple(x.shape), mesh, mode)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


@dataclasses.dataclass(frozen=True)
class ShardingCtx:
    """Threaded through model code so layers can place activations."""

    mesh: Mesh | None = None
    mode: str = "train"

    def on(self, x: jax.Array, *axes: str | None) -> jax.Array:
        return constraint(x, tuple(axes), self.mesh, self.mode)


# -- ambient context -----------------------------------------------------------
# Step factories bind the ShardingCtx here at trace time so deep layers
# (attention inner scans, SSD chunk scans) can pin activation shardings
# without threading ctx through every call signature.

import contextlib as _contextlib
import contextvars as _contextvars

_AMBIENT: _contextvars.ContextVar[ShardingCtx] = _contextvars.ContextVar(
    "repro_sharding_ctx", default=ShardingCtx())


def current_ctx() -> ShardingCtx:
    return _AMBIENT.get()


@_contextlib.contextmanager
def use_ctx(ctx: ShardingCtx):
    tok = _AMBIENT.set(ctx)
    try:
        yield ctx
    finally:
        _AMBIENT.reset(tok)


def activation(x: jax.Array, *axes: str | None) -> jax.Array:
    """Constrain an activation under the ambient ShardingCtx (no-op on 1 dev)."""
    return _AMBIENT.get().on(x, *axes)
