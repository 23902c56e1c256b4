"""Elastic re-meshing after node loss / capacity change.

On a real fleet this re-runs device discovery; here the policy layer is what
matters: given the surviving device count, pick the largest valid
(pod, data, model) mesh that preserves the model-parallel degree (TP size is
an algorithmic invariant — changing it re-shards every weight), shrink the
data axis, and rescale per-shard batch so the GLOBAL batch stays constant
(bitwise-stable loss scaling across restarts).
"""

from __future__ import annotations

import dataclasses

import jax
import numpy as np
from jax.sharding import Mesh


@dataclasses.dataclass(frozen=True)
class MeshPlan:
    shape: tuple[int, ...]
    axes: tuple[str, ...]
    per_shard_batch: int
    grad_accum: int

    @property
    def data_shards(self) -> int:
        s = dict(zip(self.axes, self.shape))
        return s.get("data", 1) * s.get("pod", 1)


def plan_mesh(
    available_devices: int,
    *,
    model_parallel: int,
    global_batch: int,
    prefer_pods: int = 1,
) -> MeshPlan:
    """Largest data-parallel degree that fits the surviving devices."""
    if available_devices < model_parallel:
        raise RuntimeError(
            f"cannot re-mesh: {available_devices} devices < TP degree "
            f"{model_parallel}")
    data = available_devices // model_parallel
    # data shards must divide the global batch; shrink until they do,
    # adding gradient accumulation to keep the global batch constant.
    while data > 1 and global_batch % data != 0:
        data -= 1
    pods = prefer_pods if data % prefer_pods == 0 else 1
    shape: tuple[int, ...]
    axes: tuple[str, ...]
    if pods > 1:
        shape, axes = (pods, data // pods, model_parallel), ("pod", "data", "model")
    else:
        shape, axes = (data, model_parallel), ("data", "model")
    per_shard = global_batch // data
    return MeshPlan(shape=shape, axes=axes, per_shard_batch=per_shard,
                    grad_accum=1)


def build_mesh(plan: MeshPlan, devices) -> Mesh:
    """Materialize a plan over an explicit device list.

    ``devices`` is required (pass ``jax.devices()`` at the call site): mesh
    re-planning after a failure must be a pure function of the surviving
    device set the caller observed, not of ambient discovery at build time
    (tracecheck TC007 — the runtime layer is deterministic-core).
    """
    n = int(np.prod(plan.shape))
    return jax.make_mesh(plan.shape, plan.axes,
                         (jax.sharding.AxisType.Auto,) * len(plan.shape),
                         devices=devices[:n])
