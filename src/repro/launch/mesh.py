"""Production mesh construction (function, not module constant: importing
this module never touches jax device state)."""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 (one v5e-class pod) or 2x16x16 (two pods, 512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devices)} — "
            "the dry-run sets XLA_FLAGS=--xla_force_host_platform_device_count"
        )
    return jax.make_mesh(shape, axes,
                         (jax.sharding.AxisType.Auto,) * len(shape),
                         devices=devices[:n])


def make_host_mesh():
    """Single-device 'mesh' for smoke tests (1x1 data/model)."""
    return jax.make_mesh((1, 1), ("data", "model"),
                         (jax.sharding.AxisType.Auto,) * 2,
                         devices=jax.devices()[:1])
