"""Jit'd public wrappers for the kernels, with a backend switch.

backend = "pallas"           — compiled Pallas (TPU deployment target)
backend = "pallas_interpret" — Pallas interpret mode (CPU validation; the
                               kernel body runs in Python, semantics identical)
backend = "xla"              — the pure-jnp oracle; used on CPU for speed.

The default is resolved from the platform at call time so library code never
hard-codes a backend.
"""

from __future__ import annotations

from typing import Literal

import jax

from repro.kernels import ref
from repro.kernels.calib_mape import calib_mape_grid_pallas
from repro.kernels.des_readout import des_readout_pallas, des_readout_ref

Array = jax.Array
Backend = Literal["auto", "pallas", "pallas_interpret", "xla"]


def resolve_backend(backend: Backend) -> str:
    if backend != "auto":
        return backend
    platform = jax.devices()[0].platform  # tracecheck: disable=TC007 — backend="auto" dispatch
    return "pallas" if platform == "tpu" else "xla"


def calib_mape_grid(
    u_th: Array, real_power: Array,
    p_idle: Array, p_max: Array, r: Array,
    *, backend: Backend = "auto",
) -> Array:
    """[C] candidate MAPEs over a cached utilization window."""
    b = resolve_backend(backend)
    if b == "xla":
        return ref.calib_mape_grid_ref(u_th, real_power, p_idle, p_max, r)
    return calib_mape_grid_pallas(
        u_th, real_power, p_idle, p_max, r,
        interpret=(b == "pallas_interpret"),
    )


def des_readout(u_th: Array, *, backend: Backend = "auto",
                **kw) -> dict[str, Array]:
    """Fused DES readout: the full per-bin metric set in one pass.

    Keyword operands are those of
    :func:`repro.kernels.des_readout.des_readout_pallas`; the ``xla``
    backend runs the reference over the identical tile decomposition, so
    in f32 the two backends agree bit for bit (not merely within
    tolerance).
    """
    b = resolve_backend(backend)
    if b == "xla":
        return des_readout_ref(u_th, **kw)
    return des_readout_pallas(u_th, interpret=(b == "pallas_interpret"),
                              **kw)
