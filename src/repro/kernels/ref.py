"""Pure-jnp oracle for the calibration-grid Pallas kernel.

It is the mathematical specification: ``calib_mape.py`` must match it to
float tolerance across the shape sweep in ``tests/test_kernels.py``.  It is
also the XLA execution path used on CPU (kernels/ops.py ``backend="xla"``).
The read-out kernel's oracle, ``des_readout_ref``, lives beside its kernel
in ``des_readout.py``: the two share one tile body.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array


def calib_mape_grid_ref(
    u_th: Array,        # [T, H] utilization
    real_power: Array,  # [T] measured total power
    p_idle: Array,      # [C]
    p_max: Array,       # [C]
    r: Array,           # [C]
) -> Array:             # [C] MAPE in %
    """Grid-search MAPE oracle.

    For candidate c:  sim_t = H*p_idle_c + (p_max_c - p_idle_c) * (S2_t - Sr_t(c))
    with S2_t = sum_h 2*u_th and Sr_t(c) = sum_h u_th^{r_c}; MAPE over t.

    Same MAPE semantics as :func:`repro.core.power.mape`: denominator
    ``|real| + eps``, zero-real bins (all hosts offline) excluded from
    the mean — one dead bin must not blow every candidate's score to 1e10 %
    and wash out the grid search — and an *all*-zero window returns NaN for
    every candidate (undefined, surfaced; ``calibrate_window`` keeps the
    incumbent parameters on such windows instead of shipping an arbitrary
    grid point as a "perfect" fit).  The mask is candidate-independent, so
    exclusion is a per-bin weight, not a shape change.

    The [C, T] intermediate is materialized here — the Pallas kernel's whole
    point is to tile this away (see calib_mape.py).
    """
    u = jnp.clip(u_th.astype(jnp.float32), 0.0, 1.0)
    t, h = u.shape
    s2 = jnp.sum(2.0 * u, axis=1)                       # [T]
    # [C, T]: sum_h u^r per candidate
    log_u = jnp.log(jnp.maximum(u, 1e-30))              # [T, H]
    sr = jnp.sum(
        jnp.exp(r.astype(jnp.float32)[:, None, None] * log_u[None]), axis=2
    )                                                   # [C, T]
    span = (p_max - p_idle).astype(jnp.float32)[:, None]
    sim = h * p_idle.astype(jnp.float32)[:, None] + span * (s2[None, :] - sr)
    rp = real_power.astype(jnp.float32)[None, :]
    nonzero = jnp.abs(rp) > 1e-9                        # [1, T]
    n_nz = jnp.sum(nonzero)
    ape = jnp.abs((rp - sim) / (jnp.abs(rp) + 1e-9)) * nonzero
    out = jnp.sum(ape, axis=1) * (100.0 / jnp.maximum(n_nz, 1))
    return jnp.where(n_nz > 0, out, jnp.nan)
