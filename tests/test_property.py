"""Hypothesis property tests on system invariants.

``hypothesis`` is optional (same policy as ``zstandard``, see
``repro/core/codec.py``): environments without it skip this module instead
of failing collection.
"""

import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core.calibrate import CalibrationSpec, calibrate_window
from repro.core.power import PowerParams, mape, opendc_power
from repro.core.desim import simulate_utilization
from repro.traces.schema import Workload

SETTINGS = dict(max_examples=25, deadline=None)


@given(
    u=st.lists(st.floats(0, 1), min_size=2, max_size=32),
    r=st.floats(1.0, 6.0),
    p_idle=st.floats(20.0, 120.0),
    span=st.floats(10.0, 400.0),
)
@settings(**SETTINGS)
def test_power_bounded(u, r, p_idle, span):
    params = PowerParams(p_idle, p_idle + span, r)
    us = jnp.asarray(sorted(u), jnp.float32)
    out = np.asarray(opendc_power(us, params))
    tol = 1e-3 * (p_idle + 2 * span)
    assert (out >= p_idle - tol).all()
    # loose cap: shape <= 2u <= 2 (the form overshoots p_max for r > 2)
    assert (out <= p_idle + 2 * span + tol).all()
    if r <= 2.0:
        assert (np.diff(out) >= -tol).all()       # monotone only for r <= 2


@given(
    scale=st.floats(0.5, 2.0),
    vals=st.lists(st.floats(10.0, 1e4), min_size=3, max_size=64),
)
@settings(**SETTINGS)
def test_mape_scale_property(scale, vals):
    a = jnp.asarray(vals, jnp.float32)
    m = float(mape(a, a * scale))
    assert m == np.float32(abs(1 - scale) * 100).item() or \
        abs(m - abs(1 - scale) * 100) < 0.05


@given(
    n_jobs=st.integers(1, 24),
    hosts=st.integers(1, 8),
    seed=st.integers(0, 2**16),
)
@settings(**SETTINGS)
def test_des_invariants(n_jobs, hosts, seed):
    """Capacity respected; placed jobs never exceed aggregate utilization 1;
    no job starts before submission."""
    rng = np.random.default_rng(seed)
    t_bins = 48
    sub = rng.integers(0, t_bins // 2, n_jobs).astype(np.int32)
    sub.sort()
    dur = rng.integers(1, 8, n_jobs).astype(np.int32)
    cores = rng.integers(1, 17, n_jobs).astype(np.int32)
    util = rng.uniform(0.1, 1.0, (n_jobs, 4)).astype(np.float32)
    w = Workload(jnp.asarray(sub), jnp.asarray(dur), jnp.asarray(cores),
                 jnp.asarray(util), jnp.ones((n_jobs,), bool))
    out = simulate_utilization(w, num_hosts=hosts, cores_per_host=16,
                               t_bins=t_bins)
    u = np.asarray(out.u_th)
    assert (u <= 1.0 + 1e-5).all()
    starts = np.asarray(out.job_start)
    placed = starts >= 0
    assert (starts[placed] >= sub[placed]).all()


@given(r_true=st.floats(1.2, 5.5), seed=st.integers(0, 99))
@settings(max_examples=10, deadline=None)
def test_calibration_never_worse_than_base(r_true, seed):
    rng = np.random.default_rng(seed)
    u = jnp.asarray(rng.uniform(0, 1, (64, 16)).astype(np.float32))
    real = jnp.asarray(np.asarray(
        opendc_power(u, PowerParams(70.0, 350.0, r_true))).sum(1))
    base = PowerParams(70.0, 350.0, 2.0)
    res = calibrate_window(u, real, CalibrationSpec(r_points=96), base)
    base_mape = float(mape(real, jnp.asarray(np.asarray(
        opendc_power(u, base)).sum(1))))
    assert res.mape <= base_mape + 1e-4
