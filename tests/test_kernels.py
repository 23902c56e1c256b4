"""Per-kernel shape/dtype sweeps: Pallas (interpret) vs the pure-jnp oracle."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.calib_mape import calib_mape_grid_pallas

RNG = np.random.default_rng(7)


@pytest.mark.parametrize("t,h,c", [
    (64, 16, 8), (100, 64, 33), (288, 277, 64), (512, 128, 200),
])
def test_calib_mape_sweep(t, h, c):
    u = jnp.asarray(RNG.uniform(0, 1, (t, h)).astype(np.float32))
    real = jnp.asarray(RNG.uniform(1e3, 5e3, (t,)).astype(np.float32))
    pi = jnp.asarray(RNG.uniform(50, 90, (c,)).astype(np.float32))
    pm = jnp.asarray(RNG.uniform(250, 450, (c,)).astype(np.float32))
    r = jnp.asarray(RNG.uniform(1, 6, (c,)).astype(np.float32))
    want = ref.calib_mape_grid_ref(u, real, pi, pm, r)
    got = calib_mape_grid_pallas(u, real, pi, pm, r, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-3)


def test_ops_backend_dispatch():
    u = jnp.asarray(RNG.uniform(0, 1, (64, 32)).astype(np.float32))
    real = jnp.asarray(RNG.uniform(1e3, 2e3, (64,)).astype(np.float32))
    c = jnp.asarray([2.0, 3.0], jnp.float32)
    pi = jnp.full((2,), 70.0)
    pm = jnp.full((2,), 350.0)
    a = ops.calib_mape_grid(u, real, pi, pm, c, backend="xla")
    b = ops.calib_mape_grid(u, real, pi, pm, c, backend="pallas_interpret")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-3)
    assert ops.resolve_backend("auto") in ("xla", "pallas")
