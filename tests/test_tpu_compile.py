"""Compile rehearsals for one TPU v5e chip: the twin's Pallas kernels at the
SURF-SARA widths (277 hosts, 7 days of 5-minute bins), compiled for a
*described* v5e topology — no chip is needed.

Interpret mode cannot show what the TPU compiler refuses: a block that
overflows the 16 MiB of scoped VMEM, a slice off the tiling.  These tests
compile the real kernels for the chip, check that the Mosaic kernel is in
the program (``tpu_custom_call``) and that the program fits one chip's
HBM.  The topology is described inside a module fixture (never at import
time) because only one process at a time may load the TPU library.
"""

import functools

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.calib_mape import calib_mape_grid_pallas
from repro.kernels.des_readout import des_readout_pallas

#: HBM of one TPU v5e chip (Google Cloud documentation, "TPU v5e")
V5E_HBM_BYTES = 16 * 10**9

HOSTS = 277          # SURF-SARA
WEEK_BINS = 2016     # 7 days of 5-minute bins
HISTORY_BINS = 144   # 4 windows x 36 bins of calibration history


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel_fits(compiled):
    assert "tpu_custom_call" in compiled.as_text()
    ma = compiled.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes)
    assert used < V5E_HBM_BYTES, f"{used} B does not fit one v5e chip"


def _readout(u, pi, pm, r, mask, cap, ci, amb, prc, fs, fe, fk, *,
             precision):
    return des_readout_pallas(
        u, p_idle=pi, p_max=pm, r=r, mask=mask, cap_t=cap, intensity=ci,
        ambient=amb, price=prc, peak_tflops=1.0, pue_base=1.1,
        pue_load_coeff=0.05, fail_start=fs, fail_end=fe, fail_kill=fk,
        precision=precision)


@pytest.mark.parametrize("precision,lanes", [
    ("f32", None), ("bf16", None), ("f32", 16)])
def test_des_readout_compiles_for_v5e(one_chip, precision, lanes):
    """The fused readout over a week of SURF-SARA, alone and vmapped over
    a 16-scenario batch (the what-if engine's use)."""
    t, h = WEEK_BINS, HOSTS
    shapes = [(t, h), (h,), (h,), (h,), (h,), (t,), (t,), (t,), (t,),
              (h,), (h,), (h,)]
    dtypes = [jnp.float32] * 5 + [jnp.float32] * 4 + [jnp.int32, jnp.int32,
                                                      jnp.bool_]
    fn = functools.partial(_readout, precision=precision)
    if lanes is not None:
        shapes = [(lanes,) + s for s in shapes]
        fn = jax.vmap(fn)
    args = [_spec(one_chip, s, d) for s, d in zip(shapes, dtypes)]
    _assert_kernel_fits(jax.jit(fn).lower(*args).compile())


@pytest.mark.parametrize("hosts,candidates", [
    (HOSTS, 64),      # r_only grid (the paper's Self-Calibrator)
    (HOSTS, 9216),    # joint grid: 64 r-points x 12 x 12 scale points
    (1, 64),          # per-host refit: one host column per call
])
def test_calib_mape_compiles_for_v5e(one_chip, hosts, candidates):
    """The calibration grid kernel over a 144-bin history stays inside
    the 16 MiB of scoped VMEM at every grid size the calibrator builds."""
    t, c = HISTORY_BINS, candidates
    args = [_spec(one_chip, (t, hosts)), _spec(one_chip, (t,))] + [
        _spec(one_chip, (c,)) for _ in range(3)]
    _assert_kernel_fits(calib_mape_grid_pallas.lower(*args).compile())
