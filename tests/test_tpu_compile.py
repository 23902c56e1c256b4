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
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.calib_mape import calib_mape_grid_pallas
from repro.kernels.des_readout import des_readout_pallas

#: HBM of one TPU v5e chip (Google Cloud documentation, "TPU v5e")
V5E_HBM_BYTES = 16 * 10**9

HOSTS = 277          # SURF-SARA
WEEK_BINS = 2016     # 7 days of 5-minute bins
HISTORY_BINS = 144   # 4 windows x 36 bins of calibration history
LANES = 64           # the what-if cell's batch


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


_RESULT = re.compile(r"^\s*(?:ROOT\s+)?%(\S+) = (.*?) ([\w-]+)\(")


def test_whatif_scan_moves_no_release_table(one_chip):
    """The S=64 SURF what-if program for one chip: no reshape, copy or
    scatter (nor any other instruction) has the ``S * (t_bins+1) * hosts``
    elements of a table of releases by bin, which the chip would relayout
    every bin; the pending releases live in per-host slots."""
    from repro.core import scenarios as sc
    from repro.runtime.fault import HostFailure
    from repro.traces.schema import DatacenterConfig
    from repro.traces.surf import SurfTraceSpec, make_surf22_like

    dc = DatacenterConfig(num_hosts=HOSTS, cores_per_host=16)
    w = make_surf22_like(SurfTraceSpec(days=7, seed=22), dc)
    policies = ("worst_fit", "best_fit", "first_fit", "random_fit")
    lanes = [sc.Scenario(
        name=f"l{i}", policy=policies[i % 4], backfill_depth=2 * (i % 2),
        failures=(HostFailure(host=3, start_bin=100, end_bin=400),)
        if i % 3 == 0 else (), pue_base=1.1 if i % 5 == 0 else None)
        for i in range(LANES)]
    ss = sc.build_scenario_set(w, dc, lanes, max_hosts=HOSTS)
    assert ss.max_units == 16
    n_jobs = int(ss.workload.submit_bin.shape[-1])
    chunk = LANES * n_jobs * WEEK_BINS > sc._BATCH_READOUT_THRESHOLD
    args = jax.tree.map(
        lambda x: _spec(one_chip, np.shape(x), np.asarray(x).dtype),
        (ss, np.zeros(WEEK_BINS, np.float32), np.zeros(WEEK_BINS, np.float32),
         np.zeros(WEEK_BINS, np.float32)))
    compiled = jax.jit(functools.partial(
        sc._scenario_lanes, max_hosts=HOSTS, t_bins=WEEK_BINS,
        max_starts_per_bin=64, model="opendc", chunk=chunk)).lower(
        *args).compile()
    table = LANES * (WEEK_BINS + 1) * HOSTS
    moved = {}
    for m in map(_RESULT.match, compiled.as_text().splitlines()):
        if m is None:
            continue
        for dims in re.findall(r"\[([\d,]*)\]", m.group(2)):
            if int(np.prod([int(d) for d in dims.split(",") if d])) == table:
                moved[m.group(1)] = (m.group(3), dims)
    assert not moved, f"table-sized instructions: {moved}"
    ma = compiled.memory_analysis()
    assert (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes) < V5E_HBM_BYTES
