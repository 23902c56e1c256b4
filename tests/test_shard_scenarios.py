"""Scenario-axis sharding: shard_map over S == single-device vmap, bit for bit.

Runs meaningfully at any device count: with one device the mesh is trivial
(the path is still exercised end to end); the ``tier1-multidevice`` CI job
re-runs this module under ``XLA_FLAGS=--xla_force_host_platform_device_count=4``
so the real multi-device shard_map path — including S-axis padding when S is
not a multiple of the device count — is covered on CPU-only CI.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.scenarios import (
    SCENARIO_AXIS,
    Scenario,
    build_scenario_set,
    run_scenarios,
    scenario_mesh,
    summarize_scenarios,
)
from repro.runtime.fault import DEGRADED, OUTAGE, HostFailure
from repro.traces.carbon import make_diurnal_carbon
from repro.traces.price import make_diurnal_price
from repro.traces.schema import DatacenterConfig
from repro.traces.surf import BINS_PER_DAY, SurfTraceSpec, make_surf22_like
from repro.traces.thermal import make_diurnal_ambient

T_BINS = int(0.25 * BINS_PER_DAY)
DC = DatacenterConfig(num_hosts=32, cores_per_host=16)


@pytest.fixture(scope="module")
def workload():
    return make_surf22_like(SurfTraceSpec(days=0.25, seed=5), DC)


#: S=6 on purpose: not a multiple of 2 or 4 devices -> exercises padding
def _grid():
    return [
        Scenario(name="base"),
        Scenario(name="h16-bf", num_hosts=16, policy="best_fit",
                 backfill_depth=2),
        Scenario(name="h24-ff", num_hosts=24, policy="first_fit"),
        Scenario(name="cap", power_cap_w=5000.0),
        Scenario(name="shift", shift_bins=6),
        Scenario(name="cc", carbon_cap_base_w=7000.0, carbon_cap_slope=-5.0),
    ]


def _assert_trees_equal(a, b):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def test_sharded_matches_vmap_bitwise(workload):
    """The acceptance gate: shard_map over the S axis reproduces the
    single-device vmap path bit for bit, summaries included."""
    ci = make_diurnal_carbon(T_BINS, seed=1)
    ss = build_scenario_set(workload, DC, _grid())
    ref_sim, ref_pred = run_scenarios(
        ss, max_hosts=ss.max_hosts, t_bins=T_BINS, carbon_intensity=ci)
    sh_sim, sh_pred = run_scenarios(
        ss, max_hosts=ss.max_hosts, t_bins=T_BINS, carbon_intensity=ci,
        shard=True)
    _assert_trees_equal(ref_sim, sh_sim)
    _assert_trees_equal(ref_pred, sh_pred)
    ref_sum = summarize_scenarios(ss, ref_sim, ref_pred, carbon_intensity=ci)
    sh_sum = summarize_scenarios(ss, sh_sim, sh_pred, carbon_intensity=ci)
    assert ref_sum == sh_sum


def test_sharded_matches_vmap_without_carbon(workload):
    """Same gate on the no-intensity path (gco2=None pytree structure)."""
    ss = build_scenario_set(workload, DC, _grid()[:4])
    ref = run_scenarios(ss, max_hosts=ss.max_hosts, t_bins=T_BINS)
    sh = run_scenarios(ss, max_hosts=ss.max_hosts, t_bins=T_BINS, shard=True)
    _assert_trees_equal(ref, sh)


def test_explicit_mesh_and_padding(workload):
    """S not divisible by the device count: lanes pad with scenario-0
    replicas and outputs slice back to the true S."""
    n_dev = len(jax.devices())
    mesh = scenario_mesh(n_dev)
    assert mesh.shape[SCENARIO_AXIS] == n_dev
    scs = _grid()[:5]                    # S=5: pads for any n_dev > 1
    ss = build_scenario_set(workload, DC, scs)
    sim, pred = run_scenarios(ss, max_hosts=ss.max_hosts, t_bins=T_BINS,
                              shard=True, mesh=mesh)
    assert sim.u_th.shape[0] == len(scs)
    assert np.asarray(pred.power_w).shape == (len(scs), T_BINS)
    ref_sim, ref_pred = run_scenarios(ss, max_hosts=ss.max_hosts,
                                      t_bins=T_BINS)
    _assert_trees_equal(ref_sim, sim)
    _assert_trees_equal(ref_pred, pred)


def test_sharded_matches_vmap_new_axes(workload):
    """The three newest axes — failure windows, dynamic PUE and spot
    price — through the shard path: the ``[T]`` ambient/price traces ride
    as replicated operands next to carbon, the per-host failure arrays and
    per-scenario PUE fields shard over S, and the mixed batch (including
    an axis-free lane) must match the vmap path bit for bit."""
    ci = make_diurnal_carbon(T_BINS, seed=1)
    amb = make_diurnal_ambient(T_BINS, seed=2)
    pr = make_diurnal_price(T_BINS, seed=3)
    scs = [
        Scenario(name="base"),                  # all new axes off
        Scenario(name="outage", failures=(
            HostFailure(host=3, start_bin=4, end_bin=24, kind=OUTAGE),
            HostFailure(host=7, start_bin=10, end_bin=40, kind=DEGRADED))),
        Scenario(name="pue", pue_base=1.2, pue_amb_coeff=0.02,
                 pue_load_coeff=0.15),
        Scenario(name="mix", power_cap_w=6000.0, shift_bins=4,
                 backfill_depth=2, pue_base=1.1, pue_load_coeff=0.05,
                 failures=(HostFailure(host=0, start_bin=8, end_bin=16,
                                       kind=OUTAGE),)),
        Scenario(name="cc-pue", carbon_cap_base_w=7000.0,
                 carbon_cap_slope=-5.0, pue_base=1.3),
    ]
    ss = build_scenario_set(workload, DC, scs)
    kw = dict(max_hosts=ss.max_hosts, t_bins=T_BINS, carbon_intensity=ci,
              ambient_c=amb, price=pr)
    ref_sim, ref_pred = run_scenarios(ss, **kw)
    sh_sim, sh_pred = run_scenarios(ss, **kw, shard=True)
    _assert_trees_equal(ref_sim, sh_sim)
    _assert_trees_equal(ref_pred, sh_pred)
    ref_sum = summarize_scenarios(ss, ref_sim, ref_pred, carbon_intensity=ci)
    sh_sum = summarize_scenarios(ss, sh_sim, sh_pred, carbon_intensity=ci)
    assert ref_sum == sh_sum
    # the batch really exercised the axes (not silently disabled lanes)
    assert ref_sum[1].failure_events == 2
    assert ref_sum[2].mean_pue is not None and ref_sum[2].mean_pue > 1.0
    assert all(s.energy_cost is not None and s.energy_cost > 0
               for s in ref_sum)


def test_one_lane_per_device_with_backfill(workload):
    """S == device count with backfill compiled in: the engine pads to
    >= 2 lanes per device and must still match the vmap path bit for
    bit."""
    n_dev = len(jax.devices())
    scs = [Scenario(name=f"s{i}", num_hosts=16 + 2 * i,
                    backfill_depth=2 if i == 1 else 0)
           for i in range(n_dev)]
    ss = build_scenario_set(workload, DC, scs)
    ref = run_scenarios(ss, max_hosts=ss.max_hosts, t_bins=T_BINS)
    sh = run_scenarios(ss, max_hosts=ss.max_hosts, t_bins=T_BINS, shard=True)
    _assert_trees_equal(ref, sh)


def test_one_lane_per_device_with_carbon_and_price(workload):
    """S == device count with carbon and price traces: a batch-1 vmap per
    device would differ from the vmap path by 1 ulp in gco2/energy_cost on
    jax 0.9.0, so the engine's >= 2 lanes per device keep it bitwise."""
    n_dev = len(jax.devices())
    ci = make_diurnal_carbon(T_BINS, seed=1)
    pr = make_diurnal_price(T_BINS, seed=3)
    scs = [Scenario(name=f"s{i}", power_cap_w=5000.0 + 500.0 * i,
                    backfill_depth=2 if i == 1 else 0)
           for i in range(n_dev)]
    ss = build_scenario_set(workload, DC, scs)
    kw = dict(max_hosts=ss.max_hosts, t_bins=T_BINS, carbon_intensity=ci,
              price=pr)
    _assert_trees_equal(run_scenarios(ss, **kw),
                        run_scenarios(ss, **kw, shard=True))


def test_multidevice_actually_shards(workload):
    """Under the forced multi-device CI environment the outputs must really
    be computed across >1 device (not silently replicated)."""
    if len(jax.devices()) < 2:
        pytest.skip("single-device environment (multi-device CI covers this)")
    ss = build_scenario_set(workload, DC, _grid()[:4])
    sim, _ = run_scenarios(ss, max_hosts=ss.max_hosts, t_bins=T_BINS,
                           shard=True)
    # the result is a concrete, fully-addressable array of the true S
    assert sim.u_th.shape[0] == 4
    assert np.isfinite(np.asarray(sim.u_th)).all()


def test_optimize_sharded_matches_unsharded(workload):
    """Optimizer smoke on the sharded evaluator: ``optimize(shard=True)``
    must reproduce the unsharded search bit for bit — every candidate's
    objective, the incumbent trace, and the winning operating point (the
    ``tier1-multidevice`` CI job runs this on a forced 4-CPU-device mesh)."""
    from repro.core.optimize import (
        ObjectiveSpec,
        OptimizerConfig,
        SearchSpace,
        optimize,
    )

    ci = make_diurnal_carbon(T_BINS, seed=1)
    space = SearchSpace(
        structures=(Scenario(name="wf"),
                    Scenario(name="bf", policy="best_fit", backfill_depth=2)),
        carbon_cap_base_w=(1500.0, 4000.0),
        shift_bins=(0, 8))
    obj = ObjectiveSpec(w_gco2_kg=1.0, w_wait=0.1, w_unplaced=10.0)
    cfg = OptimizerConfig(batch_size=8, generations=2, init="grid",
                          init_levels=2)
    kw = dict(t_bins=T_BINS, carbon_intensity=ci, key=3, config=cfg)
    ref = optimize(workload, DC, space, obj, **kw)
    sh = optimize(workload, DC, space, obj, **kw, shard=True)
    assert [c.scenario for c in ref.history] == [c.scenario for c in sh.history]
    assert [c.objective for c in ref.history] == \
        [c.objective for c in sh.history]
    np.testing.assert_array_equal(ref.incumbent_objective,
                                  sh.incumbent_objective)
    assert ref.best.scenario == sh.best.scenario
    assert ref.best.breakdown == sh.best.breakdown
    assert ref.best_summary == sh.best_summary
