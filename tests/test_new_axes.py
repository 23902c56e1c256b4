"""Failure, dynamic-PUE and spot-price axes vs the pure-Python oracle.

The three axes added to the scenario engine — per-host failure windows,
dynamic PUE(load, ambient) and electricity spot prices — are traced lanes
of the same single-compile program as caps/shifts/policies/topologies.
These tests check them three ways:

* randomized cross-checks against ``tests/reference.py`` (schedules exact,
  float read-outs to f32 tolerance);
* hand-built semantic cases (outage kills vs drain finishes; outage hosts
  draw nothing, drained hosts keep their idle floor);
* the off-switch: a mixed batch's axis-free lane is bit-for-bit the run
  with no axes at all, and invalid axis inputs fail loudly at build time.
"""

import math

import jax.numpy as jnp
import numpy as np
import pytest

from reference import reference_pue, reference_scenario

from repro.core.feedback import ProposalKind
from repro.core.power import PowerParams
from repro.core.scenarios import (
    Scenario,
    build_scenario_set,
    evaluate_scenarios,
    run_scenarios,
)
from repro.runtime.fault import DEGRADED, HostFailure
from repro.traces.schema import DatacenterConfig, Workload


def _random_case(seed, j=20, hosts=3, cores_per_host=8, t_bins=40):
    rng = np.random.default_rng(seed)
    submit = np.sort(rng.integers(0, t_bins // 2, j)).astype(np.int32)
    dur = rng.integers(1, 8, j).astype(np.int32)
    cores = rng.integers(1, cores_per_host + 1, j).astype(np.int32)
    util = rng.uniform(0.1, 1.0, (j, 3)).astype(np.float32)
    defer = rng.random(j) < 0.6
    w = Workload(jnp.asarray(submit), jnp.asarray(dur), jnp.asarray(cores),
                 jnp.asarray(util), jnp.ones((j,), bool),
                 deferrable=jnp.asarray(defer))
    dc = DatacenterConfig(num_hosts=hosts, cores_per_host=cores_per_host)
    intensity = rng.uniform(80.0, 600.0, t_bins).astype(np.float32)
    ambient = rng.uniform(5.0, 35.0, t_bins).astype(np.float32)
    price = rng.uniform(0.02, 0.45, t_bins).astype(np.float32)
    return w, dc, t_bins, intensity, ambient, price


def _workload_dict(w: Workload) -> dict:
    return dict(
        submit=np.asarray(w.submit_bin).tolist(),
        dur=np.asarray(w.duration_bins).tolist(),
        cores=np.asarray(w.cores).tolist(),
        util=np.asarray(w.util_levels).tolist(),
        valid=np.asarray(w.valid).tolist(),
        deferrable=(None if w.deferrable is None
                    else np.asarray(w.deferrable).tolist()),
    )


#: the new-axes mix: outages, drains, dynamic PUE, and combinations with the
#: pre-existing axes (caps, shifts, policies) in one batch.
def _scenarios(hosts, t_bins):
    watts = hosts * 120.0
    return [
        Scenario(name="base"),
        Scenario(name="outage", failures=(
            HostFailure(0, t_bins // 4, t_bins // 2),)),
        Scenario(name="drain", failures=(
            HostFailure(hosts - 1, 5, t_bins - 3, kind=DEGRADED),)),
        Scenario(name="multi-fail", failures=(
            HostFailure(0, 3, 11),
            HostFailure(1, 8, 20, kind=DEGRADED),)),
        Scenario(name="pue", pue_base=1.15, pue_amb_coeff=0.02,
                 pue_amb_ref=16.0, pue_load_coeff=0.12),
        Scenario(name="pue-cap", pue_base=1.3, power_cap_w=watts * 1.8),
        Scenario(name="fail-pue-shift", shift_bins=5, pue_base=1.1,
                 pue_load_coeff=0.2,
                 failures=(HostFailure(1, t_bins // 3, t_bins // 2),)),
        Scenario(name="bf-fail", policy="best_fit", backfill_depth=3,
                 failures=(HostFailure(0, 10, 25),)),
    ]


@pytest.mark.parametrize("seed", [2, 13, 31])
def test_new_axes_match_oracle(seed):
    w, dc, t_bins, intensity, ambient, price = _random_case(seed)
    params = PowerParams(p_idle=63.0, p_max=341.0, r=2.3)
    scs = _scenarios(dc.num_hosts, t_bins)
    ss, sim, pred, summaries = evaluate_scenarios(
        w, dc, scs, t_bins=t_bins, base_params=params,
        carbon_intensity=intensity, ambient_c=ambient, price=price)
    assert ss.has_failures and ss.pue_on
    wd = _workload_dict(w)
    for i, sc in enumerate(scs):
        ref = reference_scenario(
            wd, dc, sc, t_bins=t_bins, p_idle=63.0, p_max=341.0, r=2.3,
            intensity=[float(v) for v in intensity],
            ambient=[float(v) for v in ambient],
            price=[float(v) for v in price])
        # schedules (kill/drain placement rules) are exact
        assert np.asarray(sim.job_start[i]).tolist() == ref["job_start"], sc.name
        assert np.asarray(sim.job_host[i]).tolist() == ref["job_host"], sc.name
        np.testing.assert_allclose(
            np.asarray(sim.u_th[i], np.float64), np.asarray(ref["u_th"]),
            rtol=2e-5, atol=1e-6, err_msg=f"{sc.name}: u_th")
        np.testing.assert_allclose(
            np.asarray(pred.power_demand_w[i], np.float64),
            np.asarray(ref["demand"]), rtol=1e-4, err_msg=f"{sc.name}: demand")
        np.testing.assert_allclose(
            np.asarray(pred.power_w[i], np.float64),
            np.asarray(ref["power"]), rtol=1e-4,
            err_msg=f"{sc.name}: delivered power")
        np.testing.assert_allclose(
            np.asarray(pred.utilization[i], np.float64),
            np.asarray(ref["util"]), rtol=1e-4, atol=1e-6,
            err_msg=f"{sc.name}: utilization")
        # PUE lane: scenarios without the axis run the identity sentinel 1.0
        got_pue = np.asarray(pred.pue[i], np.float64)
        if sc.pue_base is not None:
            np.testing.assert_allclose(
                got_pue, np.asarray(ref["pue"]), rtol=1e-5,
                err_msg=f"{sc.name}: pue")
        else:
            assert (got_pue == 1.0).all(), f"{sc.name}: identity pue lane"
        np.testing.assert_allclose(
            np.asarray(pred.energy_cost[i], np.float64),
            np.asarray(ref["cost"]), rtol=2e-4, err_msg=f"{sc.name}: cost")
        np.testing.assert_allclose(
            np.asarray(pred.gco2[i], np.float64), np.asarray(ref["gco2"]),
            rtol=2e-4, err_msg=f"{sc.name}: gco2")
        # summary roll-ups
        assert summaries[i].failure_events == len(sc.failures)
        assert summaries[i].energy_cost == pytest.approx(
            sum(ref["cost"]), rel=2e-4)
        assert summaries[i].mean_pue == pytest.approx(
            float(np.mean(got_pue)), rel=1e-6)


def test_outage_kills_and_unpowers_drain_does_not():
    """Hand-built semantics: one long job per host, failure window in the
    middle.  The outage host's job dies at fail_start and the host draws
    *nothing* during the window; the drained host's job finishes and keeps
    paying its power bill throughout."""
    t_bins = 20
    w = Workload(jnp.asarray([0, 0], jnp.int32),
                 jnp.asarray([16, 16], jnp.int32),
                 jnp.asarray([4, 4], jnp.int32),
                 jnp.full((2, 1), 0.8, jnp.float32),
                 jnp.ones((2,), bool))
    dc = DatacenterConfig(num_hosts=2, cores_per_host=4)
    params = PowerParams(p_idle=100.0, p_max=300.0, r=2.0)
    scs = [
        Scenario(name="kill", failures=(HostFailure(0, 5, 12),)),
        Scenario(name="drain", failures=(
            HostFailure(0, 5, 12, kind=DEGRADED),)),
        Scenario(name="none"),
    ]
    _, sim, pred, _ = evaluate_scenarios(
        w, dc, scs, t_bins=t_bins, base_params=params)
    u = np.asarray(sim.u_th)
    # worst_fit ties break to the lowest host index, so job 0 lands on
    # host 0 (the failing host) and job 1 on host 1
    assert np.asarray(sim.job_host[2]).tolist() == [0, 1]
    # kill: host 0's job stops at bin 5, never resumes
    assert u[0, 4, 0] > 0 and (u[0, 5:, 0] == 0).all()
    # drain: job keeps running through the window
    assert (u[1, :16, 0] > 0).all()
    # power: during [5, 12) the outage lane omits host 0 entirely (not even
    # idle watts) while the drain lane keeps both hosts' draw
    p_kill = np.asarray(pred.power_w[0], np.float64)
    p_drain = np.asarray(pred.power_w[1], np.float64)
    p_none = np.asarray(pred.power_w[2], np.float64)
    for t in range(5, 12):
        assert p_drain[t] == pytest.approx(p_none[t], rel=1e-6)
        assert p_kill[t] <= p_drain[t] - params.p_idle + 1e-6
    # after recovery host 0 draws idle again in the kill lane
    assert p_kill[13] > p_kill[6]


def test_killed_jobs_hold_cores_until_recovery():
    """A killed job's cores come back with the host, not at the kill bin:
    a successor can only land on the failed host at fail_end."""
    t_bins = 20
    w = Workload(jnp.asarray([0, 6], jnp.int32),
                 jnp.asarray([10, 4], jnp.int32),
                 jnp.asarray([4, 4], jnp.int32),
                 jnp.full((2, 1), 0.5, jnp.float32),
                 jnp.ones((2,), bool))
    dc = DatacenterConfig(num_hosts=1, cores_per_host=4)
    _, sim, _, _ = evaluate_scenarios(
        w, dc, [Scenario(name="f", failures=(HostFailure(0, 4, 9),))],
        t_bins=t_bins, base_params=PowerParams())
    # job 0 (placed at 0, runs into the window) dies at 4; its cores are
    # held until the host returns at 9, so job 1 (submitted at 6) starts
    # exactly at the recovery bin
    assert np.asarray(sim.job_start[0]).tolist() == [0, 9]


def test_mixed_batch_axis_free_lane_is_bit_for_bit():
    """The static-flag design in action: lanes that do not use an axis run
    the identity sentinels, and their outputs equal an axes-off batch's
    bit for bit (not just approximately)."""
    w, dc, t_bins, intensity, ambient, price = _random_case(8)
    params = PowerParams(p_idle=63.0, p_max=341.0, r=2.3)
    mixed = [Scenario(name="base"),
             Scenario(name="f", failures=(HostFailure(0, 10, 20),)),
             Scenario(name="p", pue_base=1.2, pue_load_coeff=0.1)]
    _, sim_m, pred_m, _ = evaluate_scenarios(
        w, dc, mixed, t_bins=t_bins, base_params=params,
        carbon_intensity=intensity, ambient_c=ambient, price=price)
    _, sim_0, pred_0, _ = evaluate_scenarios(
        w, dc, [Scenario(name="base")], t_bins=t_bins, base_params=params,
        carbon_intensity=intensity)
    assert np.asarray(sim_m.u_th[0]).tobytes() == \
        np.asarray(sim_0.u_th[0]).tobytes()
    assert np.asarray(pred_m.power_w[0]).tobytes() == \
        np.asarray(pred_0.power_w[0]).tobytes()
    # axes off entirely -> the optional outputs stay None
    assert pred_0.pue is None and pred_0.energy_cost is None


def test_degradation_from_stragglers_bridge():
    """Straggler proposals map to DEGRADED windows the DES can consume."""
    from repro.core.feedback import Proposal
    from repro.runtime.straggler import degradation_from_stragglers

    props = [
        Proposal(ProposalKind.RESTART_STRAGGLER, 3, "host 2 slow",
                 impact={"host": 2, "ratio": 1.9}),
        Proposal(ProposalKind.RECALIBRATE, 3, "mape"),
        Proposal(ProposalKind.RESTART_STRAGGLER, 3, "host 2 again",
                 impact={"host": 2, "ratio": 2.1}),
        Proposal(ProposalKind.RESTART_STRAGGLER, 3, "host 0 slow",
                 impact={"host": 0, "ratio": 1.5}),
    ]
    fails = degradation_from_stragglers(props, start_bin=12, duration_bins=6)
    assert [f.host for f in fails] == [2, 0]
    assert all(f.kind == DEGRADED and f.start_bin == 12 and f.end_bin == 18
               for f in fails)
    # and they are valid scenario-axis input
    build_scenario_set(
        Workload(jnp.asarray([0], jnp.int32), jnp.asarray([1], jnp.int32),
                 jnp.asarray([1], jnp.int32), jnp.ones((1, 1), jnp.float32),
                 jnp.ones((1,), bool)),
        DatacenterConfig(num_hosts=3, cores_per_host=4),
        [Scenario(name="s", failures=fails)])


def test_reference_pue_shape():
    """Oracle PUE replica: load term falls with load, ambient term kicks in
    above the reference temperature only."""
    pue = (1.2, 0.05, 18.0, 0.3)
    assert reference_pue(1.0, None, pue) == pytest.approx(1.2)
    assert reference_pue(0.0, None, pue) == pytest.approx(1.5)
    assert reference_pue(1.0, 17.0, pue) == pytest.approx(1.2)
    assert reference_pue(1.0, 28.0, pue) == pytest.approx(1.2 + 0.05 * 10)


def test_orchestrator_window_cost_and_measured_overrides():
    """Windowed twinning with the new forecasts: the energy-cost record
    prices the window, measured telemetry extras (PRICE_KEY/AMBIENT_KEY)
    override the configured forecasts, and a PUE-bearing TwinConfig
    checkpoints and resumes."""
    from repro.core.orchestrator import Orchestrator, OrchestratorConfig
    from repro.core.telemetry import AMBIENT_KEY, PRICE_KEY, clip_to_window
    from repro.traces.thermal import PUEParams

    t_bins, j = 48, 16
    rng = np.random.default_rng(4)
    w = Workload(jnp.asarray(np.sort(rng.integers(0, 24, j)), jnp.int32),
                 jnp.asarray(rng.integers(1, 6, j), jnp.int32),
                 jnp.asarray(rng.integers(1, 4, j), jnp.int32),
                 jnp.asarray(rng.uniform(0.2, 0.9, (j, 2)), jnp.float32),
                 jnp.ones(j, bool))
    dc = DatacenterConfig(num_hosts=3, cores_per_host=4)
    price = np.full(t_bins, 0.10, np.float32)
    ambient = np.full(t_bins, 20.0, np.float32)
    cfg = OrchestratorConfig(
        bins_per_window=24,
        pue=PUEParams(base=1.2, amb_coeff=0.02, load_coeff=0.1))
    orch = Orchestrator(w, dc, t_bins, cfg, ambient_c=ambient, price=price)
    sim = orch._ensure_sim()
    u = np.asarray(sim.u_th)
    p_meas = 80.0 + 150.0 * u.sum(axis=1)
    # window 0 carries measured price 3x the forecast
    orch.store.ingest(clip_to_window(
        0, 24, 0, u[:24], p_meas[:24],
        **{PRICE_KEY: price[:24] * 3.0, AMBIENT_KEY: ambient[:24] + 5.0}))
    r0 = orch.run_window(0)
    r1 = orch.run_window(1)      # no telemetry: forecast-priced
    assert r0.energy_cost is not None and r1.energy_cost is not None
    # measured price is 3x the forecast, same energy to first order -> the
    # window-0 record must be priced well above the forecast-only window
    assert r0.energy_cost > 2.0 * r1.energy_cost
    # facility power: prediction carries a PUE > 1 everywhere
    assert (np.asarray(r0.prediction.pue) > 1.0).all()
    # checkpoint/resume round-trips the PUE-bearing config
    import tempfile, os
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "tw.msgpack")
        orch.save_state(path)
        orch.restore_state(path)
    assert orch.state.cfg.pue == cfg.pue


def test_cost_optimal_differs_from_carbon_optimal():
    """On opposing synthetic traces (price cheap where carbon is dirty and
    vice versa) the searched what-if lands on *different* operating points
    under a cost objective vs a carbon objective, and the cost winner is
    routed through the HITL gate as a COST_REDUCTION with a $ breakdown."""
    from repro.core.optimize import ObjectiveSpec, SearchSpace
    from repro.core.orchestrator import Orchestrator, OrchestratorConfig

    t_bins, j = 48, 12
    w = Workload(jnp.zeros(j, jnp.int32), jnp.full(j, 4, jnp.int32),
                 jnp.full(j, 2, jnp.int32),
                 jnp.full((j, 1), 0.8, jnp.float32), jnp.ones(j, bool))
    dc = DatacenterConfig(num_hosts=2, cores_per_host=4)
    price = np.where(np.arange(t_bins) < t_bins // 2, 0.50, 0.05)
    carbon = np.where(np.arange(t_bins) < t_bins // 2, 50.0, 600.0)
    space = SearchSpace(structures=(Scenario(name="s"),),
                        shift_bins=(0, 24))

    def run(objective):
        orch = Orchestrator(
            w, dc, t_bins, OrchestratorConfig(bins_per_window=24),
            carbon_intensity=carbon.astype(np.float32),
            price=price.astype(np.float32))
        return orch.optimize_whatif(space=space, objective=objective, key=1)

    cost = run(ObjectiveSpec(w_gco2_kg=0.0, w_cost=1.0, w_wait=0.0))
    carb = run(ObjectiveSpec(w_gco2_kg=1.0, w_cost=0.0, w_wait=0.0))
    # cost chases the cheap second half; carbon stays in the clean first
    assert cost.result.best.scenario.shift_bins > 0
    assert carb.result.best.scenario.shift_bins == 0
    assert cost.result.best_summary.energy_cost < \
        cost.result.baseline_summary.energy_cost
    # HITL routing: a cost proposal carrying the $ breakdown vs baseline
    kinds = {p.kind for p in cost.proposals}
    assert ProposalKind.COST_REDUCTION in kinds
    for p in cost.proposals:
        bd = p.impact["objective_breakdown"]
        bd0 = p.impact["objective_breakdown_baseline"]
        assert bd["energy_cost"] < bd0["energy_cost"]


# -- validation: every bad axis input fails loudly at build time --------------

def test_scenario_validation_errors():
    with pytest.raises(ValueError, match="pue_base must be finite and >= 1"):
        Scenario(name="x", pue_base=0.9)
    with pytest.raises(ValueError, match="without pue_base"):
        Scenario(name="x", pue_load_coeff=0.1)
    with pytest.raises(ValueError, match="0 <= start < end"):
        HostFailure(0, 7, 7)
    with pytest.raises(ValueError, match="host must be >= 0"):
        HostFailure(-1, 0, 5)
    with pytest.raises(ValueError, match="outage.*degraded"):
        HostFailure(0, 0, 5, kind="meltdown")


def test_build_rejects_bad_failure_hosts():
    w = Workload(jnp.asarray([0], jnp.int32), jnp.asarray([1], jnp.int32),
                 jnp.asarray([1], jnp.int32), jnp.ones((1, 1), jnp.float32),
                 jnp.ones((1,), bool))
    dc = DatacenterConfig(num_hosts=2, cores_per_host=4)
    with pytest.raises(ValueError, match="out of range"):
        build_scenario_set(w, dc, [
            Scenario(name="s", failures=(HostFailure(5, 0, 3),))])
    with pytest.raises(ValueError, match="merge them first"):
        build_scenario_set(w, dc, [
            Scenario(name="s", failures=(HostFailure(0, 0, 3),
                                         HostFailure(0, 4, 6)))])


def test_run_rejects_window_past_horizon_and_missing_traces():
    w = Workload(jnp.asarray([0], jnp.int32), jnp.asarray([1], jnp.int32),
                 jnp.asarray([1], jnp.int32), jnp.ones((1, 1), jnp.float32),
                 jnp.ones((1,), bool))
    dc = DatacenterConfig(num_hosts=2, cores_per_host=4)
    ss = build_scenario_set(w, dc, [
        Scenario(name="s", failures=(HostFailure(0, 50, 60),))])
    with pytest.raises(ValueError, match="can never fire"):
        run_scenarios(ss, max_hosts=2, t_bins=10)
    ss2 = build_scenario_set(w, dc, [
        Scenario(name="s", pue_base=1.2, pue_amb_coeff=0.05)])
    with pytest.raises(ValueError, match="no ambient_c trace"):
        run_scenarios(ss2, max_hosts=2, t_bins=10)
    with pytest.raises(ValueError, match="non-finite"):
        run_scenarios(ss2, max_hosts=2, t_bins=10,
                      ambient_c=np.full(10, 20.0, np.float32),
                      price=np.array([np.nan] * 10, np.float32))


def test_property_validation_fuzz():
    """Property check (optional hypothesis): any pue_base < 1 or non-finite
    is rejected; any valid (base, coeffs) combination is accepted."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=40, deadline=None)
    @given(base=(st.floats(min_value=-10, max_value=10)
                 | st.sampled_from([math.nan, math.inf, -math.inf])),
           load=st.floats(min_value=0, max_value=2))
    def check(base, load):
        ok = math.isfinite(base) and base >= 1.0
        if ok:
            s = Scenario(name="s", pue_base=base, pue_load_coeff=load)
            assert s.pue_base == base
        else:
            with pytest.raises(ValueError):
                Scenario(name="s", pue_base=base, pue_load_coeff=load)

    check()

    @settings(max_examples=40, deadline=None)
    @given(start=st.integers(min_value=-5, max_value=30),
           end=st.integers(min_value=-5, max_value=30))
    def check_windows(start, end):
        if 0 <= start < end:
            assert HostFailure(0, start, end).end_bin == end
        else:
            with pytest.raises(ValueError):
                HostFailure(0, start, end)

    check_windows()
