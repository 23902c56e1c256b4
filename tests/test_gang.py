"""Gang jobs on a fleet of mixed server sizes vs the pure-Python oracle.

A small Philly-shaped fleet (6 servers of 8 GPUs, 4 of 2), 200 jobs of 1
to 24 GPUs over 96 bins, ``max_gang`` 3: jobs above 8 GPUs are gangs of
whole 8-GPU servers.  ``tests/reference.py`` schedules them in plain
Python (``reference_gang_schedule``) and reads them out with the
utilization weighted by capacity.  Every policy, with and without
backfill, with and without failures (an outage of an 8-GPU server that
kills gangs, and a drained server), runs as one lane of one batch; each
lane must match the oracle: every job's start and hosts exactly, the
utilization field and the read-out within the tolerances of
``test_oracle.py``.  Then: a lane equals its solo run, a hand-built gang
dies with one host's outage and frees the others at once, the SURF-shaped
program has no gang scope, and the configuration's guards.
"""

import functools

import jax
import numpy as np
import pytest

from reference import reference_gang_schedule, reference_scenario

from chipbench.spans import hlo_scopes
from repro.core import scenarios as sc
from repro.core.desim import simulate_utilization, simulate_utilization_masked
from repro.core.power import PowerParams
from repro.runtime.fault import DEGRADED, HostFailure
from repro.traces.schema import DatacenterConfig, Workload

UNITS = (8,) * 6 + (2,) * 4
DC = DatacenterConfig(num_hosts=10, cores_per_host=8, host_units=UNITS,
                      unit_tflops=12.0)
T_BINS, JOBS, MAX_GANG = 96, 200, 3
P_IDLE = [800.0] * 6 + [300.0] * 4
P_MAX = [2600.0] * 6 + [900.0] * 4
PARAMS = PowerParams(p_idle=np.asarray(P_IDLE, np.float32),
                     p_max=np.asarray(P_MAX, np.float32), r=2.0)
FAILURES = (HostFailure(host=1, start_bin=30, end_bin=60),
            HostFailure(host=7, start_bin=20, end_bin=50, kind=DEGRADED))
POLICIES = ("first_fit", "best_fit", "worst_fit", "random_fit")
CASES = [(p, bf, fail) for p in POLICIES for bf in (0, 2)
         for fail in (False, True)]


def _workload(seed=0):
    rng = np.random.default_rng(seed)
    gpus = rng.choice([1, 2, 4, 8, 16, 24], JOBS,
                      p=[0.5, 0.15, 0.1, 0.1, 0.1, 0.05]).astype(np.int32)
    submit = np.sort(rng.integers(0, T_BINS // 2, JOBS)).astype(np.int32)
    dur = rng.integers(1, 20, JOBS).astype(np.int32)
    util = rng.uniform(0.1, 1.0, (JOBS, 8)).astype(np.float32)
    return Workload(submit, dur, gpus, util, np.ones(JOBS, bool))


def _scenario(policy, bf, fail):
    return sc.Scenario(name=f"{policy}-{bf}-{fail}", policy=policy,
                       backfill_depth=bf, power_cap_w=15_000.0,
                       pue_base=1.1, pue_load_coeff=0.05,
                       pue_amb_coeff=0.01,
                       failures=FAILURES if fail else ())


@pytest.fixture(scope="module")
def batch():
    w = _workload()
    rng = np.random.default_rng(1)
    traces = dict(
        carbon_intensity=rng.uniform(80, 600, T_BINS).astype(np.float32),
        ambient_c=rng.uniform(10, 30, T_BINS).astype(np.float32),
        price=rng.uniform(0.05, 0.3, T_BINS).astype(np.float32))
    ss = sc.build_scenario_set(w, DC, [_scenario(*c) for c in CASES], PARAMS,
                               max_gang=MAX_GANG)
    sim, pred = sc.run_scenarios(ss, max_hosts=DC.num_hosts, t_bins=T_BINS,
                                 **traces)
    return w, ss, sim, pred, traces


def _wd(w):
    return dict(submit=np.asarray(w.submit_bin).tolist(),
                dur=np.asarray(w.duration_bins).tolist(),
                cores=np.asarray(w.cores).tolist(),
                util=np.asarray(w.util_levels).tolist(),
                valid=np.asarray(w.valid).tolist())


def _padded(job_hosts):
    out = np.full((len(job_hosts), MAX_GANG), -1)
    for i, hs in enumerate(job_hosts):
        out[i, :len(hs)] = hs
    return out


@pytest.mark.parametrize("case", CASES, ids=["-".join(map(str, c))
                                             for c in CASES])
def test_lane_matches_oracle(batch, case):
    w, ss, sim, pred, traces = batch
    i = CASES.index(case)
    want = reference_scenario(
        _wd(w), DC, _scenario(*case), t_bins=T_BINS, p_idle=P_IDLE,
        p_max=P_MAX, r=2.0, max_gang=MAX_GANG,
        **{k: [float(x) for x in v] for k, v in (
            ("intensity", traces["carbon_intensity"]),
            ("ambient", traces["ambient_c"]), ("price", traces["price"]))})
    # the schedule, every host of every gang: exact
    assert np.asarray(sim.job_start[i]).tolist() == want["job_start"]
    assert np.asarray(sim.job_host[i]).tolist() == want["job_host"]
    np.testing.assert_array_equal(np.asarray(sim.job_hosts[i]),
                                  _padded(want["job_hosts"]))
    assert int(sim.gang_blocked_bins[i]) == want["gang_blocked_bins"]
    # the utilization field and the read-out: f32 engine vs f64 oracle
    np.testing.assert_allclose(np.asarray(sim.u_th[i], np.float64),
                               np.asarray(want["u_th"]), rtol=2e-5,
                               atol=1e-6)
    for got, key, rtol in ((pred.power_demand_w, "demand", 1e-4),
                           (pred.power_w, "power", 1e-4),
                           (pred.utilization, "util", 1e-4),
                           (pred.pue, "pue", 1e-5),
                           (pred.gco2, "gco2", 2e-4),
                           (pred.energy_cost, "cost", 2e-4)):
        np.testing.assert_allclose(np.asarray(got[i], np.float64),
                                   np.asarray(want[key]), rtol=rtol,
                                   atol=1e-6, err_msg=key)
    np.testing.assert_allclose(
        np.asarray(pred.tflops[i], np.float64),
        np.asarray(want["util"]) * sum(UNITS) * 12.0, rtol=1e-4, atol=1e-6)
    # not vacuous: gangs started, and some bin stopped at a gang head
    hosts = np.asarray(sim.job_hosts[i])
    assert (hosts[:, 1] >= 0).sum() >= 5
    assert want["gang_blocked_bins"] > 0


def test_failure_lanes_kill_gangs(batch):
    """The failure lanes are not vacuous: some gang runs on host 1 into
    its outage at bin 30, so the kill rule (checked against the oracle
    lane by lane above) is exercised."""
    w, ss, sim, pred, traces = batch
    dur = np.asarray(w.duration_bins)
    killed = 0
    for i, (_, _, fail) in enumerate(CASES):
        st, hosts = np.asarray(sim.job_start[i]), np.asarray(sim.job_hosts[i])
        gang_on_1 = (hosts[:, 1] >= 0) & (hosts == 1).any(axis=1)
        cut = gang_on_1 & (st < 30) & (st + dur > 30)
        if fail:
            killed += int(cut.sum())
    assert killed > 0


@pytest.mark.parametrize("lane", [0, 5, 11, 14])
def test_vmapped_lane_equals_solo(batch, lane):
    """One lane of the batch equals the masked DES run alone on its
    inputs, bit for bit."""
    w, ss, sim, pred, traces = batch
    fail = CASES[lane][2]
    solo = jax.jit(functools.partial(
        simulate_utilization_masked, max_hosts=DC.num_hosts, t_bins=T_BINS,
        max_backfill=ss.max_backfill, max_gang=MAX_GANG,
        max_units=ss.max_units))(
        jax.tree.map(lambda x: x[lane], ss.workload), ss.host_mask_s[lane],
        ss.host_units[lane], policy_id=ss.policy_id[lane],
        backfill_depth=ss.backfill_depth[lane],
        fail_start=ss.fail_start[lane] if fail else None,
        fail_end=ss.fail_end[lane] if fail else None,
        fail_kill=ss.fail_kill[lane] if fail else None)
    for leaf in ("u_th", "queue_len", "running", "job_start", "job_host",
                 "job_hosts", "gang_blocked_bins"):
        np.testing.assert_array_equal(np.asarray(getattr(solo, leaf)),
                                      np.asarray(getattr(sim, leaf)[lane]),
                                      err_msg=leaf)


def test_simulate_utilization_runs_gangs():
    """The single-topology entry point takes the per-host capacities as a
    tuple and schedules as the oracle does."""
    w = _workload(3)
    sim = simulate_utilization(w, num_hosts=10, cores_per_host=UNITS,
                               t_bins=T_BINS, policy="best_fit",
                               max_gang=MAX_GANG)
    d = _wd(w)
    start, hosts, blocked = reference_gang_schedule(
        d["submit"], d["dur"], d["cores"], d["valid"], num_hosts=10,
        cores_per_host=8, t_bins=T_BINS, policy="best_fit",
        host_capacity=UNITS, max_gang=MAX_GANG)
    assert np.asarray(sim.job_start).tolist() == start
    np.testing.assert_array_equal(np.asarray(sim.job_hosts), _padded(hosts))
    assert int(sim.gang_blocked_bins) == blocked


def test_gang_killed_by_one_outage_frees_its_other_hosts():
    """A 16-GPU gang on hosts 0 and 1; host 1 fails at bin 5 until bin 15.
    The gang dies at bin 5: host 0 comes back at once and takes the next
    8-GPU job at bin 5, host 1 only at bin 15."""
    w = Workload(np.asarray([0, 1, 1], np.int32),
                 np.asarray([20, 30, 30], np.int32),
                 np.asarray([16, 8, 8], np.int32),
                 np.full((3, 2), 0.5, np.float32), np.ones(3, bool))
    fs = np.asarray([np.iinfo(np.int32).max, 5], np.int32)
    fe = np.asarray([0, 15], np.int32)
    fk = np.asarray([False, True])
    sim = jax.jit(functools.partial(
        simulate_utilization_masked, max_hosts=2, t_bins=40, max_gang=2,
        max_units=8))(
        w, np.ones(2, bool), np.asarray([8, 8], np.int32),
        policy_id=0, fail_start=fs, fail_end=fe, fail_kill=fk)
    assert np.asarray(sim.job_start).tolist() == [0, 5, 15]
    assert np.asarray(sim.job_hosts).tolist() == [[0, 1], [0, -1], [1, -1]]
    u = np.asarray(sim.u_th)
    np.testing.assert_array_equal(u[:5], 0.5)      # the gang on both hosts
    np.testing.assert_array_equal(u[5:15, 1], 0.0)  # host 1 down
    np.testing.assert_array_equal(u[5:35, 0], 0.5)  # job 1 on host 0
    start, hosts, _ = reference_gang_schedule(
        [0, 1, 1], [20, 30, 30], [16, 8, 8], [True] * 3, num_hosts=2,
        cores_per_host=8, t_bins=40, policy="first_fit",
        fail_start=fs.tolist(), fail_end=fe.tolist(), fail_kill=fk.tolist(),
        max_gang=2)
    assert start == [0, 5, 15] and hosts == [[0, 1], [0], [1]]


def _scopes(ss, t_bins):
    text = jax.jit(functools.partial(
        sc._scenario_lanes, max_hosts=ss.max_hosts, t_bins=t_bins,
        max_starts_per_bin=64, model="opendc", chunk=False)).lower(
        ss, None, None, None).compile().as_text()
    return set(hlo_scopes(text).values())


def test_gang_machinery_is_compiled_out_of_surf_programs(batch):
    """A SURF-shaped batch (one server size, ``max_gang`` 1) compiles no
    gang scope; the gang batch compiles both."""
    surf_dc = DatacenterConfig(num_hosts=10, cores_per_host=8)
    w = _workload()
    surf = sc.build_scenario_set(
        Workload(w.submit_bin, w.duration_bins, np.minimum(w.cores, 8),
                 w.util_levels, w.valid), surf_dc,
        [sc.Scenario(name="a"), sc.Scenario(name="b", failures=FAILURES)],
        PowerParams())
    assert surf.max_gang == 1 and surf.host_units is None
    scopes = _scopes(surf, T_BINS)
    assert "opendt.des_scan" in scopes
    assert not {"opendt.gang_select", "opendt.gang_expand"} & scopes
    gang = _scopes(batch[1], T_BINS)
    assert {"opendt.gang_select", "opendt.gang_expand"} <= gang


def test_summaries_of_a_mixed_fleet(batch):
    """Summaries read the weighted utilization and count GPU-hours."""
    w, ss, sim, pred, traces = batch
    out = sc.summarize_scenarios(ss, sim, pred,
                                 carbon_intensity=traces["carbon_intensity"])
    for i, s in enumerate(out):
        assert s.cores_per_host == 8 and s.num_hosts == 10
        assert s.mean_util == pytest.approx(
            float(np.asarray(pred.utilization[i]).mean()))
    assert out[0].cpu_hours == pytest.approx(float(
        (np.asarray(w.cores) * np.asarray(w.duration_bins)).sum() / 12))


def test_mixed_fleet_guards():
    w = _workload()
    with pytest.raises(ValueError, match="cores_per_host cannot be"):
        sc.build_scenario_set(w, DC, [sc.Scenario(cores_per_host=4)])
    with pytest.raises(ValueError, match="capacities for 10"):
        sc.build_scenario_set(w, DC, [sc.Scenario(num_hosts=12)],
                              max_hosts=12)
    with pytest.raises(ValueError, match="needs 3 whole 8-unit servers"):
        sc.build_scenario_set(w, DC, [sc.Scenario()], max_gang=2)
    ss = sc.build_scenario_set(w, DC, [sc.Scenario()], max_gang=MAX_GANG)
    with pytest.raises(ValueError, match="does not weight hosts"):
        sc.run_scenarios(ss, max_hosts=10, t_bins=T_BINS, use_pallas=True)
    with pytest.raises(ValueError, match="mixed sizes through run_scenarios"):
        from repro.core.desim import simulate
        simulate(w, DC, T_BINS)


def test_mixed_fleet_config():
    assert DC.peak_tflops == sum(UNITS) * 12.0
    restored = DatacenterConfig(num_hosts=10, cores_per_host=8,
                                host_units=list(UNITS), unit_tflops=12.0)
    assert restored == DC and hash(restored) == hash(DC)
    # a uniform fleet keeps its peak formula
    assert DatacenterConfig().peak_tflops == pytest.approx(
        277 * 16 * 2.1 * 16 / 1e3)
    with pytest.raises(ValueError, match="largest entry"):
        DatacenterConfig(num_hosts=2, cores_per_host=4, host_units=(8, 2))


def test_philly_like_trace():
    import repro.core  # noqa: F401  (the package fixes the import order)
    from repro.configs.philly import config, power_params
    from repro.traces.philly import PhillyTraceSpec, make_philly_like

    dc = config()
    assert (dc.num_hosts, sum(dc.host_units), dc.cores_per_host) == \
        (552, 2490, 8)
    assert dc.host_units[230] == 8 and dc.host_units[231] == 2
    p = power_params()
    assert float(p.p_idle[0]) == 800.0 and float(p.p_max[551]) == 900.0
    spec = PhillyTraceSpec(days=2.0, max_jobs=4000, seed=4)
    w = make_philly_like(spec, dc)
    v = np.asarray(w.valid)
    c, d = np.asarray(w.cores)[v], np.asarray(w.duration_bins)[v]
    assert w.num_jobs == 4000 and 2500 < v.sum() < 3500
    assert np.all(np.diff(np.asarray(w.submit_bin)) >= 0)   # FCFS order
    assert set(np.unique(c)) <= set(spec.gpu_sizes)
    demand = (c * d).sum() / (2490 * 576)
    assert 0.5 < demand <= 0.70 + 1e-6
    gang = c > 8
    assert 0.02 < gang.mean() < 0.1 and (c * d)[gang].sum() / (c * d).sum() > 0.4
    again = make_philly_like(spec, dc)
    np.testing.assert_array_equal(np.asarray(again.cores), np.asarray(w.cores))
