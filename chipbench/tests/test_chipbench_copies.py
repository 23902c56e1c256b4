"""The benchmark's copies of the generators equal the program's, and its
plain reference agrees with the repository's loop-based oracle."""

import math
import os
import sys

import numpy as np
import pytest

from chipbench import gen
from chipbench import reference as ref

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SEEDS = (5, 2 ** 31 + 11)


@pytest.fixture(scope="module")
def oracle():
    sys.path.insert(0, os.path.join(ROOT, "tests"))
    import reference as oracle_mod
    return oracle_mod


@pytest.mark.parametrize("seed", SEEDS)
def test_surf22_like_equals_program(seed):
    import repro.core  # noqa: F401
    from repro.traces.schema import DatacenterConfig
    from repro.traces.surf import SurfTraceSpec, make_surf22_like

    dc = DatacenterConfig(num_hosts=16)
    want = make_surf22_like(SurfTraceSpec(days=2.0, seed=seed), dc)
    got = gen.surf22_like(seed, num_hosts=16, days=2.0)
    np.testing.assert_array_equal(got["submit"], np.asarray(want.submit_bin))
    np.testing.assert_array_equal(got["dur"], np.asarray(want.duration_bins))
    np.testing.assert_array_equal(got["cores"], np.asarray(want.cores))
    np.testing.assert_array_equal(got["util"], np.asarray(want.util_levels))
    padded = gen.surf22_like(seed, num_hosts=16, days=2.0,
                             max_jobs=got["num_valid"] + 7)
    assert padded["submit"].shape == (got["num_valid"] + 7,)
    assert padded["valid"].sum() == got["num_valid"]


@pytest.mark.parametrize("seed", SEEDS)
def test_ground_truth_equals_program(seed):
    import repro.core  # noqa: F401
    from repro.traces.surf import GroundTruthSpec, synthesize_ground_truth

    u = np.random.default_rng(seed).uniform(0, 1, (600, 12))
    want = synthesize_ground_truth(u, GroundTruthSpec(seed=seed % 1000))
    got = gen.ground_truth(u, seed=seed % 1000)
    # the program raises u to r in float32; the copy in float64
    np.testing.assert_allclose(got, want, rtol=2e-6)


@pytest.mark.parametrize("seed", SEEDS)
def test_diurnal_traces_equal_program(seed):
    import repro.core  # noqa: F401
    from repro.traces.carbon import make_diurnal_carbon
    from repro.traces.price import make_diurnal_price
    from repro.traces.thermal import make_diurnal_ambient

    np.testing.assert_array_equal(gen.diurnal_carbon(700, seed),
                                  make_diurnal_carbon(700, seed=seed))
    np.testing.assert_array_equal(gen.diurnal_ambient(700, seed),
                                  make_diurnal_ambient(700, seed=seed))
    np.testing.assert_array_equal(gen.diurnal_price(700, seed),
                                  make_diurnal_price(700, seed=seed))


@pytest.mark.parametrize("seed", SEEDS)
def test_synthetic_window_equals_producer(seed):
    from repro.serve import SyntheticProducer

    prod = SyntheticProducer("t", hosts=9, bins_per_window=12, num_windows=3,
                             seed=seed, period_s=2.0, jitter_s=0.5)
    due = gen.due_times(seed, 3, start=0.0, period_s=2.0, jitter_s=0.5)
    np.testing.assert_array_equal(due, prod._due)
    for ev in prod.poll(math.inf):
        u, p = gen.synthetic_window(seed, ev.window, hosts=9,
                                    bins_per_window=12)
        np.testing.assert_array_equal(u, ev.u_th)
        # the producer's hidden model runs in float32, the copy in float64
        np.testing.assert_allclose(p, ev.power_w, rtol=1e-6)


def _case(seed, j=30, hosts=4, cph=8, t_bins=48):
    rng = np.random.default_rng(seed)
    submit = np.sort(rng.integers(0, t_bins // 2, j))
    dur = rng.integers(1, 9, j)
    cores = rng.integers(1, cph + 1, j)
    util = rng.uniform(0.1, 1.0, (j, 3))
    valid = np.ones(j, bool)
    return submit, dur, cores, util, valid


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("policy,depth", [("worst_fit", 0), ("best_fit", 2),
                                          ("first_fit", 0), ("random_fit", 1)])
def test_reference_matches_oracle(oracle, seed, policy, depth):
    submit, dur, cores, util, valid = _case(seed)
    hosts, cph, t_bins = 4, 8, 48
    fs = [t_bins + 10 ** 6] * hosts
    fe = [0] * hosts
    fk = [False] * hosts
    fs[1], fe[1], fk[1] = 10, 30, True
    fs[2], fe[2] = 5, 20
    want_s, want_h = oracle.reference_schedule(
        submit.tolist(), dur.tolist(), cores.tolist(), valid.tolist(),
        num_hosts=hosts, cores_per_host=cph, t_bins=t_bins, policy=policy,
        backfill_depth=depth, fail_start=fs, fail_end=fe, fail_kill=fk)
    got_s, got_h = ref.schedule(
        submit, dur, cores, valid, num_hosts=hosts, cores_per_host=cph,
        t_bins=t_bins, policy=ref.POLICIES[policy], backfill_depth=depth,
        fail_start=fs, fail_end=fe, fail_kill=fk)
    assert got_s.tolist() == want_s and got_h.tolist() == want_h
    want_u = oracle.reference_u_th(
        want_s, submit.tolist(), dur.tolist(), cores.tolist(), util.tolist(),
        want_h, num_hosts=hosts, cores_per_host=cph, t_bins=t_bins,
        fail_start=fs, fail_kill=fk)
    u = ref.utilization(got_s, got_h, dur, cores, util, num_hosts=hosts,
                        cores_per_host=cph, t_bins=t_bins, fail_start=fs,
                        fail_kill=fk)
    np.testing.assert_allclose(u, want_u, rtol=1e-12, atol=1e-12)
    online = [[not (fk[h] and fs[h] <= t < fe[h]) for h in range(hosts)]
              for t in range(t_bins)]
    rng = np.random.default_rng(seed)
    ci = rng.uniform(80, 600, t_bins)
    amb = rng.uniform(5, 30, t_bins)
    price = rng.uniform(0.05, 0.3, t_bins)
    pue = (1.1, 0.01, 18.0, 0.1)
    want = oracle.reference_readout(
        want_u, p_idle=60.0, p_max=300.0, r=2.3, power_cap_w=700.0,
        intensity=ci.tolist(), online=online, pue=pue, ambient=amb.tolist(),
        price=price.tolist())
    got = ref.readout(np, np.float64, u, p_idle=60.0, p_max=300.0, r=2.3,
                      online=np.asarray(online), cap=700.0, intensity=ci,
                      pue=(1.1, 0.01, 18.0, 0.1), ambient=amb, price=price)
    for k_got, k_want in (("power_w", "power"), ("power_demand_w", "demand"),
                          ("utilization", "util"), ("energy_kwh",
                                                    "energy_kwh"),
                          ("gco2", "gco2"), ("pue", "pue"),
                          ("energy_cost", "cost")):
        np.testing.assert_allclose(got[k_got], want[k_want], rtol=1e-12,
                                   err_msg=k_got)
