"""The gang what-if cell: it resolves, its run is correct on the program,
each fault its check can see fails it, its control fails by a compared
number, and its copies agree with the program and the repository's oracle.

Runs at a tiny size on the CPU (``tiny-philly``: 6 servers of 8 GPUs and 4
of 2, one day, gangs of up to 3 servers), through the harness with the
look for a chip skipped.
"""

import dataclasses
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import control, gen_philly, harness, spans
from chipbench import reference as ref
from chipbench import reference_gang as rg

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DATA = os.path.join(ROOT, "chipbench", "tests", "data")
CELL = "philly-whatif-s32"
SEED = 2 ** 31 + 907
READERS = ("host_ms.gang", "step_device_ms.gang", "device_idle.gang",
           "bw_roofline.gang", "gang_select_device_ms.gang",
           "gang_expand_device_ms.gang")


def bench():
    return harness.load_json(os.path.join(DATA, "BENCHMARK.tiny-gang.json"))


def run(seconds: float = 0.6, trace: bool = False) -> dict:
    return harness.run_cell(ROOT, "tiny-gang", SEED, seconds, trace,
                            t0=time.perf_counter(), require_chip=False,
                            bench=bench(), traffic_dir=DATA)


def test_cell_resolves():
    cell = harness.resolve(ROOT, CELL)
    for fn in ("setup", "window", "release", "check", "min_bytes",
               "cache_counters", "control"):
        assert callable(getattr(cell.driver, fn)), fn
    assert {m["name"] for m in cell.end_to_end} == {"whatif_rate",
                                                    "setup_s"}
    assert {m["name"] for m in cell.per_layer} == set(READERS[:4])
    assert cell.chips == 1 and cell.traffic["scenarios"] == 32
    assert cell.config["max_gang"] == 8
    cap = gen_philly.capacity(cell.config["servers"])
    assert cap.size == cell.config["num_hosts"] == 552
    assert int(cap.sum()) == cell.config["gpus"] == 2490


@pytest.mark.parametrize("name", READERS)
def test_every_gang_metric_has_a_reader(name):
    reader = harness.load_module(
        os.path.join(ROOT, "chipbench", "metrics", name + ".py"),
        "m_" + name.replace(".", "_"))
    assert callable(reader.read)


def test_scope_readers_read_per_batch():
    sp = spans.Spans(scope_s={"opendt.gang_select": 0.3,
                              "opendt.gang_expand": 0.05},
                     span_s={}, span_n={}, gaps=[])

    class Reduced:
        spans = sp

    r = harness.Run(cell=None, device_kind="TPU v5 lite", window={},
                    spans={}, counters={}, program_spans={}, trace=Reduced(),
                    trace_requests=2)
    none = harness.Run(cell=None, device_kind="TPU v5 lite", window={},
                       spans={}, counters={}, program_spans={})
    for name, want in (("gang_select_device_ms.gang", 150.0),
                       ("gang_expand_device_ms.gang", 25.0)):
        reader = harness.load_module(
            os.path.join(ROOT, "chipbench", "metrics", name + ".py"),
            "m_" + name.replace(".", "_"))
        assert reader.read(r) == pytest.approx(want)
        assert reader.read(none) is None


def test_sound_run_is_correct_and_counts_gangs():
    out = run(trace=True)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 2
    assert out["metrics"]["host_ms.gang"]["value"] > 0
    assert {"schedule_mismatch_jobs", "gang_blocked_bins_gap",
            "readout_rel_gap"} <= set(out["checks"])


def test_window_counts_gang_starts_and_blocked_bins():
    cell = harness.resolve(ROOT, "tiny-gang", bench(), DATA)
    st = cell.driver.setup(cell, SEED, 0.2)

    class NoTrace:
        def start(self):
            pass

        def stop(self, n):
            pass

    w = cell.driver.window(st, 0.2, NoTrace())
    assert w["counters"]["gang_starts"] > 0
    assert w["counters"]["gang_blocked_bins"] > 0
    # the failure lanes put an outage on a largest server
    lanes, _ = cell.driver.draw_batch(cell.config, cell.traffic, SEED, 0)
    cap = gen_philly.capacity(cell.config["servers"])
    outages = [h for ln in lanes for h, _, _, k in ln["failures"]
               if k == "outage"]
    assert outages and all(cap[h] == cap.max() for h in outages)


def test_min_bytes_counts_every_gang_host():
    cell = harness.resolve(ROOT, "tiny-gang", bench(), DATA)
    st = cell.driver.State()
    st.cfg, st.tr = cell.config, cell.traffic
    st.cap = gen_philly.capacity(cell.config["servers"])
    one = cell.driver.min_bytes(st)
    st.cfg = dict(cell.config, max_gang=cell.config["max_gang"] + 1)
    s, j = cell.traffic["scenarios"], cell.config["jobs_padded"]
    assert cell.driver.min_bytes(st) - one == s * j * 4


# -- faults the check can see --------------------------------------------------

def _fault(monkeypatch, alter=None, before=None):
    from repro.core import scenarios as sc

    real = sc.run_scenarios

    def broken(ss, **kw):
        if before is not None:
            ss = before(ss)
        sim, pred = real(ss, **kw)
        return (sim, pred) if alter is None else alter(sim, pred)

    broken._cache_size = real._cache_size
    monkeypatch.setattr(sc, "run_scenarios", broken)


def test_moved_gang_host_fails(monkeypatch):
    def alter(sim, pred):
        h = sim.job_hosts
        moved = jnp.where(h[..., 1:2] >= 0, (h[..., 1:2] + 1) % 6,
                          h[..., 1:2])
        return dataclasses.replace(
            sim, job_hosts=h.at[..., 1:2].set(moved)), pred
    _fault(monkeypatch, alter)
    out = run()
    assert not out["correct"]
    assert out["checks"]["schedule_mismatch_jobs"]["value"] > 0


def test_dropped_kill_fails(monkeypatch):
    """The outage drains its host instead of killing the gangs on it."""
    _fault(monkeypatch, before=lambda ss: dataclasses.replace(
        ss, fail_kill=jnp.zeros_like(ss.fail_kill)))
    out = run()
    assert not out["correct"]
    assert control.failing(out), out["checks"]


def test_unweighted_utilization_fails(monkeypatch):
    def alter(sim, pred):
        return sim, dataclasses.replace(
            pred, utilization=jnp.mean(sim.u_th, axis=-1))
    _fault(monkeypatch, alter)
    out = run()
    assert not out["correct"]
    assert out["checks"]["readout_rel_gap"]["value"] > \
        out["checks"]["readout_rel_gap"]["limit"]


def test_gang_blocked_count_altered_fails(monkeypatch):
    def alter(sim, pred):
        return dataclasses.replace(
            sim, gang_blocked_bins=sim.gang_blocked_bins + 1), pred
    _fault(monkeypatch, alter)
    out = run()
    assert not out["correct"]
    assert out["checks"]["gang_blocked_bins_gap"]["value"] == 1


# -- the control ----------------------------------------------------------------

def _control(seed, xp, dtype):
    return control.run_control(ROOT, "tiny-gang", seed, 0.6, xp, dtype,
                               require_chip=False, bench=bench(),
                               traffic_dir=DATA)


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 5])
def test_bf16_control_fails_a_limit(seed):
    out = _control(seed, jnp, jnp.bfloat16)  # tracecheck: disable=TC005
    assert not out["correct"], out["checks"]
    assert control.failing(out) == ["readout_rel_gap"], out["checks"]


def test_float32_control_passes():
    out = _control(7, np, np.float32)
    assert out["correct"], out["checks"]


# -- the copies -------------------------------------------------------------------

@pytest.mark.parametrize("seed", (5, 2 ** 31 + 11))
def test_philly_like_equals_program(seed):
    import repro.core  # noqa: F401  (the package fixes the import order)
    from repro.configs.philly import SERVERS, config, power_params
    from repro.traces.philly import PhillyTraceSpec, make_philly_like

    cfg = harness.load_json(os.path.join(ROOT, "chipbench", "configs",
                                         "philly-gpu.json"))
    assert [tuple(s) for s in cfg["servers"]] == list(SERVERS)
    np.testing.assert_array_equal(gen_philly.capacity(cfg["servers"]),
                                  config().host_units)
    p_idle, p_max = gen_philly.power_rows(cfg["servers"])
    np.testing.assert_array_equal(p_idle, power_params().p_idle)
    np.testing.assert_array_equal(p_max, power_params().p_max)
    trace = dict(cfg["trace"])
    phases = trace.pop("num_phases")
    spec = PhillyTraceSpec(days=2.0, max_jobs=4000, seed=seed, **{
        k: tuple(v) if isinstance(v, list) else v for k, v in trace.items()})
    want = make_philly_like(spec, config(), num_phases=phases)
    got = gen_philly.philly_like(seed, servers=cfg["servers"], days=2.0,
                                 max_jobs=4000, **cfg["trace"])
    for k, leaf in (("submit", "submit_bin"), ("dur", "duration_bins"),
                    ("cores", "cores"), ("util", "util_levels"),
                    ("valid", "valid")):
        np.testing.assert_array_equal(got[k], np.asarray(getattr(want, leaf)),
                                      err_msg=k)


@pytest.fixture(scope="module")
def oracle():
    sys.path.insert(0, os.path.join(ROOT, "tests"))
    import reference as oracle_mod
    return oracle_mod


@pytest.mark.parametrize("policy", ["first_fit", "best_fit", "worst_fit",
                                    "random_fit"])
def test_reference_agrees_with_the_oracle(oracle, policy):
    """The benchmark's numpy gang reference against the repository's
    loop-based oracle: schedule, hosts, blocked bins, utilization."""
    rng = np.random.default_rng(11)
    cap = [8] * 5 + [2] * 3
    j, t_bins = 120, 64
    cores = rng.choice([1, 2, 4, 8, 16, 24], j).tolist()
    submit = np.sort(rng.integers(0, 30, j)).tolist()
    dur = rng.integers(1, 15, j).tolist()
    util = rng.uniform(0.1, 1.0, (j, 4))
    fs = [10 ** 9] * 8
    fe = [0] * 8
    fk = [False] * 8
    fs[0], fe[0], fk[0] = 12, 30, True
    fs[6], fe[6] = 5, 25
    want_st, want_h, want_b = oracle.reference_gang_schedule(
        submit, dur, cores, [True] * j, num_hosts=8, cores_per_host=8,
        t_bins=t_bins, policy=policy, backfill_depth=2, host_capacity=cap,
        max_gang=3, fail_start=fs, fail_end=fe, fail_kill=fk)
    st, hosts, blocked = rg.schedule(
        submit, dur, cores, [True] * j, capacity=cap, t_bins=t_bins,
        policy=ref.POLICIES[policy],
        backfill_depth=2, max_gang=3, fail_start=fs, fail_end=fe,
        fail_kill=fk)
    assert st.tolist() == want_st and blocked == want_b
    assert [[h for h in row if h >= 0] for row in hosts.tolist()] == want_h
    assert sum(len(h) > 1 for h in want_h) >= 5
    u = rg.utilization(st, hosts, dur, cores, util, capacity=cap,
                       t_bins=t_bins, fail_start=fs, fail_kill=fk)
    want_u = oracle.reference_u_th(
        want_st, submit, dur, cores, util.tolist(), None, num_hosts=8,
        cores_per_host=8, t_bins=t_bins, fail_start=fs, fail_kill=fk,
        job_hosts=want_h, host_capacity=cap)
    np.testing.assert_allclose(u, np.asarray(want_u), rtol=1e-12,
                               atol=1e-12)
    # the program's jitted path agrees too (not only the two references)
    from repro.core.desim import simulate_utilization_masked

    sim = jax.jit(lambda w: simulate_utilization_masked(
        w, np.ones(8, bool), np.asarray(cap, np.int32), max_hosts=8,
        t_bins=t_bins, policy_id=ref.POLICIES[policy], backfill_depth=2,
        max_backfill=2, max_gang=3, fail_start=np.minimum(
            np.asarray(fs), np.iinfo(np.int32).max).astype(np.int32),
        fail_end=np.asarray(fe, np.int32), fail_kill=np.asarray(fk)))(
        _workload(submit, dur, cores, util))
    assert np.asarray(sim.job_start).tolist() == want_st
    np.testing.assert_array_equal(np.asarray(sim.job_hosts), hosts)


def _workload(submit, dur, cores, util):
    from repro.traces.schema import Workload

    return Workload(np.asarray(submit, np.int32), np.asarray(dur, np.int32),
                    np.asarray(cores, np.int32),
                    np.asarray(util, np.float32),
                    np.ones(len(submit), bool))
