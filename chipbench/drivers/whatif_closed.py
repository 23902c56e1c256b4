"""Closed loop of what-if sweeps: the operator waits for each batch.

A request is one batch of S fresh scenarios against the deployment's week
(the configuration's, the same for every seed):
``build_scenario_set`` -> ``run_scenarios`` (program defaults) ->
``summarize_scenarios``.  Every lane keeps its place in a fixed grid
(scheduler x failures x PUE x cap, as the traffic file lists them), and each
batch draws new values from the seed: failure hosts and bins, cap and PUE
levels scaled by a few per cent, and carbon, ambient and price traces.  The
statics of the ``ScenarioSet`` are pinned, so no batch compiles anew.

``correct`` compares sampled lanes of the batches the window finished with
the plain reference: the schedule exactly, the read-out by its largest
relative gap.
"""

from __future__ import annotations

import dataclasses
import math
import time

import numpy as np

from chipbench import gen
from chipbench import reference as ref
from chipbench import twin_check

NEVER = np.iinfo(np.int32).max
#: batch index of the warm-up request, which the window never draws
WARMUP = 1 << 30


class State:
    pass


def _workload(cfg: dict) -> dict:
    """The deployment's week: the same for every seed, so that every run
    does the same work and only the scenarios change."""
    return gen.surf22_like(
        cfg["trace_seed"], num_hosts=cfg["num_hosts"],
        cores_per_host=cfg["cores_per_host"],
        days=cfg["days"], max_jobs=cfg["jobs_padded"], **cfg["trace"])


def draw_batch(cfg: dict, tr: dict, seed: int, b: int) -> list[dict]:
    """The S lanes of batch ``b``: one dict of plain values per lane."""
    rng = np.random.default_rng([seed, 0x5CE7, b])
    hosts, t_bins = cfg["num_hosts"], cfg["t_bins"]
    lanes = []
    for i in range(tr["scenarios"]):
        policy, depth = tr["schedulers"][i % len(tr["schedulers"])]
        k = i // len(tr["schedulers"])
        fail = k % 2 == 1
        pue = tr["pue_levels"][(k // 2) % len(tr["pue_levels"])]
        cap = tr["caps_w"][(k // (2 * len(tr["pue_levels"])))
                           % len(tr["caps_w"])]
        jitter = rng.uniform(0.95, 1.05, 3)
        lane = dict(policy=policy, backfill=int(depth),
                    cap=None if cap is None else float(cap * jitter[0]),
                    pue=(float(pue[0]), float(pue[1] * jitter[1]),
                         float(pue[2] * jitter[2])),
                    failures=[])
        h_out, h_deg = rng.choice(hosts, 2, replace=False)
        starts = rng.integers(0, t_bins // 2, 2)
        lens = rng.integers(t_bins // 20, t_bins // 4, 2)
        if fail:
            lane["failures"] = [
                (int(h_out), int(starts[0]), int(starts[0] + lens[0]),
                 "outage"),
                (int(h_deg), int(starts[1]), int(starts[1] + lens[1]),
                 "degraded")]
        lanes.append(lane)
    trace_seeds = rng.integers(0, 2 ** 31, 3)
    traces = dict(carbon_intensity=gen.diurnal_carbon(t_bins, trace_seeds[0]),
                  ambient_c=gen.diurnal_ambient(t_bins, trace_seeds[1]),
                  price=gen.diurnal_price(t_bins, trace_seeds[2]))
    return lanes, traces


def _scenarios(lanes: list[dict]):
    from repro.core.scenarios import Scenario
    from repro.runtime.fault import HostFailure

    out = []
    for i, ln in enumerate(lanes):
        base, load, amb = ln["pue"]
        out.append(Scenario(
            name=f"s{i}", policy=ln["policy"], backfill_depth=ln["backfill"],
            power_cap_w=ln["cap"], pue_base=base, pue_load_coeff=load,
            pue_amb_coeff=amb,
            failures=tuple(HostFailure(host=h, start_bin=s, end_bin=e,
                                       kind=k)
                           for h, s, e, k in ln["failures"])))
    return out


def setup(cell, seed: int, seconds: float) -> State:
    import repro.core  # noqa: F401  (the package fixes the import order)
    from repro.core.power import PowerParams
    from repro.traces.schema import DatacenterConfig, Workload

    st = State()
    st.cfg, st.tr, st.seed = cell.config, cell.traffic, seed
    cfg = st.cfg
    st.days = cfg["days"]
    w = _workload(cfg)
    st.w = w
    st.workload = Workload(submit_bin=w["submit"], duration_bins=w["dur"],
                           cores=w["cores"], util_levels=w["util"],
                           valid=w["valid"])
    st.dc = DatacenterConfig(num_hosts=cfg["num_hosts"],
                             cores_per_host=cfg["cores_per_host"],
                             ghz=cfg["ghz"])
    st.params = PowerParams(**cfg["power_model"])
    st.batches = []
    # warm-up: the window's one program, compiled (or read from the
    # persistent cache) and run once, on a week of the same shapes whose
    # jobs are all padding, so that the scan has nothing to place
    _request(st, WARMUP, dataclasses.replace(
        st.workload, valid=np.zeros_like(w["valid"])))
    st.batches = []
    return st


def _request(st: State, b: int, workload=None) -> dict:
    """One batch, start to summaries; returns its record."""
    import jax

    from repro.core import scenarios as sc

    lanes, traces = draw_batch(st.cfg, st.tr, st.seed, b)
    t0 = time.perf_counter()
    ss = sc.build_scenario_set(
        st.workload if workload is None else workload, st.dc,
        _scenarios(lanes), st.params,
        max_hosts=st.cfg["num_hosts"], max_backfill=st.tr["max_backfill"],
        has_failures=True, pue_on=True)
    t1 = time.perf_counter()
    sim, pred = sc.run_scenarios(ss, max_hosts=st.cfg["num_hosts"],
                                 t_bins=st.cfg["t_bins"], **traces)
    jax.block_until_ready((sim, pred))
    t2 = time.perf_counter()
    summaries = sc.summarize_scenarios(
        ss, sim, pred, carbon_intensity=traces["carbon_intensity"])
    t3 = time.perf_counter()
    rec = dict(lanes=lanes, traces=traces, t3=t3,
               host_s=(t1 - t0) + (t3 - t2), job_start=sim.job_start,
               job_host=sim.job_host, pred=pred, n=len(summaries))
    st.batches.append(rec)
    return rec


def cache_counters(st: State) -> dict:
    from repro.core.scenarios import run_scenarios

    return {"run_scenarios": run_scenarios._cache_size()}


def window(st: State, seconds: float, tracer) -> dict:
    t_start = time.perf_counter()
    end = t_start + seconds
    b = 0
    trace_from = 1
    trace_to = trace_from + st.tr["trace_batches"]
    while True:
        if b == trace_from:
            tracer.start()
        _request(st, b)
        b += 1
        if b == trace_to:
            tracer.stop(trace_to - trace_from)
        if time.perf_counter() >= end and b >= st.tr["min_batches"]:
            break
    tracer.stop(b - trace_from)
    t_last = st.batches[-1]["t3"]
    done = sum(r["n"] for r in st.batches)
    host = [r["host_s"] for r in st.batches]
    return {"whatif_rate": done * st.days / (t_last - t_start),
            "spans": {"host_whatif": host},
            "notes": {"batches": len(st.batches),
                      "window_s": t_last - t_start}}


def release(st: State) -> None:
    """Pull what the check needs to the host and drop the device buffers."""
    for r in st.batches:
        r["job_start"] = np.asarray(r["job_start"])
        r["job_host"] = np.asarray(r["job_host"])
        r["pred"] = {k: np.asarray(v) for k, v in
                     vars(r["pred"]).items() if v is not None}


LEAVES = ("power_w", "power_demand_w", "energy_kwh", "utilization", "tflops",
          "efficiency", "gco2", "pue", "energy_cost")


def lane_reference(cfg: dict, w: dict, lane: dict, traces: dict,
                   power_model: dict) -> dict:
    """The plain reference of one lane: schedule, utilization, read-out."""
    hosts, t_bins = cfg["num_hosts"], cfg["t_bins"]
    fs = np.full(hosts, NEVER, np.int64)
    fe = np.zeros(hosts, np.int64)
    fk = np.zeros(hosts, bool)
    for h, s, e, k in lane["failures"]:
        fs[h], fe[h], fk[h] = s, e, k == "outage"
    start, host = ref.schedule(
        w["submit"], w["dur"], w["cores"], w["valid"], num_hosts=hosts,
        cores_per_host=cfg["cores_per_host"], t_bins=t_bins,
        policy=ref.POLICIES[lane["policy"]], backfill_depth=lane["backfill"],
        max_starts_per_bin=cfg["max_starts_per_bin"], fail_start=fs,
        fail_end=fe, fail_kill=fk)
    u = ref.utilization(start, host, w["dur"], w["cores"], w["util"],
                        num_hosts=hosts, cores_per_host=cfg["cores_per_host"],
                        t_bins=t_bins, fail_start=fs, fail_kill=fk)
    tt = np.arange(t_bins)[:, None]
    online = ~(fk[None, :] & (tt >= fs[None, :]) & (tt < fe[None, :]))
    base, load, amb = lane["pue"]
    out = ref.readout(
        np, np.float64, u, p_idle=power_model["p_idle"],
        p_max=power_model["p_max"], r=power_model["r"], online=online,
        cap=math.inf if lane["cap"] is None else lane["cap"],
        intensity=traces["carbon_intensity"],
        pue=(base, amb, cfg["pue_amb_ref"], load),
        ambient=traces["ambient_c"], price=traces["price"],
        peak_tflops=twin_check.peak_tflops(cfg))
    return dict(job_start=start, job_host=host,
                **{k: np.asarray(v, np.float64) for k, v in out.items()})


def compare_lane(got: dict, want: dict) -> tuple[int, float]:
    """(jobs whose start or host differ, largest read-out gap) of a lane."""
    sched = int(np.sum((np.asarray(got["job_start"]) != want["job_start"])
                       | (np.asarray(got["job_host"]) != want["job_host"])))
    gap = max(twin_check.rel_gap(got[k], want[k]) for k in LEAVES)
    return sched, gap


def check_lanes(st: State) -> list[tuple[int, int]]:
    """(batch, lane) pairs to compare, drawn from the seed over the batches
    the window finished: for every scheduler, a lane with failures and one
    without."""
    rng = np.random.default_rng([st.seed, 0xC4EC])
    n_sched = len(st.tr["schedulers"])
    picks = []
    for p in range(n_sched):
        for fail in (0, 1):
            lanes = [i for i in range(st.tr["scenarios"])
                     if i % n_sched == p and (i // n_sched) % 2 == fail]
            b = int(rng.integers(0, len(st.batches)))
            picks.append((b, int(rng.choice(lanes))))
    return picks


def check(st: State):
    limits = st.tr["limits"]
    sched_bad, gap = 0, 0.0
    for b, i in check_lanes(st):
        r = st.batches[b]
        want = lane_reference(st.cfg, st.w, r["lanes"][i], r["traces"],
                              st.cfg["power_model"])
        got = dict(job_start=r["job_start"][i], job_host=r["job_host"][i],
                   **{k: r["pred"][k][i] for k in LEAVES})
        s, g = compare_lane(got, want)
        sched_bad += s
        gap = max(gap, g)
    checks = [dict(name="schedule_mismatch_jobs", value=sched_bad,
                   limit=limits["schedule_mismatch_jobs"]),
              dict(name="readout_rel_gap", value=gap,
                   limit=limits["readout_rel_gap"])]
    return checks, len(st.batches), 0


def min_bytes(st: State) -> float:
    """Least bytes one batch moves: its inputs read once, outputs written."""
    s, t, h = st.tr["scenarios"], st.cfg["t_bins"], st.cfg["num_hosts"]
    j, phases = st.cfg["jobs_padded"], st.cfg["trace"]["num_phases"]
    workload = s * j * (3 * 4 + 4 * phases + 1)
    traces = 3 * t * 4
    sim_out = s * t * h * 4 + 2 * s * t * 4 + 2 * s * j * 4
    pred_out = len(LEAVES) * s * t * 4
    return float(workload + traces + sim_out + pred_out)


def control(cell, xp, dtype) -> list[tuple]:
    """The control: the program's own read-out in ``dtype``, switched on
    where the window calls ``run_scenarios`` (the fused read-out kernel,
    its derived leaves computed in ``dtype``).  Returns the
    ``(module, name, stand-in)`` to patch for a run."""
    from repro.core import scenarios as sc

    real = sc.run_scenarios
    precision = {"bfloat16": "bf16", "float32": "f32"}[np.dtype(dtype).name]

    def standin(ss, **kw):
        return real(ss, use_pallas=True, readout_precision=precision, **kw)

    standin._cache_size = real._cache_size
    return [(sc, "run_scenarios", standin)]
