"""Closed loop of what-if sweeps on a gang-scheduled GPU cluster.

A request is one batch of S fresh scenarios against the deployment's week
(the configuration's, the same for every seed), as ``whatif_closed`` runs
them, on a fleet of mixed server sizes with gang jobs:
``build_scenario_set(max_gang=...)`` -> ``run_scenarios`` ->
``summarize_scenarios``.  Every lane keeps its place in a fixed grid
(scheduler x failures x PUE x cap, as the traffic file lists them), and
each batch draws new values from the seed: the failure bins (an outage of
one largest server, which kills the gangs on it, and a degraded server),
cap and PUE levels scaled by a few per cent, and carbon, ambient and price
traces.  The statics of the ``ScenarioSet`` are pinned, so no batch
compiles anew.

``correct`` compares sampled lanes of the batches the window finished with
the plain gang reference (:mod:`chipbench.reference_gang`): the schedule
exactly, every host of every gang included, the gang-blocked bins exactly,
and the read-out by its largest relative gap.
"""

from __future__ import annotations

import dataclasses
import math
import time

import numpy as np

from chipbench import gen, gen_philly, harness, twin_check
from chipbench import reference as ref
from chipbench import reference_gang as rg
from chipbench.drivers.whatif_closed import (
    LEAVES,
    NEVER,
    WARMUP,
    _scenarios,
    cache_counters,
    check_lanes,
)


class State:
    pass


def _workload(cfg: dict) -> dict:
    """The deployment's week, the same for every seed."""
    return gen_philly.philly_like(
        cfg["trace_seed"], servers=cfg["servers"], days=cfg["days"],
        max_jobs=cfg["jobs_padded"], **cfg["trace"])


def draw_batch(cfg: dict, tr: dict, seed: int, b: int):
    """The S lanes of batch ``b`` and its traces, in plain values."""
    rng = np.random.default_rng([seed, 0x6A46, b])
    cap = gen_philly.capacity(cfg["servers"])
    big = np.nonzero(cap == cap.max())[0]
    t_bins = cfg["t_bins"]
    n_sched = len(tr["schedulers"])
    lanes = []
    for i in range(tr["scenarios"]):
        policy, depth = tr["schedulers"][i % n_sched]
        k = i // n_sched
        fail = k % 2 == 1
        pue = tr["pue_levels"][(k // 2) % len(tr["pue_levels"])]
        c = tr["caps_w"][(k // (2 * len(tr["pue_levels"])))
                         % len(tr["caps_w"])]
        jitter = rng.uniform(0.95, 1.05, 3)
        lane = dict(policy=policy, backfill=int(depth),
                    cap=None if c is None else float(c * jitter[0]),
                    pue=(float(pue[0]), float(pue[1] * jitter[1]),
                         float(pue[2] * jitter[2])),
                    failures=[])
        h_out = int(rng.choice(big))
        h_deg = int(rng.choice(np.delete(np.arange(cap.size), h_out)))
        starts = rng.integers(0, t_bins // 2, 2)
        lens = rng.integers(t_bins // 20, t_bins // 4, 2)
        if fail:
            lane["failures"] = [
                (h_out, int(starts[0]), int(starts[0] + lens[0]), "outage"),
                (h_deg, int(starts[1]), int(starts[1] + lens[1]),
                 "degraded")]
        lanes.append(lane)
    trace_seeds = rng.integers(0, 2 ** 31, 3)
    traces = dict(carbon_intensity=gen.diurnal_carbon(t_bins, trace_seeds[0]),
                  ambient_c=gen.diurnal_ambient(t_bins, trace_seeds[1]),
                  price=gen.diurnal_price(t_bins, trace_seeds[2]))
    return lanes, traces


def setup(cell, seed: int, seconds: float) -> State:
    import repro.core  # noqa: F401  (the package fixes the import order)
    from repro.core.power import PowerParams
    from repro.traces.schema import DatacenterConfig, Workload

    if "host_units" not in DatacenterConfig.__dataclass_fields__:
        raise harness.BenchError(
            "the program has no fleets of mixed server sizes "
            "(DatacenterConfig.host_units), so it cannot run this cell")
    st = State()
    st.cfg, st.tr, st.seed = cell.config, cell.traffic, seed
    cfg = st.cfg
    st.days = cfg["days"]
    st.cap = gen_philly.capacity(cfg["servers"])
    st.p_idle, st.p_max = gen_philly.power_rows(cfg["servers"])
    w = _workload(cfg)
    st.w = w
    st.workload = Workload(submit_bin=w["submit"], duration_bins=w["dur"],
                           cores=w["cores"], util_levels=w["util"],
                           valid=w["valid"])
    st.dc = DatacenterConfig(
        num_hosts=int(st.cap.size), cores_per_host=int(st.cap.max()),
        host_units=tuple(int(x) for x in st.cap),
        unit_tflops=cfg["unit_tflops"])
    st.params = PowerParams(p_idle=st.p_idle, p_max=st.p_max,
                            r=cfg["power_model"]["r"])
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
    hosts = int(st.cap.size)
    t0 = time.perf_counter()
    ss = sc.build_scenario_set(
        st.workload if workload is None else workload, st.dc,
        _scenarios(lanes), st.params, max_hosts=hosts,
        max_backfill=st.tr["max_backfill"], has_failures=True, pue_on=True,
        max_gang=st.cfg["max_gang"])
    t1 = time.perf_counter()
    sim, pred = sc.run_scenarios(ss, max_hosts=hosts,
                                 t_bins=st.cfg["t_bins"], **traces)
    jax.block_until_ready((sim, pred))
    t2 = time.perf_counter()
    summaries = sc.summarize_scenarios(
        ss, sim, pred, carbon_intensity=traces["carbon_intensity"])
    t3 = time.perf_counter()
    rec = dict(lanes=lanes, traces=traces, t3=t3,
               host_s=(t1 - t0) + (t3 - t2), job_start=sim.job_start,
               job_hosts=sim.job_hosts, gang_blocked=sim.gang_blocked_bins,
               pred=pred, n=len(summaries))
    st.batches.append(rec)
    return rec


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
    gang = st.w["cores"] > st.cap.max()
    starts = [float(np.mean(np.sum((np.asarray(r["job_start"]) >= 0)
                                   & gang, axis=-1))) for r in st.batches]
    blocked = [float(np.mean(np.asarray(r["gang_blocked"])))
               for r in st.batches]
    return {"whatif_rate": done * st.days / (t_last - t_start),
            "spans": {"host_gang": host},
            "counters": {"gang_starts": float(np.mean(starts)),
                         "gang_blocked_bins": float(np.mean(blocked))},
            "notes": {"batches": len(st.batches),
                      "window_s": t_last - t_start}}


def release(st: State) -> None:
    """Pull what the check needs to the host and drop the device buffers."""
    for r in st.batches:
        for k in ("job_start", "job_hosts", "gang_blocked"):
            r[k] = np.asarray(r[k])
        r["pred"] = {k: np.asarray(v) for k, v in
                     vars(r["pred"]).items() if v is not None}


def lane_reference(st: State, lane: dict, traces: dict) -> dict:
    """The plain reference of one lane: schedule, utilization, read-out."""
    cfg, w, t_bins = st.cfg, st.w, st.cfg["t_bins"]
    fs = np.full(st.cap.size, NEVER, np.int64)
    fe = np.zeros(st.cap.size, np.int64)
    fk = np.zeros(st.cap.size, bool)
    for h, s, e, k in lane["failures"]:
        fs[h], fe[h], fk[h] = s, e, k == "outage"
    start, hosts, blocked = rg.schedule(
        w["submit"], w["dur"], w["cores"], w["valid"], capacity=st.cap,
        t_bins=t_bins, policy=ref.POLICIES[lane["policy"]],
        backfill_depth=lane["backfill"],
        max_starts_per_bin=cfg["max_starts_per_bin"],
        max_gang=cfg["max_gang"], fail_start=fs, fail_end=fe, fail_kill=fk)
    u = rg.utilization(start, hosts, w["dur"], w["cores"], w["util"],
                       capacity=st.cap, t_bins=t_bins, fail_start=fs,
                       fail_kill=fk)
    tt = np.arange(t_bins)[:, None]
    online = ~(fk[None, :] & (tt >= fs[None, :]) & (tt < fe[None, :]))
    base, load, amb = lane["pue"]
    out = rg.readout(
        np, np.float64, u, p_idle=st.p_idle, p_max=st.p_max,
        r=cfg["power_model"]["r"], units=st.cap, online=online,
        cap=math.inf if lane["cap"] is None else lane["cap"],
        intensity=traces["carbon_intensity"],
        pue=(base, amb, cfg["pue_amb_ref"], load),
        ambient=traces["ambient_c"], price=traces["price"],
        peak_tflops=int(st.cap.sum()) * cfg["unit_tflops"])
    return dict(job_start=start, job_hosts=hosts, gang_blocked=blocked,
                **{k: np.asarray(v, np.float64) for k, v in out.items()})


def compare_lane(got: dict, want: dict) -> tuple[int, int, float]:
    """(jobs whose start or any host differ, gap of the gang-blocked
    bins, largest read-out gap) of a lane."""
    sched = int(np.sum((np.asarray(got["job_start"]) != want["job_start"])
                       | np.any(np.asarray(got["job_hosts"])
                                != want["job_hosts"], axis=-1)))
    blocked = abs(int(got["gang_blocked"]) - int(want["gang_blocked"]))
    gap = max(twin_check.rel_gap(got[k], want[k]) for k in LEAVES)
    return sched, blocked, gap


def check(st: State):
    limits = st.tr["limits"]
    sched_bad, blocked_gap, gap = 0, 0, 0.0
    for b, i in check_lanes(st):
        r = st.batches[b]
        want = lane_reference(st, r["lanes"][i], r["traces"])
        got = dict(job_start=r["job_start"][i], job_hosts=r["job_hosts"][i],
                   gang_blocked=r["gang_blocked"][i],
                   **{k: r["pred"][k][i] for k in LEAVES})
        s, g_b, g = compare_lane(got, want)
        sched_bad += s
        blocked_gap = max(blocked_gap, g_b)
        gap = max(gap, g)
    checks = [dict(name="schedule_mismatch_jobs", value=sched_bad,
                   limit=limits["schedule_mismatch_jobs"]),
              dict(name="gang_blocked_bins_gap", value=blocked_gap,
                   limit=limits["gang_blocked_bins_gap"]),
              dict(name="readout_rel_gap", value=gap,
                   limit=limits["readout_rel_gap"])]
    return checks, len(st.batches), 0


def min_bytes(st: State) -> float:
    """Least bytes one batch moves: its inputs read once (the workload,
    the traces, each lane's capacities and per-host power rows), its
    outputs written (the utilization field, counts, every job's start
    and hosts, the gang-blocked count, the read-out)."""
    s, t = st.tr["scenarios"], st.cfg["t_bins"]
    h, j = int(st.cap.size), st.cfg["jobs_padded"]
    phases = st.cfg["trace"]["num_phases"]
    g = st.cfg["max_gang"]
    workload = s * j * (3 * 4 + 4 * phases + 1)
    traces = 3 * t * 4
    fleet = s * h * (4 + 3 * 4)
    sim_out = (s * t * h * 4 + 2 * s * t * 4 + s * j * 4 + s * j * g * 4
               + s * 4)
    pred_out = len(LEAVES) * s * t * 4
    return float(workload + traces + fleet + sim_out + pred_out)


def control(cell, xp, dtype) -> list[tuple]:
    """The control: the plain read-out in ``dtype`` in the place of the
    program's (the program still schedules; its prediction is replaced by
    :func:`chipbench.reference_gang.readout` of its utilization field,
    computed in ``dtype``).  Returns the ``(module, name, stand-in)`` to
    patch for a run."""
    from repro.core import scenarios as sc
    from repro.core.desim import Prediction

    real = sc.run_scenarios

    def standin(ss, **kw):
        sim, pred = real(ss, **kw)
        t_bins = kw["t_bins"]
        tt = np.arange(t_bins)[None, :, None]
        fs, fe, fk = (np.asarray(x)[:, None, :] for x in
                      (ss.fail_start, ss.fail_end, ss.fail_kill))
        online = (np.asarray(ss.host_mask_s)[:, None, :]
                  & ~(fk & (tt >= fs) & (tt < fe)))

        def lane(x):
            return np.asarray(x)[:, None]

        out = rg.readout(
            xp, dtype, sim.u_th, p_idle=np.asarray(ss.params.p_idle)[:, None],
            p_max=np.asarray(ss.params.p_max)[:, None],
            r=np.asarray(ss.params.r)[:, None], units=np.asarray(
                ss.host_units)[:, None, :], online=online,
            cap=lane(ss.power_cap_w), intensity=kw["carbon_intensity"],
            pue=(lane(ss.pue_base), lane(ss.pue_amb_coeff),
                 lane(ss.pue_amb_ref), lane(ss.pue_load_coeff)),
            ambient=kw["ambient_c"], price=kw["price"],
            peak_tflops=lane(ss.peak_tflops))
        f32 = {k: xp.asarray(v, np.float32) for k, v in out.items()}
        return sim, Prediction(**f32)

    standin._cache_size = real._cache_size
    return [(sc, "run_scenarios", standin)]
