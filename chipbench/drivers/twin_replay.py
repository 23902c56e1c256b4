"""Closed loop of calibrated one-week twin replays at maximum acceleration.

A request is ``DigitalTwin(workload, dc, t_bins, cfg).run(telemetry)``: the
twin's own DES of the week, then one fused ``twin_step`` per window with
self-calibration.  The physical twin is the traffic: a pool of weeks drawn
from the seed (each padded to the configuration's job count), their
utilization from the plain reference DES and their measured power from the
hidden model of :func:`chipbench.gen.ground_truth`, all made in set-up.
The window cycles through the pool; nothing of one replay is reused by the
next.

``correct`` takes sampled replays and holds every window's prediction,
MAPE and calibrated parameters against the float64 reference
(:mod:`chipbench.twin_check`).
"""

from __future__ import annotations

import time

import numpy as np

from chipbench import gen
from chipbench import reference as ref
from chipbench import twin_check


class State:
    pass


def week(cfg: dict, seed: int, p: int) -> dict:
    """Pool week ``p``: jobs, reference utilization, measured power."""
    s = int(np.random.default_rng([seed, 0x3EE4, p]).integers(0, 2 ** 31))
    w = gen.surf22_like(s, num_hosts=cfg["num_hosts"],
                        cores_per_host=cfg["cores_per_host"],
                        days=cfg["days"], max_jobs=cfg["jobs_padded"],
                        **cfg["trace"])
    start, host = ref.schedule(
        w["submit"], w["dur"], w["cores"], w["valid"],
        num_hosts=cfg["num_hosts"], cores_per_host=cfg["cores_per_host"],
        t_bins=cfg["t_bins"], policy=ref.POLICIES["worst_fit"],
        max_starts_per_bin=cfg["max_starts_per_bin"])
    u = ref.utilization(start, host, w["dur"], w["cores"], w["util"],
                        num_hosts=cfg["num_hosts"],
                        cores_per_host=cfg["cores_per_host"],
                        t_bins=cfg["t_bins"])
    w["u_ref"] = u
    w["tel_u"] = u.astype(np.float32)
    w["tel_p"] = gen.ground_truth(u, seed=s + 1).astype(np.float32)
    return w


def setup(cell, seed: int, seconds: float) -> State:
    import repro.core  # noqa: F401  (the package fixes the import order)
    from repro.core import OrchestratorConfig
    from repro.core.calibrate import CalibrationSpec
    from repro.core.power import PowerParams
    from repro.traces.schema import DatacenterConfig, Workload

    st = State()
    st.cfg, st.tr, st.seed = cell.config, cell.traffic, seed
    cfg = st.cfg
    st.pool = [week(cfg, seed, p) for p in range(st.tr["pool_weeks"])]
    for w in st.pool:
        w["workload"] = Workload(
            submit_bin=w["submit"], duration_bins=w["dur"], cores=w["cores"],
            util_levels=w["util"], valid=w["valid"])
    st.dc = DatacenterConfig(num_hosts=cfg["num_hosts"],
                             cores_per_host=cfg["cores_per_host"],
                             ghz=cfg["ghz"])
    cal = cfg["calibration"]
    st.ocfg = OrchestratorConfig(
        bins_per_window=cfg["bins_per_window"],
        calibration=CalibrationSpec(mode=cal["mode"], r_lo=cal["r_lo"],
                                    r_hi=cal["r_hi"],
                                    r_points=cal["r_points"]),
        calibrate=True, history_windows=cfg["history_windows"],
        acceleration=None)
    st.params = PowerParams(**cfg["power_model"])
    st.replays = []
    # warm-up: one whole replay compiles the DES, the step and every
    # per-window slice the loop takes
    _request(st, 0)
    st.replays = []
    return st


def _telemetry(w: dict):
    from repro.core.telemetry import clip_to_window

    def source(window: int, bins_per_window: int):
        return clip_to_window(window, bins_per_window, 0, w["tel_u"],
                              w["tel_p"])
    return source


def _request(st: State, i: int) -> dict:
    from repro.core.twin import DigitalTwin

    p = i % len(st.pool)
    w = st.pool[p]
    twin = DigitalTwin(w["workload"], st.dc, st.cfg["t_bins"], st.ocfg,
                       st.params)
    res = twin.run(_telemetry(w))
    rec = dict(pool=p, t1=time.perf_counter(), res=res,
               final_params=twin.orchestrator.state.params)
    st.replays.append(rec)
    return rec


def cache_counters(st: State) -> dict:
    from repro.core.state import twin_step_jit

    return {"twin_step": twin_step_jit._cache_size()}


def window(st: State, seconds: float, tracer) -> dict:
    t_start = time.perf_counter()
    end = t_start + seconds
    i = 0
    trace_from, trace_to = 1, 1 + st.tr["trace_replays"]
    while True:
        if i == trace_from:
            tracer.start()
        _request(st, i)
        i += 1
        if i == trace_to:
            tracer.stop(trace_to - trace_from)
        if time.perf_counter() >= end and i >= st.tr["min_replays"]:
            break
    tracer.stop(i - trace_from)
    t_last = st.replays[-1]["t1"]
    days = st.cfg["days"] * len(st.replays)
    host = []
    for r in st.replays:
        recs = r["res"].records
        host += [b.started_at - a.started_at - a.sim_seconds
                 for a, b in zip(recs, recs[1:])]
    mapes = [float(r["res"].overall_mape) for r in st.replays]
    return {"twin_rate": days / (t_last - t_start),
            "program_spans": {"window_host_s": host},
            "notes": {"replays": len(st.replays),
                      "overall_mape_pct": sorted(set(mapes))}}


def _outputs(rec: dict) -> list[dict]:
    """The program's per-window outputs of one replay, on the host."""
    recs = rec["res"].records
    outs = []
    for k, r in enumerate(recs):
        nxt = recs[k + 1].params if k + 1 < len(recs) else rec["final_params"]
        pred = r.prediction
        outs.append(dict(
            pred={leaf: np.asarray(getattr(pred, leaf))
                  for leaf in twin_check.PRED_LEAVES},
            mape=r.mape,
            params_next=tuple(np.asarray(x) for x in
                              (nxt.p_idle, nxt.p_max, nxt.r))))
    return outs


def release(st: State) -> None:
    picks = check_replays(st)
    for j, rec in enumerate(st.replays):
        rec["outs"] = _outputs(rec) if j in picks else None
        rec["res"] = rec["final_params"] = None


def check_replays(st: State) -> list[int]:
    rng = np.random.default_rng([st.seed, 0xC4EC])
    n = len(st.replays)
    k = min(st.tr["check_replays"], n)
    return sorted(int(x) for x in rng.choice(n, k, replace=False))


def stream(cfg: dict, w: dict) -> list[tuple]:
    """The replay's windows ``(u_sim, tel_u, tel_p)``."""
    bw = cfg["bins_per_window"]
    n = cfg["t_bins"] // bw
    return [(w["u_ref"][k * bw:(k + 1) * bw], w["tel_u"][k * bw:(k + 1) * bw],
             w["tel_p"][k * bw:(k + 1) * bw]) for k in range(n)]


def check(st: State):
    worst = dict(pred_rel_gap=0.0, mape_gap_pp=0.0, calib_regret_pp=0.0)
    args = twin_check.stream_args(st.cfg)
    for rec in st.replays:
        if rec["outs"] is None:
            continue
        got = twin_check.compare_stream(
            stream(st.cfg, st.pool[rec["pool"]]), rec["outs"], **args)
        for k in worst:
            worst[k] = max(worst[k], got[k])
    limits = st.tr["limits"]
    checks = [dict(name=k, value=v, limit=limits[k]) for k, v in worst.items()]
    return checks, len(st.replays), 0


def min_bytes(st: State) -> float:
    """Least bytes one replay moves: the DES's trace in and field out, and
    each window's state, telemetry and simulated slice in, state and
    prediction out."""
    c = st.cfg
    t, h, j = c["t_bins"], c["num_hosts"], c["jobs_padded"]
    phases = c["trace"]["num_phases"]
    bw, k = c["bins_per_window"], c["history_windows"]
    des = j * (3 * 4 + 4 * phases + 1) + t * h * 4 + 2 * t * 4 + 2 * j * 4
    state = k * bw * h * 4 + k * bw * 4
    per_window = 2 * state + 2 * bw * h * 4 + bw * 4 + 5 * bw * 4
    return float(des + (t // bw) * per_window)


def control(cell, xp, dtype) -> list[tuple]:
    """The control: the reference in ``dtype`` in the place of the twin's
    step (:class:`chipbench.twin_check.ReferenceStep`).  Returns the
    ``(module, name, stand-in)`` to patch for a run."""
    from repro.core import orchestrator

    return [(orchestrator, "twin_step_jit", twin_check.ReferenceStep(
        orchestrator.twin_step_jit, cell.config, xp, dtype))]
