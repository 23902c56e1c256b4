"""Open loop of live tenant telemetry into the streaming ``TwinService``.

Tenant-windows come due on a fixed schedule whether or not the service keeps
up: each of the configuration's tenants has period ``tenants / R`` (``R``
the cell's aggregate rate, in windows per second), a phase and a jitter
drawn from the seed.  Each tenant's windows are the ``SyntheticProducer``
draw of :func:`chipbench.gen.synthetic_window`, made in set-up; streams are
unique, so the result cache pays its ``put`` and never hits.

The loop is the service's live loop run on this thread -- submit what is
due, one ``_step_once``, take what was emitted -- so that every emission is
stamped when it happens.  A window's latency runs from its due time to its
in-order emission.  Windows due inside the window that are still in flight
when it closes are waited for, and count with their wait.

``correct`` holds every window of sampled tenants against the float64
reference (:mod:`chipbench.twin_check`).
"""

from __future__ import annotations

import math
import time

import numpy as np

from chipbench import gen
from chipbench import twin_check


class State:
    pass


def _serve_config(cfg: dict):
    import repro.core  # noqa: F401  (the package fixes the import order)
    from repro.core.calibrate import CalibrationSpec
    from repro.core.power import PowerParams
    from repro.core.state import TwinConfig
    from repro.serve import ServeConfig
    from repro.traces.schema import DatacenterConfig

    cal = cfg["calibration"]
    twin = TwinConfig(
        bins_per_window=cfg["bins_per_window"],
        dc=DatacenterConfig(num_hosts=cfg["num_hosts"],
                            cores_per_host=cfg["cores_per_host"],
                            ghz=cfg["ghz"]),
        calibration=CalibrationSpec(mode=cal["mode"], r_lo=cal["r_lo"],
                                    r_hi=cal["r_hi"],
                                    r_points=cal["r_points"]),
        history_windows=cfg["history_windows"])
    sv = cfg["serve"]
    return ServeConfig(twin=twin,
                       base_params=PowerParams(**cfg["power_model"]),
                       lanes=cfg["lanes"], cache=sv["cache"],
                       inflight_depth=sv["inflight_depth"],
                       queue_capacity=sv["queue_capacity"],
                       cache_entries=sv["cache_entries"])


def _event(tenant: str, window: int, u, p):
    from repro.serve.producers import WindowEvent

    return WindowEvent(tenant=tenant, window=window, u_th=u, power_w=p,
                       sim_u=u)


def tenant_windows(cfg: dict, tseed: int, n: int) -> list[tuple]:
    tel = cfg["telemetry"]
    return [gen.synthetic_window(
        tseed, k, hosts=cfg["num_hosts"],
        bins_per_window=cfg["bins_per_window"],
        util_mean=tel["util_mean"], hidden=tuple(tel["hidden_power_model"]),
        noise=tel["noise"]) for k in range(n)]


def setup(cell, seed: int, seconds: float) -> State:
    from repro.serve import TwinService

    st = State()
    st.cfg, st.tr, st.seed = cell.config, cell.traffic, seed
    cfg, tr = st.cfg, st.tr
    n_t = cfg["tenants"]
    st.period = n_t / tr["rate_windows_per_s"]
    rng = np.random.default_rng([seed, 0x7E4A])
    st.tseeds = [int(x) for x in rng.integers(0, 2 ** 31, n_t)]
    phases = rng.uniform(0.0, st.period, n_t)
    n_w = int(math.ceil(seconds / st.period)) + 2
    st.names = [f"t{i:03d}" for i in range(n_t)]
    st.windows = {}
    st.due = []          # (due_s, tenant, window), window-relative
    for i, name in enumerate(st.names):
        st.windows[name] = tenant_windows(cfg, st.tseeds[i], n_w)
        due = gen.due_times(st.tseeds[i], n_w, start=phases[i] - st.period,
                            period_s=st.period,
                            jitter_s=tr["jitter_frac"] * st.period)
        st.due += [(float(d), name, k) for k, d in enumerate(due)]
    st.due.sort()
    st.scfg = _serve_config(cfg)
    # warm-up on a service of its own: the fleet program, and the per-lane
    # slices of dispatch and harvest on every lane
    warm = TwinService(st.scfg)
    for i in range(cfg["lanes"]):
        warm.admit(f"warm{i}")
    for k in range(2):
        for i in range(cfg["lanes"]):
            u, p = gen.synthetic_window(seed ^ 0x5A5A, i * 7 + k,
                                        hosts=cfg["num_hosts"],
                                        bins_per_window=cfg["bins_per_window"])
            warm.submit(_event(f"warm{i}", k, u, p))
    warm.run_until_idle(pump=False)
    del warm
    st.svc = TwinService(st.scfg)
    for name in st.names:
        st.svc.admit(name)
    return st


def cache_counters(st: State) -> dict:
    from repro.core.twin import fleet_step_masked

    return {"fleet_step_masked": fleet_step_masked._cache_size()}


def window(st: State, seconds: float, tracer) -> dict:
    import jax

    svc = st.svc
    due = st.due
    emitted = {}
    lateness = []
    nxt = 0
    trace_at = st.tr["trace_start_s"]
    trace_end = trace_at + st.tr["trace_seconds"]
    batches_at_trace = 0
    t0 = time.perf_counter()
    closed = False
    while True:
        now = time.perf_counter() - t0
        if not closed and now >= seconds:
            closed = True
            t_close = now
            drain_end = now + st.tr["drain_limit_s"]
        if tracer.enabled and not closed:
            if tracer.t_start is None and now >= trace_at:
                tracer.start()
                batches_at_trace = svc.stats.batches
            elif tracer.active and now >= trace_end:
                tracer.stop(svc.stats.batches - batches_at_trace)
        submitted = 0
        while not closed and nxt < len(due) and due[nxt][0] <= now:
            d, name, k = due[nxt]
            u, p = st.windows[name][k]
            with jax.profiler.TraceAnnotation("bench.submit"):
                ok = svc.submit(_event(name, k, u, p))
            if not ok:
                break
            lateness.append(now - d)
            nxt += 1
            submitted += 1
        with jax.profiler.TraceAnnotation("bench.step"):
            progress = svc._step_once()
        t_emit = time.perf_counter() - t0
        for r in svc.drain():
            emitted[(r.tenant, r.window)] = (t_emit, r.output)
        if closed and (len(emitted) >= nxt or t_emit >= drain_end):
            break
        if not progress and not submitted:
            wait = (due[nxt][0] - (time.perf_counter() - t0)
                    if nxt < len(due) and not closed else 0.0005)
            time.sleep(min(max(wait, 0.0), 0.002))
    tracer.stop(svc.stats.batches - batches_at_trace)
    lat = []
    in_time = 0
    for d, name, k in due[:nxt]:
        got = emitted.get((name, k))
        lat.append(math.inf if got is None else got[0] - d)
        if got is not None and got[0] <= seconds:
            in_time += 1
    st.emitted = emitted
    st.fill = svc.stats.fill_ratio
    st.attempted = nxt
    st.failed = sum(1 for d, name, k in due[:nxt] if (name, k) not in emitted)
    # a window never emitted is infinitely late
    p95 = (float(np.percentile(lat, 95)) * 1e3
           if lat and st.failed == 0 else math.inf)
    late = np.asarray(lateness or [0.0])
    return {"window_p95_ms": p95,
            "windows_per_s": in_time / seconds,
            "counters": {"fill_ratio": svc.stats.fill_ratio},
            "notes": {"windows_due": nxt, "emitted": len(emitted),
                      "emitted_in_window": in_time,
                      "p50_ms": float(np.median(lat)) * 1e3
                      if lat and st.failed == 0 else math.inf,
                      # a backlog that grows shows as later windows waiting
                      # longer: the median wait of each half of the window
                      "p50_halves_ms": _halves(due[:nxt], lat, seconds),
                      "generator_late_p95_ms": float(np.percentile(late, 95))
                      * 1e3,
                      "generator_late_max_ms": float(late.max()) * 1e3,
                      "batches": svc.stats.batches,
                      "cache_hits": svc.stats.windows_cached,
                      "queue_rejects": svc.stats.queue_rejects,
                      "after_close_s": t_emit - t_close}}


def _halves(due, lat, seconds: float) -> list:
    out = []
    for lo, hi in ((0.0, seconds / 2), (seconds / 2, seconds)):
        part = [x for (d, _, _), x in zip(due, lat) if lo <= d < hi]
        out.append(float(np.median(part)) * 1e3 if part else math.nan)
    return out


def release(st: State) -> None:
    st.svc = None


def check_tenants(st: State) -> list[str]:
    rng = np.random.default_rng([st.seed, 0xC4EC])
    k = min(st.tr["check_tenants"], len(st.names))
    return [st.names[int(i)] for i in sorted(rng.choice(len(st.names), k,
                                                        replace=False))]


def _output(out) -> dict:
    pred = out.prediction
    return dict(pred={leaf: np.asarray(getattr(pred, leaf))
                      for leaf in twin_check.PRED_LEAVES},
                mape=out.mape, calib_mape=out.calib_mape,
                params_next=(out.params_next.p_idle, out.params_next.p_max,
                             out.params_next.r))


def check(st: State):
    limits = st.tr["limits"]
    worst = dict(pred_rel_gap=0.0, mape_gap_pp=0.0, calib_regret_pp=0.0,
                 calib_gap_pp=0.0)
    args = twin_check.stream_args(st.cfg)
    for name in check_tenants(st):
        ks = sorted(k for (t, k) in st.emitted if t == name)
        if ks != list(range(len(ks))):
            worst["pred_rel_gap"] = math.inf   # a window lost or reordered
            continue
        wins = [(u, u, p) for u, p in st.windows[name][:len(ks)]]
        outs = [_output(st.emitted[(name, k)][1]) for k in ks]
        got = twin_check.compare_stream(wins, outs, **args)
        for k in worst:
            worst[k] = max(worst[k], got[k])
    checks = [dict(name=k, value=v, limit=limits[k]) for k, v in worst.items()]
    return checks, st.attempted, st.failed


def min_bytes(st: State) -> float:
    """Least bytes one dispatched batch moves: every active lane's state
    read and written, its telemetry and simulated slice read, its outputs
    written (lanes counted at the window's mean fill)."""
    c = st.cfg
    lanes, bw, h, k = c["lanes"], c["bins_per_window"], c["num_hosts"], \
        c["history_windows"]
    state = k * bw * h * 4 + k * bw * 4
    per_lane = 2 * state + 2 * bw * h * 4 + bw * 4 + 5 * bw * 4
    return float(lanes * st.fill * per_lane)


def control(cell, xp, dtype) -> list[tuple]:
    """The control: the reference in ``dtype`` in the place of the fleet
    step (:class:`chipbench.twin_check.ReferenceStep`).  Returns the
    ``(module, name, stand-in)`` to patch for a run."""
    from repro.serve import service

    return [(service, "fleet_step_masked", twin_check.ReferenceStep(
        service.fleet_step_masked, cell.config, xp, dtype))]
