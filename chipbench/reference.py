"""Plain reference of the twin's semantics, independent of the program.

What the benchmark's ``correct`` compares the program against:

* :func:`schedule` -- FCFS placement (first/best/worst/random fit, bounded
  backfill, max starts per bin) with host failure windows, bin by bin;
* :func:`utilization` -- the per-host utilization field of a schedule;
* :func:`readout` -- demand, enforced cap with linear throttle, dynamic PUE,
  energy, carbon and cost from a utilization field;
* :func:`twin_window` -- one window of the calibrated twin: predict with the
  pipelined parameters, score against telemetry, grid-search ``r`` over the
  history.

It follows the event semantics of ``tests/reference.py`` (the repository's
loop-based oracle), rewritten over numpy arrays so that a week of 277 hosts
takes well under a second.  :func:`readout` and :func:`twin_window` take an
array module and a dtype: float64 numpy is the reference, and the same code
in bfloat16 is the control that has to come out as not correct.  Nothing
here imports the program.
"""

from __future__ import annotations

import math

import numpy as np

SAMPLE_SECONDS = 300.0
NEVER = np.iinfo(np.int32).max
POLICIES = {"first_fit": 0, "best_fit": 1, "worst_fit": 2, "random_fit": 3}


def _hash_scores(hosts: np.ndarray, t: int, salt: int) -> np.ndarray:
    """Seed-free per-host scores of random fit (uint32 mix, 23 bits)."""
    x = (hosts * np.uint32(0x9E3779B1)
         ^ np.uint32((t * 0x85EBCA77) & 0xFFFFFFFF)
         ^ np.uint32((salt * 0xC2B2AE3D) & 0xFFFFFFFF))
    x = (x ^ (x >> np.uint32(16))) * np.uint32(0x7FEB352D)
    x = (x ^ (x >> np.uint32(15))) * np.uint32(0x846CA68B)
    x = x ^ (x >> np.uint32(16))
    return (x & np.uint32(0x7FFFFF)).astype(np.int64)


def _pick(free, fits, policy: int, t: int, salt: int, idx_u32) -> int:
    """Host for a job: best score among fitting hosts, ties to the lowest."""
    if policy == 0:
        return int(np.argmax(fits))
    if policy == 1:
        return int(np.argmax(np.where(fits, -free, -(1 << 40))))
    if policy == 2:
        return int(np.argmax(np.where(fits, free, -1)))
    return int(np.argmax(np.where(fits, _hash_scores(idx_u32, t, salt), -1)))


def schedule(submit, dur, cores, valid, *, num_hosts: int, cores_per_host: int,
             t_bins: int, policy: int = 2, backfill_depth: int = 0,
             max_starts_per_bin: int = 64, fail_start=None, fail_end=None,
             fail_kill=None) -> tuple[np.ndarray, np.ndarray]:
    """``(job_start, job_host)`` of the FCFS scheduler, ``-1`` = never started.

    Per bin: release the cores of jobs that end, then place the queue head
    if it is submitted and fits a host that is up, else let the first of
    its next ``backfill_depth`` submitted successors that fits jump ahead,
    else block the bin; at most ``max_starts_per_bin`` starts a bin.  A host
    in ``[fail_start, fail_end)`` takes no placements; an outage host
    (``fail_kill``) kills the jobs that run into its window, and their cores
    come back at ``fail_end``.
    """
    submit = np.asarray(submit, np.int64)
    dur = np.maximum(np.asarray(dur, np.int64), 1)
    cores = np.asarray(cores, np.int64)
    valid = np.asarray(valid, bool)
    j = submit.shape[0]
    free = np.full(num_hosts, cores_per_host, np.int64)
    release = np.zeros((t_bins + 1, num_hosts), np.int64)
    start = np.full(j, -1, np.int64)
    host_of = np.full(j, -1, np.int64)
    idx_u32 = np.arange(num_hosts, dtype=np.uint32)
    failing = fail_start is not None
    if failing:
        fs = np.asarray(fail_start, np.int64)
        fe = np.asarray(fail_end, np.int64)
        fk = np.asarray(fail_kill, bool)
    online = np.ones(num_hosts, bool)
    head = 0
    for t in range(t_bins):
        free += release[t]
        if failing:
            online = ~((fs <= t) & (t < fe))
        n = 0
        while n < max_starts_per_bin:
            while head < j and start[head] >= 0:
                head += 1
            if head >= j or submit[head] > t or not valid[head]:
                break
            jid = head
            fits = (free >= cores[jid]) & online
            if not fits.any():
                jid = -1
                for d in range(1, backfill_depth + 1):
                    c = head + d
                    if c >= j:
                        break
                    if start[c] >= 0 or not valid[c] or submit[c] > t:
                        continue
                    fits = (free >= cores[c]) & online
                    if fits.any():
                        jid = c
                        break
                if jid < 0:
                    break
            h = _pick(free, fits, policy, t, n, idx_u32)
            free[h] -= cores[jid]
            start[jid] = t
            host_of[jid] = h
            end = min(t + dur[jid], t_bins)
            if failing and fk[h] and t < fs[h] < t + dur[jid]:
                end = min(fe[h], t_bins)
            release[end, h] += cores[jid]
            n += 1
    return start, host_of


def utilization(job_start, job_host, dur, cores, util, *, num_hosts: int,
                cores_per_host: int, t_bins: int, fail_start=None,
                fail_kill=None) -> np.ndarray:
    """``[T, H]`` float64 host utilization of a schedule.

    A job runs in ``[start, start + max(dur, 1))``, cut at the outage start
    of its host when killed, and in bin ``t`` contributes phase
    ``(t - start) * U // dur`` of its profile times its cores over the
    host's cores.
    """
    st = np.asarray(job_start, np.int64)
    hs = np.asarray(job_host, np.int64)
    du = np.maximum(np.asarray(dur, np.int64), 1)
    util = np.asarray(util, np.float64)
    phases = util.shape[1]
    run = st >= 0
    end = st + du
    if fail_start is not None:
        fs = np.asarray(fail_start, np.int64)[np.where(run, hs, 0)]
        fk = np.asarray(fail_kill, bool)[np.where(run, hs, 0)]
        killed = run & fk & (st < fs) & (fs < end)
        end = np.where(killed, fs, end)
    end = np.minimum(end, t_bins)
    jobs = np.nonzero(run & (end > st))[0]
    lens = end[jobs] - st[jobs]
    rep = np.repeat(jobs, lens)
    offs = np.arange(rep.shape[0]) - np.repeat(np.cumsum(lens) - lens, lens)
    t = st[rep] + offs
    ph = np.clip(offs * phases // du[rep], 0, phases - 1)
    w = util[rep, ph] * np.asarray(cores, np.float64)[rep] / cores_per_host
    u = np.zeros(t_bins * num_hosts, np.float64)
    np.add.at(u, t * num_hosts + hs[rep], w)
    return u.reshape(t_bins, num_hosts)


def readout(xp, dtype, u_th, *, p_idle, p_max, r, online=None, cap=math.inf,
            intensity=None, pue=None, ambient=None, price=None,
            peak_tflops=1.0) -> dict:
    """Per-bin read-out of a utilization field ``[T, H]``, in ``dtype``.

    ``online`` (``[T, H]`` bool) drops hosts in an outage from the power
    sum, the idle floor and the utilization mean.  Demand is facility power
    (IT power times PUE from the unthrottled mean utilization and the
    ambient trace); delivered power is ``min(demand, cap)``; utilization is
    throttled by the share of above-idle power the cap removes.
    """
    def c(x):
        return xp.asarray(x, dtype)

    u = xp.clip(c(u_th), 0.0, 1.0)
    on = (xp.ones(u.shape, dtype) if online is None else c(online))
    p_idle, p_max, r = c(p_idle), c(p_max), c(r)
    host_w = p_idle + (p_max - p_idle) * (2.0 * u - u ** r)
    it = xp.sum(host_w * on, axis=-1)
    idle = xp.sum(xp.broadcast_to(p_idle, u.shape) * on, axis=-1)
    n_on = xp.maximum(xp.sum(on, axis=-1), c(1.0))
    util_raw = xp.sum(c(u_th) * on, axis=-1) / n_on
    out = {}
    demand, pue_t = it, None
    if pue is not None:
        base, amb_coeff, amb_ref, load_coeff = (c(v) for v in pue)
        pue_t = base + load_coeff * (1.0 - xp.clip(util_raw, 0.0, 1.0))
        if ambient is not None:
            pue_t = pue_t + amb_coeff * xp.maximum(c(ambient) - amb_ref, 0.0)
        demand, idle = it * pue_t, idle * pue_t
        out["pue"] = pue_t
    cap_t = c(cap)
    power = xp.minimum(demand, cap_t)
    throttle = xp.clip((cap_t - idle) / xp.maximum(demand - idle, c(1e-9)),
                       0.0, 1.0)
    util_t = xp.where(demand > cap_t, util_raw * throttle, util_raw)
    energy = power * c(SAMPLE_SECONDS / 3600.0 / 1000.0)
    tflops = util_t * c(peak_tflops)
    out.update(power_w=power, power_demand_w=demand, energy_kwh=energy,
               utilization=util_t, tflops=tflops,
               efficiency=tflops / xp.maximum(energy, c(1e-9)))
    if intensity is not None:
        out["gco2"] = energy * c(intensity)
    if price is not None:
        out["energy_cost"] = energy * c(price)
    return out


def mape(xp, real, sim, eps: float = 1e-9):
    """MAPE in %, zero-real bins left out, NaN when every bin is zero."""
    nonzero = xp.abs(real) > eps
    n = xp.sum(nonzero, axis=-1)
    ape = xp.abs((real - sim) / (xp.abs(real) + eps))
    total = xp.sum(xp.where(nonzero, ape, 0.0), axis=-1)
    return xp.where(n > 0, total / xp.maximum(n, 1), xp.nan) * 100.0


def window_sums(xp, dtype, u, r):
    """``(sum_h u, [C] rows of sum_h u^r)`` per bin of a window ``[Tw, H]``.

    What the grid search needs of a telemetry window, kept per window so
    that a history of K windows is not raised to every power again.
    """
    u = xp.clip(xp.asarray(u, dtype), 0.0, 1.0)
    r = xp.asarray(r, dtype)
    s1 = xp.sum(u, axis=-1)                                      # [Tw]
    sr = xp.sum(u[None, :, :] ** r[:, None, None], axis=-1)      # [C, Tw]
    return s1, sr


def twin_window(xp, dtype, u_sim, params, tel_u, tel_p, hist, cand, *,
                peak_tflops: float, history_windows: int) -> dict:
    """One window of the calibrated twin (no carbon, PUE or price columns).

    Predicts the window from ``u_sim`` with ``params`` ``(p_idle, p_max,
    r)``, scores it against the telemetry, appends the telemetry to ``hist``
    (a list, changed in place, of the last ``history_windows`` windows) and
    scores every candidate row ``(p_idle, p_max, r)`` of ``cand`` over that
    history.  Returns the prediction leaves, ``mape``, ``cand_mapes``
    (``[C]``) and ``best`` (first index of the least finite MAPE, -1 when
    none is finite).
    """
    pred = readout(xp, dtype, u_sim, p_idle=params[0], p_max=params[1],
                   r=params[2], peak_tflops=peak_tflops)
    pred.pop("power_demand_w")
    real = xp.asarray(tel_p, dtype)
    m = mape(xp, real, pred["power_w"])
    cand = np.asarray(cand, np.float64)
    hist.append((window_sums(xp, dtype, tel_u, cand[:, 2]), real))
    del hist[:-history_windows]
    s1 = xp.concatenate([x[0][0] for x in hist])                  # [N]
    sr = xp.concatenate([x[0][1] for x in hist], axis=-1)         # [C, N]
    hp = xp.concatenate([x[1] for x in hist])                     # [N]
    h = xp.asarray(np.asarray(tel_u).shape[-1], dtype)
    pi = xp.asarray(cand[:, 0:1], dtype)
    pm = xp.asarray(cand[:, 1:2], dtype)
    sim = h * pi + (pm - pi) * (2.0 * s1[None, :] - sr)
    cm = mape(xp, hp[None, :], sim)
    cm_np = np.asarray(cm, np.float64)
    finite = np.isfinite(cm_np)
    best = (int(np.argmin(np.where(finite, cm_np, np.inf)))
            if finite.any() else -1)
    return dict(pred=pred, mape=m, cand_mapes=cm_np, best=best)
