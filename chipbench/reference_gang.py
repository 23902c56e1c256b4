"""Plain reference of gang scheduling on a fleet of mixed server sizes.

What the gang cells' ``correct`` compares the program against:

* :func:`schedule` -- FCFS placement against per-host capacities: a job
  wider than ``unit`` (the largest server) needs ``ceil(cores / unit)``
  whole free ``unit``-servers that are up, all at once (the top ones by the
  policy's score, ties to the lowest index), at most ``max_gang`` of them;
  bounded backfill, max starts per bin, host failure windows and the gang
  kill rule (an outage on any host of a running gang kills the whole job at
  the first outage start; the outage host's GPUs come back at its
  ``fail_end``, the others' at once);
* :func:`utilization` -- the per-host utilization field, a gang's GPUs
  spread evenly over its hosts, each host over its own capacity;
* :func:`readout` -- :func:`chipbench.reference.readout` with the
  utilization, TFLOP/s and the PUE's load term weighted by capacity.

The event semantics follow ``tests/reference.py`` (the repository's
loop-based oracle) over numpy arrays.  Nothing here imports the program.
"""

from __future__ import annotations

import math

import numpy as np

from chipbench.reference import NEVER, SAMPLE_SECONDS, _hash_scores

BIG = 1 << 40


def _scores(free, policy: int, t: int, salt: int, idx_u32) -> np.ndarray:
    """Per-host score of a policy: higher is chosen first."""
    if policy == 0:
        return -idx_u32.astype(np.int64)
    if policy == 1:
        return -free
    if policy == 2:
        return free.copy()
    return _hash_scores(idx_u32, t, salt)


def _hosts(free, need: int, unit: int, max_gang: int, online, policy: int,
           t: int, salt: int, idx_u32):
    """The hosts a job of ``need`` GPUs would take now, or ``None``."""
    if need > unit:
        n = -(-need // unit)
        whole = (free >= unit) & online
        if n > max_gang or whole.sum() < n:
            return None
        score = np.where(whole, _scores(free, policy, t, salt, idx_u32),
                         -BIG)
        # stable sort on -score: equal scores keep the lowest index first
        return np.argsort(-score, kind="stable")[:n]
    fits = (free >= need) & online
    if not fits.any():
        return None
    score = np.where(fits, _scores(free, policy, t, salt, idx_u32), -BIG)
    return np.array([int(np.argmax(score))])


def schedule(submit, dur, cores, valid, *, capacity, t_bins: int,
             policy: int = 2, backfill_depth: int = 0,
             max_starts_per_bin: int = 64, max_gang: int = 1,
             fail_start=None, fail_end=None, fail_kill=None):
    """``(job_start [J], job_hosts [J, max_gang], gang_blocked_bins)``.

    ``-1`` marks a job never started and an unused host slot.  A
    gang-blocked bin is one whose placement stopped at a gang head.
    """
    submit = np.asarray(submit, np.int64)
    dur = np.maximum(np.asarray(dur, np.int64), 1)
    cores = np.asarray(cores, np.int64)
    valid = np.asarray(valid, bool)
    cap = np.asarray(capacity, np.int64)
    h_n = cap.shape[0]
    unit = int(cap.max()) if max_gang > 1 else BIG
    j = submit.shape[0]
    free = cap.copy()
    release = np.zeros((t_bins + 1, h_n), np.int64)
    start = np.full(j, -1, np.int64)
    hosts_of = np.full((j, max_gang), -1, np.int64)
    blocked_bins = 0
    idx_u32 = np.arange(h_n, dtype=np.uint32)
    failing = fail_start is not None
    if failing:
        fs = np.asarray(fail_start, np.int64)
        fe = np.asarray(fail_end, np.int64)
        fk = np.asarray(fail_kill, bool)
    online = np.ones(h_n, bool)
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
            hs = _hosts(free, cores[jid], unit, max_gang, online, policy, t,
                        n, idx_u32)
            if hs is None:
                jid = -1
                for d in range(1, backfill_depth + 1):
                    c = head + d
                    if c >= j:
                        break
                    if start[c] >= 0 or not valid[c] or submit[c] > t:
                        continue
                    hs = _hosts(free, cores[c], unit, max_gang, online,
                                policy, t, n, idx_u32)
                    if hs is not None:
                        jid = c
                        break
                if jid < 0:
                    blocked_bins += int(cores[head] > unit)
                    break
            take = unit if cores[jid] > unit else cores[jid]
            end = np.full(hs.shape, t + dur[jid])
            if failing:
                kill = fk[hs] & (t < fs[hs]) & (fs[hs] < t + dur[jid])
                if kill.any():
                    kt = fs[hs][kill].min()
                    end = np.where(kill & (fs[hs] == kt), fe[hs], kt)
            free[hs] -= take
            np.add.at(release, (np.minimum(end, t_bins), hs), take)
            start[jid] = t
            hosts_of[jid, :hs.shape[0]] = hs
            n += 1
    return start, hosts_of, blocked_bins


def utilization(job_start, job_hosts, dur, cores, util, *, capacity,
                t_bins: int, fail_start=None, fail_kill=None) -> np.ndarray:
    """``[T, H]`` float64 utilization of a schedule.

    A job runs in ``[start, start + max(dur, 1))``, cut at the first outage
    start among its hosts when killed; in bin ``t`` each of its ``n`` hosts
    gets phase ``(t - start) * U // dur`` of its profile times ``cores /
    n`` over the host's capacity.
    """
    st = np.asarray(job_start, np.int64)
    hs = np.asarray(job_hosts, np.int64)
    du = np.maximum(np.asarray(dur, np.int64), 1)
    util = np.asarray(util, np.float64)
    cap = np.asarray(capacity, np.float64)
    h_n = cap.shape[0]
    phases = util.shape[1]
    run = st >= 0
    on = run[:, None] & (hs >= 0)
    n_hosts = np.maximum(on.sum(axis=1), 1)
    end = st + du
    if fail_start is not None:
        hsafe = np.where(on, hs, 0)
        fs = np.asarray(fail_start, np.int64)[hsafe]
        kill = (on & np.asarray(fail_kill, bool)[hsafe]
                & (st[:, None] < fs) & (fs < end[:, None]))
        end = np.where(kill.any(axis=1),
                       np.where(kill, fs, NEVER).min(axis=1), end)
    end = np.minimum(end, t_bins)
    u = np.zeros(t_bins * h_n, np.float64)
    for k in range(hs.shape[1]):
        jobs = np.nonzero(on[:, k] & (end > st))[0]
        lens = end[jobs] - st[jobs]
        rep = np.repeat(jobs, lens)
        offs = np.arange(rep.shape[0]) - np.repeat(np.cumsum(lens) - lens,
                                                   lens)
        ph = np.clip(offs * phases // du[rep], 0, phases - 1)
        host = hs[rep, k]
        w = (util[rep, ph] * np.asarray(cores, np.float64)[rep]
             / n_hosts[rep] / cap[host])
        np.add.at(u, (st[rep] + offs) * h_n + host, w)
    return u.reshape(t_bins, h_n)


def readout(xp, dtype, u_th, *, p_idle, p_max, r, units, online=None,
            cap=math.inf, intensity=None, pue=None, ambient=None,
            price=None, peak_tflops=1.0) -> dict:
    """Per-bin read-out of a utilization field ``[..., T, H]`` in ``dtype``.

    As :func:`chipbench.reference.readout`, with the mean utilization the
    share of online units busy (each host weighted by its ``units``); the
    per-lane scalars (``cap``, the ``pue`` tuple, ``peak_tflops``) may
    carry leading lane axes, shaped ``[..., 1]``.
    """
    def c(x):
        return xp.asarray(x, dtype)

    u = xp.clip(c(u_th), 0.0, 1.0)
    on = (xp.ones(u.shape, dtype) if online is None else c(online))
    p_idle, p_max, r = c(p_idle), c(p_max), c(r)
    host_w = p_idle + (p_max - p_idle) * (2.0 * u - u ** r)
    it = xp.sum(host_w * on, axis=-1)
    idle = xp.sum(xp.broadcast_to(p_idle, u.shape) * on, axis=-1)
    won = on * c(units)
    util_raw = xp.sum(c(u_th) * won, axis=-1) / xp.maximum(
        xp.sum(won, axis=-1), c(1.0))
    out = {}
    demand = it
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
