"""Pure-Python oracle for the vectorized DES and its masked read-out.

An easily-audited, loop-based re-implementation of what the jitted engine
computes — scheduling (FCFS + placement policies + bounded backfill),
deferrable-job time-shifting, the OpenDC power model, **enforced** power
caps (static and carbon-aware) with linear throttling, energy and gCO2.
Everything runs in plain Python floats (float64), so any agreement with the
float32 tensor engine is evidence, not tautology.

Used by ``test_policies.py`` (placement exactness) and ``test_oracle.py``
(cap/shift/readout cross-checks on randomized small cases).  Kept free of
jax imports on purpose: the oracle must not share code with the system
under test.
"""

import math

import numpy as np


# -- placement ----------------------------------------------------------------

def _rand_score(host: int, t: int, salt: int) -> int:
    """Python replica of desim._hash_scores (uint32 mix, masked to 23 bits)."""
    m = 0xFFFFFFFF
    x = ((host * 0x9E3779B1) ^ (t * 0x85EBCA77) ^ (salt * 0xC2B2AE3D)) & m
    x = ((x ^ (x >> 16)) * 0x7FEB352D) & m
    x = ((x ^ (x >> 15)) * 0x846CA68B) & m
    x = x ^ (x >> 16)
    return x & 0x7FFFFF


def _pick_host(free, need, policy, t, salt, online=None):
    """Argmax-of-score host choice; ties break to the lowest host index.

    ``online`` filters placement-eligible hosts (failure windows — both
    outages and drains accept no *new* placements); scores still key on the
    raw free-core counts, matching the engine's masked argmax.
    """
    fits = [h for h in range(len(free)) if free[h] >= need
            and (online is None or online[h])]
    if not fits:
        return None
    if policy == "first_fit":
        return fits[0]
    if policy == "best_fit":
        return min(fits, key=lambda h: (free[h], h))
    if policy == "worst_fit":
        return max(fits, key=lambda h: (free[h], -h))
    if policy == "random_fit":
        return max(fits, key=lambda h: (_rand_score(h, t, salt), -h))
    raise ValueError(policy)


def _gang_hosts(free, unit, n, policy, t, salt, online=None):
    """The ``n`` hosts of a gang: whole free ``unit``-hosts that are up,
    ranked by the policy's score, ties to the lowest index; ``None`` when
    fewer than ``n`` are free."""
    whole = [h for h in range(len(free)) if free[h] >= unit
             and (online is None or online[h])]
    if len(whole) < n:
        return None
    score = {"first_fit": lambda h: -h,
             "best_fit": lambda h: -free[h],
             "worst_fit": lambda h: free[h],
             "random_fit": lambda h: _rand_score(h, t, salt)}[policy]
    return sorted(whole, key=lambda h: (-score(h), h))[:n]


def reference_gang_schedule(submit, dur, cores, valid, *, num_hosts,
                            cores_per_host, t_bins, policy="worst_fit",
                            backfill_depth=0, max_starts_per_bin=64,
                            fail_start=None, fail_end=None, fail_kill=None,
                            host_capacity=None, max_gang=1):
    """:func:`reference_schedule` with every host of a job and the count
    of gang-blocked bins: ``(job_start, job_hosts, gang_blocked_bins)``.

    ``host_capacity`` lists each host's units (default ``cores_per_host``
    for all).  With ``max_gang > 1`` a job asking for more than ``unit``
    units, the largest capacity, needs ``ceil(cores / unit)`` (at most
    ``max_gang``) whole free ``unit``-hosts at once (:func:`_gang_hosts`)
    and holds each whole.  If an outage host of a running gang fails
    during its run, the whole job dies at the first such outage start:
    the outage host's units come back at its ``fail_end``, the others' at
    once.  ``job_hosts[i]`` lists job ``i``'s hosts (empty if it never
    started); a gang-blocked bin is one whose placement stopped at a gang
    head.
    """
    j = len(submit)
    cap = (list(host_capacity) if host_capacity is not None
           else [cores_per_host] * num_hosts)
    unit = max(cap)
    free = list(cap)
    release = [[0] * num_hosts for _ in range(t_bins + 1)]
    job_start = [-1] * j
    job_hosts = [[] for _ in range(j)]
    blocked_bins = 0
    next_job = 0

    def is_gang(c):
        return max_gang > 1 and cores[c] > unit

    def hosts_for(c, t, n, online):
        if is_gang(c):
            k = -(-cores[c] // unit)
            return (None if k > max_gang else
                    _gang_hosts(free, unit, k, policy, t, n, online))
        h = _pick_host(free, cores[c], policy, t, n, online)
        return None if h is None else [h]

    for t in range(t_bins):
        for h in range(num_hosts):
            free[h] += release[t][h]
        online = (None if fail_start is None else
                  [not (fail_start[h] <= t < fail_end[h])
                   for h in range(num_hosts)])
        n = 0
        while n < max_starts_per_bin:
            while next_job < j and job_start[next_job] >= 0:
                next_job += 1
            if (next_job >= j or submit[next_job] > t
                    or not valid[next_job]):
                break
            jid = next_job
            hosts = hosts_for(jid, t, n, online)
            if hosts is None:
                jid = None
                for d in range(1, backfill_depth + 1):
                    c = next_job + d
                    if c >= j:
                        break
                    if (job_start[c] >= 0 or not valid[c]
                            or submit[c] > t):
                        continue
                    hosts = hosts_for(c, t, n, online)
                    if hosts is not None:
                        jid = c
                        break
                if jid is None:
                    blocked_bins += is_gang(next_job)
                    break
            take = unit if is_gang(jid) else cores[jid]
            end = t + max(dur[jid], 1)
            kill = [h for h in hosts if fail_start is not None
                    and fail_kill[h] and t < fail_start[h] < end]
            kt = min((fail_start[h] for h in kill), default=None)
            for h in hosts:
                free[h] -= take
                back = end
                if kill:
                    # killed at the first outage; the outage host's units
                    # come back with the host, the others' at once
                    back = fail_end[h] if fail_start[h] == kt and h in kill \
                        else kt
                release[min(back, t_bins)][h] += take
            job_start[jid] = t
            job_hosts[jid] = hosts
            n += 1
    return job_start, job_hosts, blocked_bins


def reference_schedule(submit, dur, cores, valid, *, num_hosts,
                       cores_per_host, t_bins, policy="worst_fit",
                       backfill_depth=0, max_starts_per_bin=64,
                       fail_start=None, fail_end=None, fail_kill=None):
    """Event-semantics FCFS scheduler the vectorized kernel must reproduce.

    Per bin: release finished jobs' cores, then repeatedly (a) place the
    queue head if it is submitted and fits anywhere, else (b) let the first
    of its next `backfill_depth` submitted successors that fits jump ahead,
    else (c) block the bin.  Host choice per `_pick_host`.

    Failure schedules (``fail_start``/``fail_end``/``fail_kill``, per-host
    lists): during ``[fail_start[h], fail_end[h])`` host ``h`` accepts no
    new placements; when ``fail_kill[h]``, a job placed before the window
    that would run into it dies at ``fail_start[h]`` and its cores return
    with the host at ``fail_end[h]``.
    """
    job_start, job_hosts, _ = reference_gang_schedule(
        submit, dur, cores, valid, num_hosts=num_hosts,
        cores_per_host=cores_per_host, t_bins=t_bins, policy=policy,
        backfill_depth=backfill_depth,
        max_starts_per_bin=max_starts_per_bin, fail_start=fail_start,
        fail_end=fail_end, fail_kill=fail_kill)
    return job_start, [hs[0] if hs else -1 for hs in job_hosts]


# -- workload perturbation ----------------------------------------------------

def apply_shift(submit, dur, util, cores, valid, deferrable, shift_bins):
    """Deferrable-job time-shifting, mirroring scenarios._perturb.

    Moves deferrable valid jobs by ``shift_bins`` (clipped at bin 0), then
    stably re-sorts the whole job axis by the new submission times — the
    DES's FCFS queue order *is* the array order.  ``deferrable=None`` means
    all jobs move.  Returns the re-ordered (submit, dur, util, cores, valid,
    deferrable) lists.
    """
    j = len(submit)
    movable = [valid[i] and (deferrable is None or deferrable[i])
               for i in range(j)]
    shifted = [max(submit[i] + shift_bins, 0) if movable[i] else submit[i]
               for i in range(j)]
    order = sorted(range(j), key=lambda i: (shifted[i], i))   # stable
    pick = lambda xs: [xs[i] for i in order]                  # noqa: E731
    return (pick(shifted), pick(dur), pick(util), pick(cores), pick(valid),
            None if deferrable is None else pick(deferrable))


# -- utilization field --------------------------------------------------------

def reference_u_th(job_start, submit, dur, cores, util_levels, job_host, *,
                   num_hosts, cores_per_host, t_bins,
                   fail_start=None, fail_kill=None, job_hosts=None,
                   host_capacity=None):
    """``[t_bins][num_hosts]`` per-host utilization from a schedule.

    Replicates the engine's post-scan read-out: a job runs in bins
    ``[start, start + max(dur, 1))``, contributing phase
    ``clip((t - start) * U // max(dur, 1), 0, U - 1)`` of its piecewise
    profile times its core count, normalized by the host's core capacity.
    Killed jobs (pre-outage placements on a ``fail_kill`` host that run
    into its window) stop at ``fail_start`` — phase indexing keeps the
    *original* duration, exactly like the engine's ``end_eff`` clamp.

    With ``job_hosts`` (a list of hosts per job, from
    :func:`reference_gang_schedule`) a gang's cores are spread evenly over
    its hosts and it stops at the first outage start among them;
    ``host_capacity`` normalizes each host by its own units.
    """
    j = len(job_start)
    u = [[0.0] * num_hosts for _ in range(t_bins)]
    cap = (list(host_capacity) if host_capacity is not None
           else [cores_per_host] * num_hosts)
    phases = len(util_levels[0]) if j else 1
    for i in range(j):
        if job_start[i] < 0:
            continue
        hosts = job_hosts[i] if job_hosts is not None else [job_host[i]]
        d = max(dur[i], 1)
        end = job_start[i] + d
        if fail_start is not None:
            end = min([end] + [fail_start[h] for h in hosts if fail_kill[h]
                               and job_start[i] < fail_start[h] < end])
        for t in range(job_start[i], min(end, t_bins)):
            ph = min(max((t - job_start[i]) * phases // d, 0), phases - 1)
            for h in hosts:
                u[t][h] += (util_levels[i][ph] * cores[i] / len(hosts)
                            / cap[h])
    return u


# -- power / cap / carbon read-out -------------------------------------------

def opendc_power(u, p_idle, p_max, r):
    """OpenDC analytical model, scalar: P = P_idle + span * (2u - u^r)."""
    u = min(max(u, 0.0), 1.0)
    return p_idle + (p_max - p_idle) * (2.0 * u - u ** r)


def effective_cap(power_cap_w, carbon_cap_base_w, carbon_cap_slope,
                  intensity_t):
    """Per-bin enforced cap: min(static, max(base + slope * I_t, 0)).

    ``None`` caps read as +inf (uncapped); the carbon-aware term only
    applies when an intensity value is supplied (matching the engine, which
    rejects carbon caps without a trace).
    """
    cap = power_cap_w if power_cap_w is not None else math.inf
    if intensity_t is not None:
        base = (carbon_cap_base_w if carbon_cap_base_w is not None
                else math.inf)
        cap = min(cap, max(base + carbon_cap_slope * intensity_t, 0.0))
    return cap


def reference_pue(util_raw, ambient_t, pue):
    """Scalar replica of ``repro.traces.thermal.dynamic_pue``.

    ``pue`` is a ``(base, amb_coeff, amb_ref, load_coeff)`` tuple; the
    ambient term only applies when a temperature is supplied.
    """
    base, amb_coeff, amb_ref, load_coeff = pue
    load = min(max(util_raw, 0.0), 1.0)
    p = base + load_coeff * (1.0 - load)
    if ambient_t is not None:
        p += amb_coeff * max(ambient_t - amb_ref, 0.0)
    return p


def reference_readout(u_th, *, p_idle, p_max, r, power_cap_w=None,
                      carbon_cap_base_w=None, carbon_cap_slope=0.0,
                      intensity=None, sample_seconds=300.0,
                      online=None, pue=None, ambient=None, price=None,
                      units=None):
    """Masked-readout oracle: demand, enforced cap, throttle, energy, gCO2.

    Mirrors ``scenarios._predict_masked`` in plain float64:

    * ``demand_t``   — sum of the per-host OpenDC power over active hosts;
    * ``cap_t``      — the effective (static ∧ carbon-aware) per-bin cap;
    * ``throttled_t``— demand ran into the cap (the engine's cap-exceeded
      flag);
    * ``power_t``    — delivered = min(demand, cap);
    * ``util_t``     — mean active-host utilization, linearly throttled by
      the above-idle fraction the cap removed when throttled;
    * ``energy_t`` / ``gco2_t`` — delivered energy (kWh) and carbon (g).

    New axes (all default off, reproducing the old read-out exactly):

    * ``online``  — ``[T][H]`` bool; offline (outage) hosts draw no power,
      not even idle, and leave the utilization denominator;
    * ``pue`` / ``ambient`` — ``(base, amb_coeff, amb_ref, load_coeff)``
      tuple + °C list: demand, idle floor and hence cap enforcement move
      to facility watts (PUE from the *unthrottled* utilization);
    * ``price``   — ``[T]`` $/kWh: adds ``cost_t = energy_t * price_t``;
    * ``units``   — ``[H]`` host capacities of a fleet of mixed sizes: the
      utilization (and the PUE's load term) is the share of online units
      busy, each host weighted by its capacity.
    """
    t_bins = len(u_th)
    num_hosts = len(u_th[0]) if t_bins else 0
    out = {k: [] for k in ("demand", "cap", "power", "throttled", "util",
                           "energy_kwh", "gco2", "pue", "cost")}
    for t in range(t_bins):
        i_t = intensity[t] if intensity is not None else None
        on = online[t] if online is not None else [True] * num_hosts
        n_on = sum(1 for h in range(num_hosts) if on[h])
        wt = [1.0] * num_hosts if units is None else units
        pi = p_idle if isinstance(p_idle, (list, tuple)) else \
            [p_idle] * num_hosts
        pm = p_max if isinstance(p_max, (list, tuple)) else \
            [p_max] * num_hosts
        demand = sum(opendc_power(u_th[t][h], pi[h], pm[h], r)
                     for h in range(num_hosts) if on[h])
        idle_floor = sum(pi[h] for h in range(num_hosts) if on[h])
        util_raw = (sum(u_th[t][h] * wt[h] for h in range(num_hosts)
                        if on[h])
                    / max(sum(wt[h] for h in range(num_hosts) if on[h]), 1))
        pue_t = math.nan
        if pue is not None:
            pue_t = reference_pue(
                util_raw, ambient[t] if ambient is not None else None, pue)
            demand *= pue_t
            idle_floor *= pue_t
        cap = effective_cap(power_cap_w, carbon_cap_base_w,
                            carbon_cap_slope, i_t)
        throttled = demand > cap
        power = min(demand, cap)
        throttle = min(max((cap - idle_floor)
                           / max(demand - idle_floor, 1e-9), 0.0), 1.0)
        util = util_raw * throttle if throttled else util_raw
        energy = power * sample_seconds / 3600.0 / 1000.0
        out["demand"].append(demand)
        out["cap"].append(cap)
        out["power"].append(power)
        out["throttled"].append(throttled)
        out["util"].append(util)
        out["energy_kwh"].append(energy)
        out["gco2"].append(energy * i_t if i_t is not None else math.nan)
        out["pue"].append(pue_t)
        out["cost"].append(energy * price[t] if price is not None
                           else math.nan)
    return out


def reference_mape(real, sim, eps=1e-9):
    """Scalar replica of ``repro.core.power.mape``: denominator
    ``|real| + eps``, zero-real bins excluded, all-zero → NaN, in %."""
    total, n = 0.0, 0
    for rv, sv in zip(real, sim):
        if abs(rv) > eps:
            total += abs((rv - sv) / (abs(rv) + eps))
            n += 1
    return total / n * 100.0 if n else math.nan


def reference_calibrate_per_host(u_th, real_power, candidates, fleet_params,
                                 fleet_mape):
    """Loop-based oracle for ``calibrate._per_host_refit`` (float64).

    ``u_th`` is ``[T][H]``, ``real_power`` ``[T]``, ``candidates`` a list of
    ``(p_idle, p_max, r)`` scalar tuples (the same grid the engine scores),
    ``fleet_params`` a ``(p_idle, p_max, r)`` tuple of scalars or ``[H]``
    lists.  Measured total power is attributed to hosts by their predicted
    share under the fleet fit; each host argmins the grid against its share
    column (first finite minimum wins, like the engine's argmin), hosts with
    no finite score keep the fleet row, and the returned MAPE is the
    *total-power* MAPE of the combined per-host prediction (fleet MAPE when
    that is undefined).  Returns ``((p_idle_row, p_max_row, r_row), mape)``.
    """
    t_bins, h = len(u_th), len(u_th[0])

    def fleet_row(v):
        return list(v) if isinstance(v, (list, tuple)) else [v] * h

    fpi, fpm, fr = (fleet_row(fleet_params[0]), fleet_row(fleet_params[1]),
                    fleet_row(fleet_params[2]))
    pred = [[opendc_power(u_th[t][j], fpi[j], fpm[j], fr[j])
             for j in range(h)] for t in range(t_bins)]
    rows = ([], [], [])
    for j in range(h):
        target = [real_power[t] * pred[t][j] / max(sum(pred[t]), 1e-9)
                  for t in range(t_bins)]
        best, best_m = None, math.inf
        for c in candidates:
            m = reference_mape(
                target, [opendc_power(u_th[t][j], *c) for t in range(t_bins)])
            if not math.isnan(m) and m < best_m:
                best, best_m = c, m
        chosen = best if best is not None else (fpi[j], fpm[j], fr[j])
        for row, v in zip(rows, chosen):
            row.append(v)
    combined = [sum(opendc_power(u_th[t][j], rows[0][j], rows[1][j],
                                 rows[2][j]) for j in range(h))
                for t in range(t_bins)]
    m = reference_mape(real_power, combined)
    return rows, (fleet_mape if math.isnan(m) else m)


def reference_scenario(workload, dc, scenario, *, t_bins, p_idle, p_max, r,
                       intensity=None, ambient=None, price=None,
                       max_starts_per_bin=64, max_gang=1):
    """Full single-scenario oracle: perturb -> schedule -> readout.

    ``workload`` is a dict of plain lists (``submit``, ``dur``, ``cores``,
    ``util`` — ``[J][U]`` —, ``valid``, optional ``deferrable``);
    ``scenario`` a :class:`repro.core.scenarios.Scenario`; power params are
    the *resolved* scalars (scenario override already applied by the
    caller, or the base).  Returns the readout dict plus the schedule and
    post-perturbation submit times (``job_start``, ``job_host``,
    ``submit``, ``waits`` over started valid jobs).

    The scenario's failure windows, PUE fields and the ``ambient``/``price``
    traces are threaded through schedule, utilization and read-out exactly
    like the engine's traced lanes.  A fleet of mixed server sizes
    (``dc.host_units``) schedules against each host's capacity, gangs jobs
    wider than the largest server when ``max_gang > 1`` and weights the
    utilization by capacity; ``p_idle``/``p_max`` may then be per-host
    lists.  ``job_hosts`` lists every job's hosts.
    """
    submit = list(workload["submit"])
    dur = list(workload["dur"])
    util = [list(row) for row in workload["util"]]
    cores = list(workload["cores"])
    valid = list(workload["valid"])
    defer = (None if workload.get("deferrable") is None
             else list(workload["deferrable"]))

    if scenario.arrival_scale != 1.0:
        # float32 on purpose: mirrors scenarios._perturb's rounding exactly
        submit = [int(np.floor(np.float32(s) / np.float32(
            scenario.arrival_scale))) for s in submit]
    if scenario.duration_scale != 1.0:
        dur = [max(int(np.ceil(np.float32(d) * np.float32(
            scenario.duration_scale))), 1) for d in dur]
    if scenario.util_scale != 1.0:
        util = [[min(max(u * scenario.util_scale, 0.0), 1.0) for u in row]
                for row in util]
    if scenario.shift_bins != 0:
        submit, dur, util, cores, valid, defer = apply_shift(
            submit, dur, util, cores, valid, defer, int(scenario.shift_bins))

    num_hosts = (scenario.num_hosts if scenario.num_hosts is not None
                 else dc.num_hosts)
    cores_per_host = (scenario.cores_per_host
                      if scenario.cores_per_host is not None
                      else dc.cores_per_host)
    policy = scenario.policy if scenario.policy is not None else "worst_fit"

    fs = fe = fk = None
    if scenario.failures:
        fs = [t_bins + 10 ** 6] * num_hosts  # sentinel: never fails
        fe = [0] * num_hosts
        fk = [False] * num_hosts
        for f in scenario.failures:
            fs[f.host] = int(f.start_bin)
            fe[f.host] = int(f.end_bin)
            fk[f.host] = f.kind == "outage"

    units = (None if dc.host_units is None
             else list(dc.host_units)[:num_hosts])
    job_start, job_hosts, gang_blocked = reference_gang_schedule(
        submit, dur, cores, valid, num_hosts=num_hosts,
        cores_per_host=cores_per_host, t_bins=t_bins, policy=policy,
        backfill_depth=int(scenario.backfill_depth),
        max_starts_per_bin=max_starts_per_bin,
        fail_start=fs, fail_end=fe, fail_kill=fk, host_capacity=units,
        max_gang=max_gang)
    job_host = [hs[0] if hs else -1 for hs in job_hosts]
    u_th = reference_u_th(
        job_start, submit, dur, cores, util, job_host,
        num_hosts=num_hosts, cores_per_host=cores_per_host, t_bins=t_bins,
        fail_start=fs, fail_kill=fk, job_hosts=job_hosts,
        host_capacity=units)
    online = None
    if fs is not None:
        # power-side availability: only *outage* hosts go dark (drained
        # hosts keep drawing power), matching scenarios._scenario_lanes
        online = [[not (fk[h] and fs[h] <= t < fe[h])
                   for h in range(num_hosts)] for t in range(t_bins)]
    pue = None
    if scenario.pue_base is not None:
        pue = (float(scenario.pue_base), float(scenario.pue_amb_coeff),
               float(scenario.pue_amb_ref), float(scenario.pue_load_coeff))
    out = reference_readout(
        u_th, p_idle=p_idle, p_max=p_max, r=r,
        power_cap_w=scenario.power_cap_w,
        carbon_cap_base_w=scenario.carbon_cap_base_w,
        carbon_cap_slope=scenario.carbon_cap_slope, intensity=intensity,
        online=online, pue=pue, ambient=ambient, price=price, units=units)
    out.update(
        job_start=job_start, job_host=job_host, job_hosts=job_hosts,
        gang_blocked_bins=gang_blocked, submit=submit, u_th=u_th,
        waits=[job_start[i] - submit[i] for i in range(len(submit))
               if valid[i] and job_start[i] >= 0])
    return out
