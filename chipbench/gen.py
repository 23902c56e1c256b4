"""Traffic generators of the benchmark, in numpy, seeded.

Copies of the program's own generators, kept here so that the yardstick
does not move when the program does:

* :func:`surf22_like` -- ``repro.traces.surf.make_surf22_like`` (the
  SURF-22 LISA surrogate), with a cap on the job count and padding to it;
* :func:`ground_truth` -- ``repro.traces.surf.synthesize_ground_truth``,
  the hidden power model behind the measured telemetry, in float64;
* :func:`diurnal_carbon`, :func:`diurnal_ambient`, :func:`diurnal_price`
  -- ``repro.traces.{carbon,thermal,price}.make_diurnal_*``;
* :func:`synthetic_window` and :func:`due_times` -- the window draw and the
  due-time schedule of ``repro.serve.producers.SyntheticProducer``.

``chipbench/tests/test_chipbench_copies.py`` holds each copy against the
program's version.  Nothing here imports the program.
"""

from __future__ import annotations

import numpy as np

SAMPLE_SECONDS = 300.0
BINS_PER_DAY = 288
#: submit bin of padding jobs (``repro.traces.schema.pad_workload``)
PAD_SUBMIT = np.iinfo(np.int32).max // 4


def surf22_like(seed: int, *, num_hosts: int = 277, cores_per_host: int = 16,
                days: float = 7.0, mean_cpu_hours: float = 39.52,
                duration_sigma: float = 1.1, target_utilization: float = 0.28,
                num_phases: int = 8, max_jobs: int | None = None) -> dict:
    """The SURF-22-like job trace as numpy arrays, FCFS-sorted.

    Draws jobs until the core-bin demand of ``target_utilization`` is met,
    or until ``max_jobs`` jobs are drawn, whichever comes first; then pads
    to ``max_jobs`` with invalid jobs, so every seed gives the same shapes.
    Returns ``submit``, ``dur``, ``cores``, ``util`` (``[J, U]``), ``valid``
    and ``num_valid``.
    """
    rng = np.random.default_rng(seed)
    t_bins = int(round(days * BINS_PER_DAY))
    total_core_bins = num_hosts * cores_per_host * t_bins * target_utilization
    mean_bins = mean_cpu_hours * 3600.0 / SAMPLE_SECONDS
    mu = np.log(mean_bins) - duration_sigma ** 2 / 2.0
    hour_weights = 0.5 + 0.5 * np.sin(
        np.linspace(0, 2 * np.pi, 24, endpoint=False) - np.pi / 2) ** 2
    jobs = []
    mass = 0.0
    while mass < total_core_bins and (max_jobs is None
                                      or len(jobs) < max_jobs):
        core_bins = float(rng.lognormal(mu, duration_sigma))
        cores = int(min(cores_per_host, max(1, rng.geometric(0.35))))
        dur = int(np.clip(round(core_bins / cores), 1, t_bins))
        day = rng.integers(0, max(1, int(days)))
        hour = rng.choice(24, p=hour_weights / hour_weights.sum())
        minute_bin = rng.integers(0, BINS_PER_DAY // 24)
        submit = int(day * BINS_PER_DAY + hour * (BINS_PER_DAY // 24)
                     + minute_bin)
        jobs.append((min(submit, t_bins - 1), dur, cores))
        mass += dur * cores
    j = len(jobs)
    submit = np.array([x[0] for x in jobs], np.int32)
    dur = np.array([x[1] for x in jobs], np.int32)
    cores = np.array([x[2] for x in jobs], np.int32)
    base = rng.beta(2.2, 1.3, size=(j, 1)).astype(np.float32)
    wobble = rng.normal(0, 0.08, size=(j, num_phases)).astype(np.float32)
    ramp = np.linspace(0.6, 1.0, num_phases, dtype=np.float32)[None, :]
    util = np.clip(base * ramp + wobble, 0.05, 1.0)
    order = np.argsort(submit, kind="stable")
    out = dict(submit=submit[order], dur=dur[order], cores=cores[order],
               util=util[order], valid=np.ones((j,), bool), num_valid=j)
    if max_jobs is not None and j < max_jobs:
        pad = max_jobs - j
        out.update(
            submit=np.concatenate([out["submit"],
                                   np.full(pad, PAD_SUBMIT, np.int32)]),
            dur=np.concatenate([out["dur"], np.ones(pad, np.int32)]),
            cores=np.concatenate([out["cores"], np.ones(pad, np.int32)]),
            util=np.concatenate([out["util"],
                                 np.zeros((pad, num_phases), np.float32)]),
            valid=np.concatenate([out["valid"], np.zeros(pad, bool)]))
    return out


def opendc_power(u, p_idle, p_max, r):
    """OpenDC host power ``P_idle + (P_max - P_idle)(2u - u^r)``, u clipped."""
    u = np.clip(u, 0.0, 1.0)
    return p_idle + (p_max - p_idle) * (2.0 * u - np.power(u, r))


def ground_truth(u_th, *, seed: int = 7, p_idle_mean: float = 71.5,
                 p_idle_spread: float = 6.0, p_max_mean: float = 362.0,
                 p_max_spread: float = 18.0, r_start: float = 1.45,
                 r_end: float = 3.40, r_diurnal: float = 0.10,
                 wander_daily_sigma: float = 0.02,
                 noise_active_frac: float = 0.10,
                 noise_total_frac: float = 0.006, step_day: float = 4.5,
                 step_frac: float = 0.05) -> np.ndarray:
    """Measured power ``[T]`` (W) of a hidden model driven by ``u_th [T, H]``.

    Per-host idle/max spread, a drifting exponent r*(t), a facility wander,
    a firmware step and heteroscedastic meter noise, in float64.
    """
    u = np.asarray(u_th, np.float64)
    t_bins, num_hosts = u.shape
    rng = np.random.default_rng(seed)
    p_idle_h = rng.normal(p_idle_mean, p_idle_spread, num_hosts)
    p_max_h = rng.normal(p_max_mean, p_max_spread, num_hosts)
    tt = np.linspace(0.0, 1.0, t_bins)
    days = max(t_bins / BINS_PER_DAY, 1.0)
    r_t = (r_start + (r_end - r_start) * tt
           + r_diurnal * np.sin(2 * np.pi * tt * days))
    total = opendc_power(u, p_idle_h[None, :], p_max_h[None, :],
                         r_t[:, None]).sum(axis=1)
    idle_floor = float(p_idle_h.sum())
    active = np.maximum(total - idle_floor, 0.0)
    step_sigma = wander_daily_sigma / np.sqrt(BINS_PER_DAY)
    wander = np.exp(np.cumsum(rng.normal(0.0, step_sigma, t_bins)))
    step = np.ones(t_bins)
    if step_day is not None:
        step_bin = int(step_day * BINS_PER_DAY)
        if 0 <= step_bin < t_bins:
            step[step_bin:] += step_frac
    noise = (rng.normal(0.0, 1.0, t_bins) * (noise_active_frac * active)
             + rng.normal(0.0, 1.0, t_bins) * (noise_total_frac * total))
    return total * wander * step + noise


def _tod(t_bins: int) -> np.ndarray:
    return (np.arange(t_bins) % BINS_PER_DAY) / BINS_PER_DAY


def _daily(seed, t_bins, draw) -> np.ndarray:
    rng = np.random.default_rng(seed)
    n_days = -(-t_bins // BINS_PER_DAY)
    return np.repeat(draw(rng, n_days), BINS_PER_DAY)[:t_bins]


def diurnal_carbon(t_bins: int, seed: int, *, base: float = 320.0,
                   solar_dip: float = 180.0, evening_peak: float = 120.0,
                   wander_daily_sigma: float = 0.04) -> np.ndarray:
    """Grid carbon intensity ``[T]`` (gCO2/kWh): solar dip, evening peak."""
    tod = _tod(t_bins)
    solar = np.clip(np.sin(np.pi * (tod * 24.0 - 7.0) / 12.0), 0.0, None) ** 2
    evening = np.exp(-0.5 * ((tod * 24.0 - 19.5) / 1.8) ** 2)
    out = base - solar_dip * solar + evening_peak * evening
    out = out * _daily(seed, t_bins, lambda rng, n: np.exp(
        rng.normal(0.0, wander_daily_sigma, n)))
    return np.maximum(out, 0.0).astype(np.float32)


def diurnal_ambient(t_bins: int, seed: int, *, base: float = 16.0,
                    amplitude: float = 8.0,
                    wander_daily_sigma: float = 0.5) -> np.ndarray:
    """Outside-air temperature ``[T]`` (deg C), peaking mid-afternoon."""
    out = base + amplitude * np.sin(2.0 * np.pi * (_tod(t_bins) * 24.0 - 9.0)
                                    / 24.0)
    out = out + _daily(seed, t_bins, lambda rng, n: rng.normal(
        0.0, wander_daily_sigma, n))
    return out.astype(np.float32)


def diurnal_price(t_bins: int, seed: int, *, base: float = 0.10,
                  night_discount: float = 0.06, evening_peak: float = 0.15,
                  wander_daily_sigma: float = 0.05) -> np.ndarray:
    """Electricity spot price ``[T]`` ($/kWh): cheap night, dear evening."""
    hours = _tod(t_bins) * 24.0
    night = np.exp(-0.5 * ((hours - 3.0) / 2.5) ** 2)
    evening = np.exp(-0.5 * ((hours - 19.0) / 2.0) ** 2)
    out = base - night_discount * night + evening_peak * evening
    out = out * _daily(seed, t_bins, lambda rng, n: rng.lognormal(
        0.0, wander_daily_sigma, n))
    return out.astype(np.float32)


def synthetic_window(seed: int, window: int, *, hosts: int,
                     bins_per_window: int, util_mean: float = 0.4,
                     hidden: tuple = (72.0, 365.0, 2.4),
                     noise: float = 0.01) -> tuple[np.ndarray, np.ndarray]:
    """One tenant window ``(u [Tw, H] float32, power [Tw] float32)``.

    A pure function of ``(seed, window)``: utilization drawn around
    ``util_mean``, power from the hidden OpenDC model plus meter noise.
    """
    rng = np.random.default_rng([seed, window])
    u = np.clip(rng.normal(util_mean, 0.15, (bins_per_window, hosts)),
                0.0, 1.0).astype(np.float32)
    p = opendc_power(u.astype(np.float64), *hidden).sum(axis=-1)
    p = (p * (1.0 + rng.normal(0.0, noise, p.shape))).astype(np.float32)
    return u, p


def due_times(seed: int, num_windows: int, *, start: float, period_s: float,
              jitter_s: float) -> np.ndarray:
    """Window due times ``start + (w + 1) * period + U[0, jitter)``."""
    rng = np.random.default_rng([seed, 0xD0])
    return (start + period_s * (np.arange(num_windows) + 1)
            + rng.uniform(0.0, jitter_s or 0.0, num_windows))
