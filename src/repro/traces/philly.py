"""Synthetic Philly-like GPU-cluster workload (gang-scheduled DNN training).

Jeon et al., *Analysis of Large-Scale Multi-Tenant GPU Clusters for DNN
Training Workloads* (USENIX ATC '19), studied Microsoft's Philly cluster
and published its trace (github.com/msr-fiddle/philly-traces), which is not
vendored in this offline container.  What the study fixes, and this
surrogate keeps: servers of two sizes (2 and 8 GPUs); most jobs ask for one
GPU; a job wider than a server is gang-scheduled on whole servers, all at
once or not at all, and waits for them (head-of-line blocking and
fragmentation are the study's central finding); GPU utilisation is low, and
lower for distributed jobs.

Every size the surrogate sets itself is an assumption, listed on
:class:`PhillyTraceSpec`: about 1,500 jobs a day on a diurnal arrival
curve, the GPU-count mix, log-normal durations with a heavier tail for
larger jobs scaled so that GPU demand is a set share of capacity, and
8-phase utilisation profiles drawn lower for gangs.  The deployment it
runs on is :func:`repro.configs.philly.config`.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from repro.traces.schema import SAMPLE_SECONDS, DatacenterConfig, Workload

#: bins per day at the 5-minute sampling granularity
BINS_PER_DAY = int(24 * 3600 / SAMPLE_SECONDS)  # 288


@dataclasses.dataclass(frozen=True)
class PhillyTraceSpec:
    """Knobs of the synthetic Philly surrogate (all assumed)."""

    days: float = 7.0
    jobs_per_day: float = 1500.0
    #: GPUs per job and their shares: most jobs use one GPU
    gpu_sizes: tuple[int, ...] = (1, 2, 4, 8, 16, 32, 64)
    gpu_shares: tuple[float, ...] = (0.70, 0.10, 0.08, 0.07, 0.03, 0.015,
                                     0.005)
    #: GPU-bins asked for over the horizon, as a share of the fleet's
    target_demand: float = 0.70
    #: log-normal sigma of a one-GPU job's duration
    duration_sigma: float = 1.2
    #: per doubling of the GPU count: longer median, heavier tail
    median_per_doubling: float = 0.2
    sigma_per_doubling: float = 0.1
    #: utilisation levels of gang jobs relative to single-server jobs
    gang_util_scale: float = 0.75
    #: jobs beyond this many are cut; fewer are padded to it
    max_jobs: int = 12288
    seed: int = 15


def make_philly_like(spec: PhillyTraceSpec = PhillyTraceSpec(),
                     dc: "DatacenterConfig | None" = None,
                     num_phases: int = 8) -> Workload:
    """Generate the Philly-like job trace, FCFS-sorted and padded to
    ``spec.max_jobs`` (numpy; host-side I/O).

    ``cores`` of the workload are GPUs; jobs wider than ``dc``'s largest
    server are gangs.  ``dc`` defaults to :func:`repro.configs.philly.config`.
    """
    if dc is None:
        from repro.configs.philly import config

        dc = config()
    rng = np.random.default_rng(spec.seed)
    t_bins = int(round(spec.days * BINS_PER_DAY))
    capacity = sum(dc.host_units or (dc.cores_per_host,) * dc.num_hosts)

    n = int(min(rng.poisson(spec.jobs_per_day * spec.days), spec.max_jobs))
    shares = np.asarray(spec.gpu_shares, np.float64)
    gpus = rng.choice(np.asarray(spec.gpu_sizes, np.int32), n,
                      p=shares / shares.sum())
    doublings = np.log2(gpus)
    raw = rng.lognormal(spec.median_per_doubling * doublings,
                        spec.duration_sigma
                        + spec.sigma_per_doubling * doublings)
    scale = spec.target_demand * capacity * t_bins / float(np.sum(gpus * raw))
    dur = np.clip(np.ceil(raw * scale), 1, t_bins).astype(np.int32)

    hour_weights = 0.5 + 0.5 * np.sin(
        np.linspace(0, 2 * np.pi, 24, endpoint=False) - np.pi / 2) ** 2
    day = rng.integers(0, max(1, int(spec.days)), n)
    hour = rng.choice(24, n, p=hour_weights / hour_weights.sum())
    minute_bin = rng.integers(0, BINS_PER_DAY // 24, n)
    submit = np.minimum(day * BINS_PER_DAY + hour * (BINS_PER_DAY // 24)
                        + minute_bin, t_bins - 1).astype(np.int32)

    base = rng.beta(2.0, 1.6, size=(n, 1)).astype(np.float32)
    base = np.where(gpus[:, None] > dc.cores_per_host,
                    base * np.float32(spec.gang_util_scale), base)
    wobble = rng.normal(0, 0.08, size=(n, num_phases)).astype(np.float32)
    ramp = np.linspace(0.6, 1.0, num_phases, dtype=np.float32)[None, :]
    util = np.clip(base * ramp + wobble, 0.05, 1.0).astype(np.float32)

    order = np.argsort(submit, kind="stable")
    pad = spec.max_jobs - n
    return Workload(
        submit_bin=jnp.asarray(np.concatenate(
            [submit[order], np.full(pad, np.iinfo(np.int32).max // 4,
                                    np.int32)])),
        duration_bins=jnp.asarray(np.concatenate(
            [dur[order], np.ones(pad, np.int32)])),
        cores=jnp.asarray(np.concatenate(
            [gpus[order], np.ones(pad, np.int32)])),
        util_levels=jnp.asarray(np.concatenate(
            [util[order], np.zeros((pad, num_phases), np.float32)])),
        valid=jnp.asarray(np.arange(spec.max_jobs) < n),
    )
