"""The Philly-like GPU-cluster trace, a copy kept with the benchmark.

A copy of ``repro.traces.philly.make_philly_like`` and of the server table
of ``repro.configs.philly`` in numpy, so that the yardstick does not move
when the program does; ``chipbench/tests/test_chipbench_gang.py`` holds it
against the program's.  Nothing here imports the program.
"""

from __future__ import annotations

import numpy as np

from chipbench.gen import BINS_PER_DAY, PAD_SUBMIT


def capacity(servers) -> np.ndarray:
    """``[H]`` GPUs per host of ``[[count, gpus, idle W, peak W], ...]``."""
    return np.concatenate([np.full(n, g, np.int32)
                           for n, g, _, _ in servers])


def power_rows(servers) -> tuple[np.ndarray, np.ndarray]:
    """``([H] idle W, [H] peak W)`` of each host by its server size."""
    return (np.concatenate([np.full(n, i, np.float32)
                            for n, _, i, _ in servers]),
            np.concatenate([np.full(n, p, np.float32)
                            for n, _, _, p in servers]))


def philly_like(seed: int, *, servers, days: float = 7.0,
                jobs_per_day: float = 1500.0,
                gpu_sizes=(1, 2, 4, 8, 16, 32, 64),
                gpu_shares=(0.70, 0.10, 0.08, 0.07, 0.03, 0.015, 0.005),
                target_demand: float = 0.70, duration_sigma: float = 1.2,
                median_per_doubling: float = 0.2,
                sigma_per_doubling: float = 0.1,
                gang_util_scale: float = 0.75, num_phases: int = 8,
                max_jobs: int = 12288) -> dict:
    """The job trace as numpy arrays, FCFS-sorted, padded to ``max_jobs``:
    ``submit``, ``dur``, ``cores`` (GPUs), ``util`` (``[J, U]``),
    ``valid`` and ``num_valid``."""
    rng = np.random.default_rng(seed)
    t_bins = int(round(days * BINS_PER_DAY))
    cap = capacity(servers)
    n = int(min(rng.poisson(jobs_per_day * days), max_jobs))
    shares = np.asarray(gpu_shares, np.float64)
    gpus = rng.choice(np.asarray(gpu_sizes, np.int32), n,
                      p=shares / shares.sum())
    doublings = np.log2(gpus)
    raw = rng.lognormal(median_per_doubling * doublings,
                        duration_sigma + sigma_per_doubling * doublings)
    scale = target_demand * int(cap.sum()) * t_bins / float(
        np.sum(gpus * raw))
    dur = np.clip(np.ceil(raw * scale), 1, t_bins).astype(np.int32)
    hour_weights = 0.5 + 0.5 * np.sin(
        np.linspace(0, 2 * np.pi, 24, endpoint=False) - np.pi / 2) ** 2
    day = rng.integers(0, max(1, int(days)), n)
    hour = rng.choice(24, n, p=hour_weights / hour_weights.sum())
    minute_bin = rng.integers(0, BINS_PER_DAY // 24, n)
    submit = np.minimum(day * BINS_PER_DAY + hour * (BINS_PER_DAY // 24)
                        + minute_bin, t_bins - 1).astype(np.int32)
    base = rng.beta(2.0, 1.6, size=(n, 1)).astype(np.float32)
    base = np.where(gpus[:, None] > cap.max(),
                    base * np.float32(gang_util_scale), base)
    wobble = rng.normal(0, 0.08, size=(n, num_phases)).astype(np.float32)
    ramp = np.linspace(0.6, 1.0, num_phases, dtype=np.float32)[None, :]
    util = np.clip(base * ramp + wobble, 0.05, 1.0).astype(np.float32)
    order = np.argsort(submit, kind="stable")
    pad = max_jobs - n
    return dict(
        submit=np.concatenate([submit[order],
                               np.full(pad, PAD_SUBMIT, np.int32)]),
        dur=np.concatenate([dur[order], np.ones(pad, np.int32)]),
        cores=np.concatenate([gpus[order], np.ones(pad, np.int32)]),
        util=np.concatenate([util[order],
                             np.zeros((pad, num_phases), np.float32)]),
        valid=np.arange(max_jobs) < n, num_valid=n)
