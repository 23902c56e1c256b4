"""Workload-trace schema.

Mirrors the OpenDC workload input format (fragments of jobs with CPU demand)
at the granularity the paper reads out (5-minute sampling).  A trace is a
struct-of-arrays over jobs — dense tensors, directly consumable by the
vectorized simulator.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array

#: industry-standard sampling granularity used throughout the paper (§3.3).
SAMPLE_SECONDS = 300.0  # 5 minutes


@dataclasses.dataclass(frozen=True)
class Workload:
    """A job trace, struct-of-arrays, SURF-22 shaped.

    Attributes:
      submit_bin: ``[J] int32`` — submission time, in 5-min bins from t0.
      duration_bins: ``[J] int32`` — runtime in bins (ceil).
      cores: ``[J] int32`` — units requested (cores, or GPUs on an
        accelerator fleet); wider than a host means a gang of whole servers
        of the largest size (see :func:`repro.core.desim.simulate_utilization_masked`).
      util_levels: ``[J, U] float32`` — piecewise utilization profile of the
        job over its lifetime, expressed as U equal-length phases of per-core
        utilization in [0, 1] (OpenDC "fragments").
      valid: ``[J] bool`` — padding mask (traces are padded to fixed J).
      deferrable: ``[J] bool`` or ``None`` — which jobs tolerate submission
        time-shifting (batch/background work vs. interactive).  ``None``
        means *all* jobs are deferrable — the permissive default keeps
        carbon-aware time-shift scenarios (``Scenario.shift_bins``)
        available on traces that carry no deferability metadata.
    """

    submit_bin: Array
    duration_bins: Array
    cores: Array
    util_levels: Array
    valid: Array
    deferrable: Array | None = None

    @property
    def num_jobs(self) -> int:
        return int(self.submit_bin.shape[0])

    @property
    def num_phases(self) -> int:
        return int(self.util_levels.shape[1])

    def cpu_hours(self) -> Array:
        """Total CPU-hours per job (core-hours, the SURF-22 reporting unit)."""
        hours = self.duration_bins.astype(jnp.float32) * (SAMPLE_SECONDS / 3600.0)
        return jnp.where(self.valid, hours * self.cores.astype(jnp.float32), 0.0)


jax.tree_util.register_pytree_node(
    Workload,
    lambda w: ((w.submit_bin, w.duration_bins, w.cores, w.util_levels,
                w.valid, w.deferrable), None),
    lambda _, c: Workload(*c),
)


@dataclasses.dataclass(frozen=True)
class DatacenterConfig:
    """Static topology of the twinned datacenter (paper §3.2: SURF-SARA).

    A fleet of one server size gives ``cores_per_host`` units to every
    host.  A fleet of mixed sizes (a GPU cluster with 8- and 2-GPU servers)
    lists each host's capacity in ``host_units``: ``num_hosts`` is then its
    length and ``cores_per_host`` its largest entry, the server size a gang
    job takes whole.  ``unit_tflops`` is the peak of one unit (a GPU);
    unset, a unit is a core at ``ghz`` x ``flops_per_cycle``.

    >>> DatacenterConfig(num_hosts=3, cores_per_host=8, host_units=(8, 2, 2),
    ...                  unit_tflops=12.0).peak_tflops
    144.0
    >>> DatacenterConfig(num_hosts=2, cores_per_host=8, host_units=(8, 2, 2))
    Traceback (most recent call last):
        ...
    ValueError: host_units lists 3 hosts but num_hosts is 2
    """

    num_hosts: int = 277
    cores_per_host: int = 16
    ghz: float = 2.1
    mem_gb: float = 128.0
    #: double-precision FLOPs per core per cycle (FMA width) — used for the
    #: TFLOPs performance metric in E1's extension (Fig. 5B).
    flops_per_cycle: float = 16.0
    #: per-host capacity in units, for a fleet of mixed server sizes
    host_units: tuple[int, ...] | None = None
    #: peak TFLOP/s of one unit; None: one core's
    unit_tflops: float | None = None

    def __post_init__(self):
        if self.host_units is None:
            return
        units = tuple(int(u) for u in self.host_units)
        # a restored checkpoint hands back a list: keep the config hashable
        object.__setattr__(self, "host_units", units)
        if len(units) != self.num_hosts:
            raise ValueError(f"host_units lists {len(units)} hosts but "
                             f"num_hosts is {self.num_hosts}")
        if min(units) < 1 or max(units) != self.cores_per_host:
            raise ValueError(
                f"host_units must be >= 1 with cores_per_host "
                f"({self.cores_per_host}) its largest entry, got "
                f"{min(units)}..{max(units)}")

    @property
    def unit_peak_tflops(self) -> float:
        """Peak TFLOP/s of one unit (a core, or the stated ``unit_tflops``)."""
        if self.unit_tflops is not None:
            return float(self.unit_tflops)
        return self.ghz * 1e9 * self.flops_per_cycle / 1e12

    @property
    def peak_tflops(self) -> float:
        """Peak datacenter TFLOP/s at 100 % utilization: every unit of
        every host at its peak."""
        if self.host_units is None and self.unit_tflops is None:
            return (
                self.num_hosts * self.cores_per_host * self.ghz * 1e9
                * self.flops_per_cycle
            ) / 1e12
        return sum(self.host_units or (self.cores_per_host,) * self.num_hosts
                   ) * self.unit_peak_tflops


def stack_workloads(ws: "list[Workload] | tuple[Workload, ...]") -> Workload:
    """Stack S workloads into one batched Workload with leaves ``[S, J, ...]``.

    Workloads with differing job counts are first padded (see
    :func:`pad_workload`) to the common maximum so every scenario is
    shape-identical — the precondition for vmapping the DES over the
    scenario axis (``repro.core.scenarios``).
    """
    if not ws:
        raise ValueError("need at least one workload to stack")
    to_jobs = max(w.num_jobs for w in ws)
    padded = [pad_workload(w, to_jobs) for w in ws]
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *padded)


def host_mask(num_hosts: "int | np.ndarray | Array", max_hosts: int) -> Array:
    """Active-host mask(s) ``[..., max_hosts]`` for a padded host axis.

    ``num_hosts`` may be a scalar (one mask) or an ``[S]`` vector (a mask per
    scenario).
    """
    n = jnp.asarray(num_hosts, jnp.int32)
    return jnp.arange(max_hosts, dtype=jnp.int32) < n[..., None]


def pad_workload(w: Workload, to_jobs: int) -> Workload:
    """Pad a workload to a fixed job count (static shapes for jit)."""
    j = w.num_jobs
    if j >= to_jobs:
        return w
    pad = to_jobs - j

    def _pad(x, fill=0):
        widths = [(0, pad)] + [(0, 0)] * (x.ndim - 1)
        return jnp.pad(x, widths, constant_values=fill)

    return Workload(
        submit_bin=_pad(w.submit_bin, np.iinfo(np.int32).max // 4),
        duration_bins=_pad(w.duration_bins, 1),
        cores=_pad(w.cores, 1),
        util_levels=_pad(w.util_levels, 0.0),
        valid=_pad(w.valid, False),
        deferrable=(None if w.deferrable is None
                    else _pad(w.deferrable, False)),
    )
