"""Philly-like GPU cluster (Jeon et al., USENIX ATC '19) for the twin.

552 servers: 231 with 8 GPUs (hosts 0-230) and 321 with 2 GPUs (hosts
231-551), 2,490 GPUs in all, 12 TFLOP/s per GPU.  The split, the per-GPU
peak and the per-server power curves are assumed; the study names two
server sizes, not their counts.
"""

import numpy as np

from repro.core.power import PowerParams
from repro.traces.schema import DatacenterConfig

#: (servers, GPUs each, idle W, peak W), largest servers first
SERVERS = ((231, 8, 800.0, 2600.0), (321, 2, 300.0, 900.0))


def config() -> DatacenterConfig:
    units = tuple(g for n, g, _, _ in SERVERS for _ in range(n))
    return DatacenterConfig(num_hosts=len(units), cores_per_host=max(units),
                            host_units=units, unit_tflops=12.0)


def power_params() -> PowerParams:
    """Per-host OpenDC power rows (r = 2): each server size its own idle
    and peak."""
    def row(i):
        return np.concatenate([np.full(s[0], s[i], np.float32)
                               for s in SERVERS])
    return PowerParams(p_idle=row(2), p_max=row(3), r=2.0)
