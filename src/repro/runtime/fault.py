"""Host-failure schedules for the DES.

:class:`HostFailure` / :func:`failure_arrays` are *scenario-axis* failure
schedules.  A tuple of per-host outage/degradation windows becomes three
dense ``[max_hosts]`` arrays (start, end, kill-flag) the batched DES
folds into a time-varying host mask, so "rack 3 dies at noon" is one
traced lane of a what-if batch.
"""

from __future__ import annotations

import dataclasses

import numpy as np

#: schedule sentinel for "this host never fails": the window start sits
#: past any representable bin, so every `start <= t < end` test is false
#: and the compiled program is bit-for-bit the no-failure program.
NEVER_BIN = np.iinfo(np.int32).max

#: failure kinds: an OUTAGE kills running jobs and draws no power for the
#: window; a DEGRADED host drains — no new placements, but running jobs
#: finish normally and the host keeps drawing power.
OUTAGE = "outage"
DEGRADED = "degraded"


@dataclasses.dataclass(frozen=True)
class HostFailure:
    """One per-host failure window ``[start_bin, end_bin)`` on the DES clock.

    ``kind="outage"`` models a hard failure: jobs running on the host at
    ``start_bin`` are killed (their cores come back when the host does,
    at ``end_bin``), the host accepts no placements and draws no power
    during the window.  ``kind="degraded"`` models a drain/slow host:
    no *new* placements land during the window, but running jobs keep
    running and the host keeps drawing power.
    """

    host: int
    start_bin: int
    end_bin: int
    kind: str = OUTAGE

    def __post_init__(self):
        if self.host < 0:
            raise ValueError(f"failure host must be >= 0, got {self.host}")
        if not 0 <= self.start_bin < self.end_bin:
            raise ValueError(
                f"failure window must satisfy 0 <= start < end, got "
                f"[{self.start_bin}, {self.end_bin})")
        if self.kind not in (OUTAGE, DEGRADED):
            raise ValueError(
                f"failure kind must be {OUTAGE!r} or {DEGRADED!r}, "
                f"got {self.kind!r}")


def failure_arrays(failures, max_hosts: int
                   ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Dense ``[max_hosts]`` (start, end, kill) arrays from a failure tuple.

    Hosts without a window get the ``NEVER_BIN`` sentinel start (and end
    0), so the traced comparisons are false at every bin — disabled lanes
    in a mixed batch run the exact no-failure program.  One window per
    host: the DES carries a single (start, end) pair per host, so
    overlapping schedules must be merged by the caller.
    """
    fs = np.full(max_hosts, NEVER_BIN, np.int32)
    fe = np.zeros(max_hosts, np.int32)
    kill = np.zeros(max_hosts, bool)
    for f in failures:
        if f.host >= max_hosts:
            raise ValueError(
                f"failure host {f.host} out of range for {max_hosts} hosts")
        if fs[f.host] != NEVER_BIN:
            raise ValueError(
                f"host {f.host} has multiple failure windows; the DES "
                "carries one window per host — merge them first")
        fs[f.host] = f.start_bin
        fe[f.host] = f.end_bin
        kill[f.host] = f.kind == OUTAGE
    return fs, fe, kill
