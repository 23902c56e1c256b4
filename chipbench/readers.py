"""Shared arithmetic of the per-layer metric readers in ``metrics/``."""

from __future__ import annotations

from chipbench.peaks import peak


def step_device_s(run, *programs: str) -> "float | None":
    """Device seconds of the named programs per request in the traced part."""
    if run.trace is None or run.trace_requests <= 0:
        return None
    t = run.trace.module_time(*programs)
    return None if t is None or t <= 0 else t / run.trace_requests


def step_device_ms(run, *programs: str) -> "float | None":
    t = step_device_s(run, *programs)
    return None if t is None else t * 1e3


def bw_roofline(run, *programs: str) -> "float | None":
    """Least time of a request's bytes at the chip's peak bandwidth, as a
    share (%) of the device time its programs took."""
    t = step_device_s(run, *programs)
    if t is None or run.min_bytes <= 0:
        return None
    least = run.min_bytes / peak(run.device_kind)["hbm_bytes_per_s"]
    return least / t * 100.0


def device_idle(run) -> "float | None":
    """Share (%) of the traced window in which no operation ran."""
    if run.trace is None or not run.trace.devices or run.trace.window_s <= 0:
        return None
    return (1.0 - run.trace.busy_s / run.trace.window_s) * 100.0


def mean_ms(values) -> "float | None":
    values = list(values or ())
    return sum(values) / len(values) * 1e3 if values else None
