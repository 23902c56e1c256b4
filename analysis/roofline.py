"""Live roofline of the masked-DES hot path (scan vs readout).

This module interrogates the **running** XLA compiler about the program
every scenario lane actually pays for — the masked placement scan
(``lax.scan`` over bins driving the policy kernel and the failure mask)
and the post-scan readout that expands placements into the dense
``[T, H]`` utilization grid.

Per phase it reports:

  * ``flops`` / ``bytes`` from ``jit(f).lower(x).compile().cost_analysis()``
    (XLA's own cost model — unavailable on some backends/versions, in which
    case the fields are ``None`` and only wall times are reported);
  * measured wall seconds, split with the same dead-code-elimination trick
    as :func:`benchmarks.nfr2_speed.des_hot_path` (a wrapper returning only
    ``job_start`` compiles the readout away);
  * derived achieved GFLOP/s, GB/s and arithmetic intensity (FLOP/byte) —
    the coordinates of each phase on a machine roofline.

Usage::

    PYTHONPATH=src python analysis/roofline.py
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.core.desim import simulate_utilization_masked
from repro.traces.schema import DatacenterConfig, host_mask
from repro.traces.surf import BINS_PER_DAY, SurfTraceSpec, make_surf22_like


def _time(fn, n: int = 5) -> float:
    fn()                                  # warmup / compile
    t0 = time.time()
    for _ in range(n):
        fn()
    return (time.time() - t0) / n


def xla_cost(fn, *args) -> dict | None:
    """``{"flops": ..., "bytes": ...}`` from XLA's compiled cost model.

    Guarded: ``cost_analysis`` is backend/version dependent (it may raise,
    return ``None``, or return a one-element list) — any failure degrades to
    ``None`` rather than breaking the benchmark run.
    """
    try:
        analysis = jax.jit(fn).lower(*args).compile().cost_analysis()
        if isinstance(analysis, (list, tuple)):
            analysis = analysis[0] if analysis else None
        if not analysis:
            return None
        return {
            "flops": float(analysis.get("flops", 0.0)),
            "bytes": float(analysis.get("bytes accessed", 0.0)),
        }
    except Exception:
        return None


def _phase(name: str, cost: dict | None, wall_s: float) -> dict:
    out = {"name": name, "wall_s": wall_s,
           "flops": None, "bytes": None,
           "gflop_per_s": None, "gb_per_s": None, "flop_per_byte": None}
    if cost is not None:
        out["flops"], out["bytes"] = cost["flops"], cost["bytes"]
        if wall_s > 0:
            out["gflop_per_s"] = cost["flops"] / wall_s / 1e9
            out["gb_per_s"] = cost["bytes"] / wall_s / 1e9
        if cost["bytes"] > 0:
            out["flop_per_byte"] = cost["flops"] / cost["bytes"]
    return out


def analyze_des_hot_path(days: float = 2.0,
                         dc: DatacenterConfig | None = None) -> dict:
    """Roofline coordinates for the scan and readout phases of the DES."""
    dc = dc or DatacenterConfig()
    w = make_surf22_like(SurfTraceSpec(days=days), dc)
    t_bins = int(days * BINS_PER_DAY)
    mask = host_mask(dc.num_hosts, dc.num_hosts)
    cores = jnp.asarray(dc.cores_per_host, jnp.int32)
    kw = dict(max_hosts=dc.num_hosts, t_bins=t_bins)

    def scan_only(wl):
        return simulate_utilization_masked(wl, mask, cores, **kw).job_start

    def full(wl):
        return simulate_utilization_masked(wl, mask, cores, **kw).u_th

    scan_cost = xla_cost(scan_only, w)
    full_cost = xla_cost(full, w)
    readout_cost = None
    if scan_cost is not None and full_cost is not None:
        readout_cost = {
            "flops": max(full_cost["flops"] - scan_cost["flops"], 0.0),
            "bytes": max(full_cost["bytes"] - scan_cost["bytes"], 0.0),
        }

    scan_s = _time(lambda: jax.jit(scan_only)(w).block_until_ready())
    total_s = _time(lambda: jax.jit(full)(w).block_until_ready())
    readout_s = max(total_s - scan_s, 0.0)

    return {
        "days": days,
        "t_bins": t_bins,
        "num_hosts": dc.num_hosts,
        "jobs": int(w.duration_bins.shape[0]),
        "cost_analysis_available": full_cost is not None,
        "phases": [
            _phase("placement_scan", scan_cost, scan_s),
            _phase("post_scan_readout", readout_cost, readout_s),
            _phase("total", full_cost, total_s),
        ],
    }


def table(result: dict) -> str:
    hdr = (f"{'phase':20s} {'wall_s':>9s} {'GFLOP':>9s} {'GB':>9s} "
           f"{'GFLOP/s':>9s} {'GB/s':>8s} {'FLOP/B':>7s}")
    rows = [hdr, "-" * len(hdr)]

    def fmt(v, scale=1.0, spec=".3f"):
        return "--" if v is None else format(v / scale, spec)

    for p in result["phases"]:
        rows.append(
            f"{p['name']:20s} {p['wall_s']:9.4f} "
            f"{fmt(p['flops'], 1e9):>9s} {fmt(p['bytes'], 1e9):>9s} "
            f"{fmt(p['gflop_per_s']):>9s} {fmt(p['gb_per_s']):>8s} "
            f"{fmt(p['flop_per_byte'], 1.0, '.2f'):>7s}")
    return "\n".join(rows)


if __name__ == "__main__":
    import json

    res = analyze_des_hot_path()
    print(f"masked DES hot path: {res['t_bins']} bins x "
          f"{res['num_hosts']} hosts, {res['jobs']} jobs "
          f"(cost_analysis {'ok' if res['cost_analysis_available'] else 'n/a'})")
    print(table(res))
    print(json.dumps(res, indent=2))
