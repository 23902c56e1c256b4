"""Benchmark harness — one entry per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (harness contract), then a
human-readable summary per experiment.

  E1  (Fig. 4/5)  reproduce FootPrinter + extend with perf/efficiency
  E2  (Fig. 6)    self-calibration accuracy vs static simulation
  NFR2 (§3.1)     7 days twinned under 1 hour
  roofline        dry-run-derived roofline table (results/dryrun)
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import des_readout_bench  # noqa: E402
import e1_footprinter  # noqa: E402
import m3sa_metamodel  # noqa: E402
import e2_calibration  # noqa: E402
import fleet_bench  # noqa: E402
import nfr2_speed  # noqa: E402
import roofline  # noqa: E402
import serve_bench  # noqa: E402
import whatif_batch  # noqa: E402

#: committed what-if/scenario-engine performance snapshot (regenerate with
#: ``PYTHONPATH=src python benchmarks/run.py whatif``)
BENCH_WHATIF = os.path.join(os.path.dirname(__file__), "BENCH_whatif.json")

#: committed DES readout-kernel performance snapshot (regenerate with
#: ``PYTHONPATH=src python benchmarks/run.py des``)
BENCH_DES = os.path.join(os.path.dirname(__file__), "BENCH_des.json")

#: committed streaming-service performance snapshot (regenerate with
#: ``PYTHONPATH=src python benchmarks/run.py serve``)
BENCH_SERVE = os.path.join(os.path.dirname(__file__), "BENCH_serve.json")

#: committed fleet-axis engine snapshot (regenerate with
#: ``PYTHONPATH=src python benchmarks/run.py fleet``)
BENCH_FLEET = os.path.join(os.path.dirname(__file__), "BENCH_fleet.json")

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def lint_findings() -> int:
    """Standing tracecheck debt, recorded in snapshot provenance.

    Counts every post-suppression finding a fresh ``python -m tools.lint``
    run reports (baselined or new), so the perf trajectory also shows the
    contract-debt trend (tools/check_bench.py --compare prints the drift).
    """
    if _REPO_ROOT not in sys.path:
        sys.path.insert(0, _REPO_ROOT)
    from tools.lint.engine import DEFAULT_BASELINE, load_baseline, run_lint
    entries = (load_baseline(DEFAULT_BASELINE)
               if DEFAULT_BASELINE.exists() else [])
    res = run_lint(["src", "tests", "benchmarks", "tools"],
                   baseline_entries=entries)
    return len(res.findings)


def whatif_snapshot(days: float = 0.5) -> dict:
    """Write the scenario-engine performance snapshot to BENCH_whatif.json.

    Captures the steady-state numbers the what-if refactors are judged by:
    optimizer warm candidates/s (single compiled evaluator, asserted inside
    :func:`whatif_batch.run_optimizer`), the mixed new-axes grid's compile
    count (failure x PUE x price x cap — one program, asserted), mean
    closed-loop window-step seconds, and the DES hot-path scan/readout wall
    split that :mod:`analysis.roofline` prices against the hardware.

    Wall-clock numbers are machine-dependent — the committed snapshot is a
    reference point (backend/device count recorded alongside), not a gate;
    the compile counts are the invariants.
    """
    import jax

    from repro.core import run_surf_experiment
    from repro.traces.schema import DatacenterConfig
    from repro.traces.surf import BINS_PER_DAY, SurfTraceSpec, make_surf22_like

    opt = whatif_batch.run_optimizer(days=days)
    axes = whatif_batch.run_new_axes_grid(days=days)
    hot = nfr2_speed.des_hot_path()

    # mean window-step seconds: a 1-day calibrated closed loop, per-window
    # fused twin_step timings from the orchestrator's own records.
    dc = DatacenterConfig()
    w = make_surf22_like(SurfTraceSpec(days=1.0), dc)
    res = run_surf_experiment(w, dc, int(1.0 * BINS_PER_DAY), calibrate=True)
    steps = [r.sim_seconds for r in res.records]

    snap = {
        "regenerate_with": "PYTHONPATH=src python benchmarks/run.py whatif",
        "jax_version": jax.__version__,
        "backend": jax.default_backend(),
        "devices": len(jax.devices()),
        "lint_findings": lint_findings(),
        "optimizer": {
            "days": days,
            "candidates": opt["candidates"],
            "compiles": opt["compiles"],
            "warm_s": opt["warm_s"],
            "warm_candidates_per_s": opt["cand_per_s_warm"],
        },
        "new_axes_grid": axes,
        "window_step": {
            "windows": len(steps),
            "mean_seconds": float(np_mean(steps)),
            "max_seconds": float(max(steps)) if steps else None,
        },
        "des_hot_path": hot,
    }
    with open(BENCH_WHATIF, "w") as f:
        json.dump(snap, f, indent=2)
        f.write("\n")
    return snap


def des_snapshot(days: float = 0.5) -> dict:
    """Write the DES readout-kernel performance snapshot to BENCH_des.json.

    The PR-7 trajectory entry (ROADMAP open item 2): the DES hot path's
    scan/readout wall split, the readout microbench (legacy unfused vs
    fused-XLA vs Pallas, the latter interpret-mode on CPU and recorded as
    such), the end-to-end engine sweep on both readout paths, and the
    donated optimizer's warm candidates/s.  The compile counts are the
    gated invariants (``tools/check_bench.py --compare``); wall-clock
    numbers are machine-dependent reference points with the backend and
    device count recorded alongside.
    """
    import jax

    d = des_readout_bench.run(days=days)
    snap = {
        "regenerate_with": "PYTHONPATH=src python benchmarks/run.py des",
        "jax_version": jax.__version__,
        "backend": jax.default_backend(),
        "devices": len(jax.devices()),
        "lint_findings": lint_findings(),
        **d,
    }
    with open(BENCH_DES, "w") as f:
        json.dump(snap, f, indent=2)
        f.write("\n")
    return snap


def serve_snapshot() -> dict:
    """Write the streaming-service performance snapshot to BENCH_serve.json.

    The PR-9 trajectory entry (ROADMAP open item 1): warm serving rate
    (tenants/s and tenant-windows/s through ``TwinService``), batch fill
    ratio, the replay phase's cache hit rate, and the gated invariant —
    cold/warm/replay services all riding ONE compiled
    ``fleet_step_masked`` program.  Wall-clock numbers are
    machine-dependent reference points; the compile count is the gate.
    """
    import jax

    snap = {
        "regenerate_with": "PYTHONPATH=src python benchmarks/run.py serve",
        "jax_version": jax.__version__,
        "backend": jax.default_backend(),
        "devices": len(jax.devices()),
        "lint_findings": lint_findings(),
        "serve": serve_bench.run(),
    }
    with open(BENCH_SERVE, "w") as f:
        json.dump(snap, f, indent=2)
        f.write("\n")
    return snap


def fleet_snapshot() -> dict:
    """Write the fleet-axis engine snapshot to BENCH_fleet.json.

    The ROADMAP item-5 trajectory entry: warm window-step seconds on the
    vmap and sharded ``run_fleet`` paths, the per-path compile counts
    (ONE program each, warm re-run included — asserted in
    :mod:`fleet_bench` and schema-checked by ``tools/check_bench.py``),
    the sharded-vs-vmap bitwise cross-check, and lanes/device on this
    machine's mesh.  Wall clocks are machine-dependent reference points.
    """
    import jax

    snap = {
        "regenerate_with": "PYTHONPATH=src python benchmarks/run.py fleet",
        "jax_version": jax.__version__,
        "backend": jax.default_backend(),
        "devices": len(jax.devices()),
        "lint_findings": lint_findings(),
        "fleet": fleet_bench.run(),
    }
    with open(BENCH_FLEET, "w") as f:
        json.dump(snap, f, indent=2)
        f.write("\n")
    return snap


def np_mean(xs: list) -> float:
    return sum(xs) / len(xs) if xs else float("nan")


def main() -> None:
    rows: list[tuple[str, float, str]] = []

    e1 = e1_footprinter.run()
    rows.append((
        "e1_footprinter_reproduce",
        e1["wall_seconds"] * 1e6,
        f"fp_mape={e1['footprinter_mape']:.2f}%"
        f";opendt_mape={e1['opendt_mape']:.2f}%"
        f";paper=7.86%/5.13%"
        f";mean_util={e1['mean_utilization']:.3f}"
        f";best_eff={e1['best_efficiency_tflops_per_kwh']:.1f}TFLOPs/kWh",
    ))

    e2 = e2_calibration.run()
    rows.append((
        "e2_self_calibration",
        e2["wall_seconds"] * 1e6,
        f"uncal={e2['uncalibrated_mape']:.2f}%"
        f";cal={e2['calibrated_mape']:.2f}%"
        f";joint={e2['joint_calibrated_mape']:.2f}%"
        f";paper=5.13%/4.39%"
        f";nfr1_cal={e2['nfr1_calibrated']['compliance']:.2f}"
        f";nfr1_unc={e2['nfr1_uncalibrated']['compliance']:.2f}",
    ))

    n2 = nfr2_speed.run()
    rows.append((
        "nfr2_twin_speed",
        n2["closed_loop_wall_s"] * 1e6,
        f"7days_in={n2['closed_loop_wall_s']:.1f}s"
        f";paper=2760s;speedup={n2['speedup_vs_paper']:.0f}x"
        f";des_days_per_s={n2['sim_days_per_wall_second']:.1f}",
    ))
    rows.append((
        "calibration_grid",
        n2["calibration_window_s"] * 1e6,
        f"candidates_per_s={n2['calibration_candidates_per_s']:.0f}",
    ))

    m3 = m3sa_metamodel.run()
    rows.append((
        "m3sa_multi_model",
        0.0,
        f"opendc={m3['model_opendc_mape']:.2f}%"
        f";linear={m3['model_linear_mape']:.2f}%"
        f";weighted_meta={m3['meta_weighted_mape']:.2f}%"
        f";weights={m3['weights']}",
    ))

    wi = whatif_snapshot()
    rows.append((
        "whatif_snapshot",
        wi["window_step"]["mean_seconds"] * 1e6,
        f"cand_per_s={wi['optimizer']['warm_candidates_per_s']:.1f}"
        f";opt_compiles={wi['optimizer']['compiles']}"
        f";axes_compiles={wi['new_axes_grid']['compiles']}"
        f";scan_frac={wi['des_hot_path']['scan_fraction']:.2f}",
    ))

    de = des_snapshot()
    rows.append((
        "des_snapshot",
        de["readout_microbench"]["fused_xla_s"] * 1e6,
        f"fused_vs_legacy="
        f"{de['readout_microbench']['fused_vs_legacy_speedup']:.2f}x"
        f";pallas_mode={de['readout_microbench']['pallas_mode']}"
        f";sweep_compiles={de['engine_sweep']['pallas_compiles']}"
        f";cand_per_s={de['optimizer']['cand_per_s_warm']:.1f}",
    ))

    sv = serve_snapshot()
    rows.append((
        "serve_snapshot",
        sv["serve"]["warm_s"] * 1e6,
        f"windows_per_s={sv['serve']['windows_per_s_warm']:.1f}"
        f";fill={sv['serve']['batch_fill_ratio']:.2f}"
        f";cache_hit_rate={sv['serve']['cache_hit_rate']:.2f}"
        f";compiles={sv['serve']['compiles']}",
    ))

    fl = fleet_snapshot()
    rows.append((
        "fleet_snapshot",
        fl["fleet"]["sharded_window_step_s"] * 1e6,
        f"vmap_ms_per_window={fl['fleet']['vmap_window_step_s'] * 1e3:.1f}"
        f";sharded_ms_per_window="
        f"{fl['fleet']['sharded_window_step_s'] * 1e3:.1f}"
        f";lanes_per_device={fl['fleet']['lanes_per_device']}"
        f";compiles={fl['fleet']['vmap_compiles']}"
        f"+{fl['fleet']['sharded_compiles']}"
        f";bitwise={fl['fleet']['sharded_bitwise_equal']}",
    ))

    cells = roofline.load_cells()
    summ = roofline.summarize(cells)
    rows.append((
        "dryrun_roofline",
        0.0,
        f"ok={summ['cells_ok']};skipped={summ['cells_skipped']}"
        f";errors={summ['cells_error']}"
        f";dominant={summ['dominant_counts']}",
    ))

    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")

    print("\n=== E1 (paper Fig. 4/5) ===")
    print(json.dumps(e1, indent=2))
    print("\n=== E2 (paper Fig. 6) ===")
    print(json.dumps({k: v for k, v in e2.items()
                      if not k.startswith("per_window")}, indent=2))
    print("\n=== Multi-model / Meta-Model (paper §2.2, M3SA) ===")
    print(json.dumps(m3, indent=2))
    print("\n=== NFR2 ===")
    print(json.dumps(n2, indent=2))
    print("\n=== Roofline (results/dryrun) ===")
    print(roofline.table(cells))
    print(json.dumps(summ, indent=2))
    print(f"\n=== What-if snapshot (written to {BENCH_WHATIF}) ===")
    print(json.dumps(wi, indent=2))
    print(f"\n=== DES readout snapshot (written to {BENCH_DES}) ===")
    print(json.dumps(de, indent=2))
    print(f"\n=== Streaming-service snapshot (written to {BENCH_SERVE}) ===")
    print(json.dumps(sv, indent=2))
    print(f"\n=== Fleet-axis snapshot (written to {BENCH_FLEET}) ===")
    print(json.dumps(fl, indent=2))


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    if len(sys.argv) > 1 and sys.argv[1] == "whatif":
        print(json.dumps(whatif_snapshot(), indent=2))
    elif len(sys.argv) > 1 and sys.argv[1] == "des":
        print(json.dumps(des_snapshot(), indent=2))
    elif len(sys.argv) > 1 and sys.argv[1] == "serve":
        print(json.dumps(serve_snapshot(), indent=2))
    elif len(sys.argv) > 1 and sys.argv[1] == "fleet":
        print(json.dumps(fleet_snapshot(), indent=2))
    else:
        main()
